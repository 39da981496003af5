"""Where XLA's persistent compile cache lives.

`import paddle_tpu` calls `ensure_compile_cache()` as its last act, beside
the compile log's installation, and nobody else has to: every executable
the process makes after the import goes through the cache, the caller's
own first of all (weights built or loaded on the device before any
Executor or engine exists), so a second process on the same machine loads
them instead of compiling them again. Until PR 58 the directory was set by
`Executor.__init__` and the scheduler's `_ensure_jits`, after a server's
weights had been compiled with no cache to keep them; those calls are gone.
Setting the directory touches no device and creates nothing on disk (jax
opens the cache at the first compile, and not at all where
`jax_enable_compilation_cache` is false).
"""

import os

__all__ = ["ensure_compile_cache"]

# <checkout>/.jax_cache. A cache only hits if every run looks in the
# same place, so the path comes from where the package sits and from
# nothing that changes between runs (no tempfile, pid or clock).
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def ensure_compile_cache() -> str:
    """Returns the directory compiled executables persist in. When
    JAX_COMPILATION_CACHE_DIR is set, jax reads it itself and nothing is
    touched; a directory the caller already configured is kept too.
    Otherwise the cache goes to `<checkout>/.jax_cache`."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    configured = jax.config.jax_compilation_cache_dir
    if configured:
        return configured
    jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE_DIR)
    return _CHECKOUT_CACHE_DIR
