"""Where XLA's persistent compile cache lives.

The executor and the serving scheduler call `ensure_compile_cache()`
before they build their first jit, so a second process (or a second run
on the same machine) loads executables instead of compiling them again.
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
