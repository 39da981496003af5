"""The benchmark's own tests run on the CPU (four virtual devices for the
data-parallel rehearsal) and keep nothing in the compile cache. Nothing here
or in the test files touches jax while it is imported."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=4").strip()
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (os.path.dirname(BENCH), BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)
