"""Seconds in which the backend's COMPILER ran in the start: the program's
`compile/backend` spans of the executables the persistent cache did not hold.
About 0 on a warm start; with `setup_cache_load_s` it is the harness's
`compile_s` (the same jax events)."""
from lib import setup_phases

LAYER, UNIT, MOVES = "compile cache", "s", "setup_s"


def read(run):
    return setup_phases.value(run, "backend_compile_s")
