"""Seconds in which jax lowered the start's traced programs to MLIR modules:
the program's `compile/lower` spans. Paid on every start, cache or no cache."""
from lib import setup_phases

LAYER, UNIT, MOVES = "compile cache", "s", "setup_s"


def read(run):
    return setup_phases.value(run, "lower_s")
