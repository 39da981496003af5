"""Seconds of Python in which jax TRACED the executables of the start: the
program's `compile/trace` spans, top level only (a jit traced inside another's
trace is inside that one's seconds, and counted by `setup_inner_traces`).
No compiler runs in them and no cache shortens them."""
from lib import setup_phases

LAYER, UNIT, MOVES = "compile cache", "s", "setup_s"


def read(run):
    return setup_phases.value(run, "trace_s")
