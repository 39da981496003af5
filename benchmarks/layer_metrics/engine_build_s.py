"""Seconds constructing the serving engine: the program's `serving/engine_build`
span (weights as served, the arena and its pools, the scheduler); the jitted
entry points are made at the first request, on the drive thread, under
`serving/engine_build/jits`, which is in `setup_named_share`'s union."""
from lib import setup_phases

LAYER, UNIT, MOVES = "compile cache", "s", "setup_s"


def read(run):
    return setup_phases.value(run, "engine_build_s")
