"""Executables the program compiled or loaded before the window opened:
records of its compile log that began before `t0` and reached the backend,
probes (a second lowering for a cost analysis) excluded; a trace alone (a
jitted function under `jax.eval_shape`) is no executable."""
from lib import setup_phases

LAYER, UNIT, MOVES = "compile cache", "count", "setup_s"


def read(run):
    return setup_phases.value(run, "executables")
