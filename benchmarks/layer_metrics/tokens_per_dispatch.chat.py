"""Tokens emitted per fused decode dispatch over the window."""
from lib import readers

LAYER, UNIT, MOVES = "scheduler", "tokens", "tpot_mean_ms"


def read(run):
    return readers.ratio(readers.delta(run, "tokens_out"), readers.delta(run, "dispatches"))
