"""Prefills per decode dispatch over the window."""
from lib import readers

LAYER, UNIT, MOVES = "scheduler", "count", "serve_tok_s"


def read(run):
    return readers.ratio(readers.delta(run, "prefills"), readers.delta(run, "dispatches"))
