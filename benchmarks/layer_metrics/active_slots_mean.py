"""Slots in use, sampled every 50 ms over the window."""
from lib import readers

LAYER, UNIT, MOVES = "engine: queue, admission", "slots", "tpot_mean_ms"


def read(run):
    return readers.mean(readers.samples(run, 1))
