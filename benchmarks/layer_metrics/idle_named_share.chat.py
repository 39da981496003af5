"""Of the first chip's idle seconds in the traced window, the share whose gap
lies under a phase span of the program (a child of `serving/engine_step`, or
the driver's `serving/idle_wait`). Low means the spans miss where the chip
waits."""
from lib import program_spans

LAYER, UNIT, MOVES = "engine: queue, admission", "%", "tpot_mean_ms"


def read(run):
    return program_spans.idle_named_share(run)
