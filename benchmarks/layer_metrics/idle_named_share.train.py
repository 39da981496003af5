"""Of the first chip's idle seconds in the traced window, the share whose gap
lies under a phase span of the program (a child of `executor/run`). Low means
the spans miss where the chip waits."""
from lib import program_spans

LAYER, UNIT, MOVES = "executor", "%", "train_tok_s"


def read(run):
    return program_spans.idle_named_share(run)
