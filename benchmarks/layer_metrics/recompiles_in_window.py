"""Executor compilations after the warm-up."""
LAYER, UNIT, MOVES = "executor", "count", "train_tok_s"


def read(run):
    return run.get("recompiles")
