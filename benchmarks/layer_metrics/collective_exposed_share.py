"""Collective time with no compute running on that chip, over the window."""
LAYER, UNIT, MOVES = "data parallel training", "%", "train_tok_s"


def read(run):
    trace = run.get("trace")
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"] if trace else None
