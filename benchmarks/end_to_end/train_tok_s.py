"""Tokens of the steps completing in the window, between completion events."""
from lib import metrics

UNIT, BETTER = "tokens/s", "higher"


def read(run):
    return metrics.train_tok_s(run["steps"], run["t0"], run["seconds"])
