"""Prompt and generated tokens completed, between completion events."""
from lib import metrics

UNIT, BETTER = "tokens/s", "higher"


def read(run):
    return metrics.serve_tok_s(run["records"], run["t0"], run["seconds"])
