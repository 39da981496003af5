"""Token-weighted time between tokens after the first, over the measured set."""
from lib import metrics

UNIT, BETTER = "ms", "lower"


def read(run):
    return metrics.tpot_mean_ms(run["measured"])
