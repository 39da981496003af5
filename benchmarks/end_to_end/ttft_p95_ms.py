"""95th percentile over the measured set of first token received minus due."""
from lib import metrics

UNIT, BETTER = "ms", "lower"


def read(run):
    return metrics.ttft_p95_ms(run["measured"])
