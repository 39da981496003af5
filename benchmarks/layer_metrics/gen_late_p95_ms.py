"""How late the generator sent a request: sent minus due, 95th percentile."""
from lib import metrics

LAYER, UNIT, MOVES = "load generator (benchmark)", "ms", "ttft_p95_ms"


def read(run):
    late = [r["sent"] - r["due"] for r in run.get("measured") or []]
    return 1e3 * metrics.quantile(late, 0.95) if late else None
