"""Submit to admission, as the engine stamps it on each request's last frame."""
from lib import metrics

LAYER, UNIT, MOVES = "engine: queue, admission", "ms", "ttft_p95_ms"


def read(run):
    waits = [r["queue_wait"] for r in run.get("measured") or [] if r.get("queue_wait") is not None]
    return 1e3 * metrics.quantile(waits, 0.9) if waits else None
