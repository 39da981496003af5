"""Device self time under the `kda/*` scopes (the linear-attention mixer: its projections,
the convolution and the gates, the recurrence's read-modify-write of the state or the
prefill's chunked scan, the output gate) over device busy time. A program without the
scopes reports nothing."""
from lib import scope_reduce

LAYER, UNIT, MOVES = "linear attention", "%", "serve_tok_s"


def read(run):
    seconds = scope_reduce.scope_seconds(run, None, "kda/")
    trace = run.get("trace")
    if seconds is None or not trace:
        return None
    return 100.0 * seconds / trace["busy_s"]
