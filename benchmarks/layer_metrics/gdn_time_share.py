"""Device self time under the `gdn/*` scopes (the Gated-DeltaNet mixer: its two projections,
the convolution, the l2 norms and the decay, the recurrence's read-modify-write of the state
or the prefill's chunked scan, the gated norm and the output projection) over device busy
time. A program without the scopes reports nothing."""
from lib import scope_reduce

LAYER, UNIT, MOVES = "gated delta-rule mixer", "%", "serve_tok_s"


def read(run):
    seconds = scope_reduce.scope_seconds(run, None, "gdn/")
    trace = run.get("trace")
    if seconds is None or not trace:
        return None
    return 100.0 * seconds / trace["busy_s"]
