"""Device self time under the `ssd/*` scopes (the state-space mixer: its two projections,
the convolution and dt's softplus, the prompt's chunked scan or the step's
read-modify-write of the state, the gated norm) over device busy time. A program without
the scopes reports nothing."""
from lib import scope_reduce

LAYER, UNIT, MOVES = "state-space mixer", "%", "serve_tok_s"


def read(run):
    seconds = scope_reduce.scope_seconds(run, None, "ssd/")
    trace = run.get("trace")
    if seconds is None or not trace:
        return None
    return 100.0 * seconds / trace["busy_s"]
