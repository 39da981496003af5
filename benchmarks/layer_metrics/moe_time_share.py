"""Device self time of the operations under the program's `moe/*` scopes
(router, dispatch, the grouped expert products, the shared expert, combine)
over device busy time."""
from lib import scope_reduce

LAYER, UNIT, MOVES = "routed and shared experts", "%", "serve_tok_s"


def read(run):
    seconds = scope_reduce.scope_seconds(run, None, "moe/")
    trace = run.get("trace")
    if seconds is None or not trace:
        return None
    return 100.0 * seconds / trace["busy_s"]
