"""Device self time of the operations under the program's `moe/shared` scope (the
shared experts, which every chip of an expert-parallel deployment computes alike: one
SwiGLU four experts wide here) over device busy time."""
from lib import scope_reduce

LAYER, UNIT, MOVES = "routed and shared experts", "%", "serve_tok_s"


def read(run):
    seconds = scope_reduce.scope_seconds(run, None, "moe/shared")
    trace = run.get("trace")
    if seconds is None or not trace:
        return None
    return 100.0 * seconds / trace["busy_s"]
