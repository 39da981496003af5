"""The shortcut-connected expert layer's share of a STEP: device self time under `moe/*`
(router, dispatch, the grouped expert products, the identity term, combine) inside
`jit_chunk_impl` over that program's own device time in the traced window. `moe_time_share`
is the same scopes over all busy time, prompts included; this is the share of the steps
alone, which ISSUE 50 predicted at a third. The branch has no edge to the dense half it runs
beside, so the share is of self time wherever the compiler put it. A trace whose tables
carry no program time (another mode's) reports nothing."""
from lib import scope_reduce

LAYER, UNIT, MOVES = "routed and shared experts", "%", "serve_tok_s"


def read(run):
    seconds = scope_reduce.scope_seconds(run, "jit_chunk_impl", "moe/")
    whole = ((run.get("scopes") or {}).get("jit_chunk_impl") or {}).get("busy_s")
    if not seconds or not whole:
        return None
    return 100.0 * seconds / whole
