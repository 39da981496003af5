"""Device self time of the operations under the program's `attn/window` scope (a
sliding layer's attention: the banded flash forward of a prefill, the paged kernel's
walk over the ring in a decode step) over device busy time. The by-scope tables are
the mode's (`serve-closed-mellum` reads the trace with the `attn/` scopes known); a
run without them reports nothing."""
from lib import scope_reduce

LAYER, UNIT, MOVES = "attention (grouped heads, window + full)", "%", "serve_tok_s"


def read(run):
    seconds = scope_reduce.scope_seconds(run, None, "attn/window")
    trace = run.get("trace")
    if seconds is None or not trace:
        return None
    return 100.0 * seconds / trace["busy_s"]
