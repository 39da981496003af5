"""Device self time of the operations under the program's `attn/full` scope (a full
layer's attention: the flash forward over the whole triangle in a prefill, the paged
kernel's walk over every page in a decode step) over device busy time."""
from lib import scope_reduce

LAYER, UNIT, MOVES = "attention (grouped heads, window + full)", "%", "serve_tok_s"


def read(run):
    seconds = scope_reduce.scope_seconds(run, None, "attn/full")
    trace = run.get("trace")
    if seconds is None or not trace:
        return None
    return 100.0 * seconds / trace["busy_s"]
