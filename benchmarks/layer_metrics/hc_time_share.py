"""Device self time of the operations under the program's `hc/*` scopes (the
residual mixer's coefficients, its input and its update of the streams) over
device busy time. The by-scope tables are the mode's (`serve-closed-xing` reads
the trace with the `hc/` scopes known); a run without them reports nothing."""
from lib import scope_reduce

LAYER, UNIT, MOVES = "residual streams", "%", "serve_tok_s"


def read(run):
    seconds = scope_reduce.scope_seconds(run, None, "hc/")
    trace = run.get("trace")
    if seconds is None or not trace:
        return None
    return 100.0 * seconds / trace["busy_s"]
