"""Device self time under `ffn/dense` (LongCat-Flash's two dense SwiGLUs a layer, 12288
wide, in prompts and in steps) over device busy time. A run whose tables carry no such scope
reports nothing."""
from lib import scope_reduce

LAYER, UNIT, MOVES = "decode/prefill math", "%", "serve_tok_s"


def read(run):
    seconds = scope_reduce.scope_seconds(run, None, "ffn/")
    trace = run.get("trace")
    if seconds is None or not trace:
        return None
    return 100.0 * seconds / trace["busy_s"]
