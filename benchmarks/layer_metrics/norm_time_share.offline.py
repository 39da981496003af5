"""Device self time under `norm` (every block's two layer norms, float32
statistics over bfloat16 rows; the final one is `head`'s) over busy time."""
from lib import stage_times

LAYER, UNIT, MOVES = "decode/prefill math", "%", "serve_tok_s"


def read(run):
    return stage_times.share(run, ("norm",))
