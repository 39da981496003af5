"""Device self time under `head` (the final norm, the product over the tied
embedding table, the cast of the logits to float32) over busy time."""
from lib import stage_times

LAYER, UNIT, MOVES = "decode/prefill math", "%", "serve_tok_s"


def read(run):
    return stage_times.share(run, ("head",))
