"""Coverage: of the first chip's busy time in the traced window, the share whose
operation lies under a stage the program names (`lib/stage_times.py`: `embed`,
`norm`, `attn/*`, `ffn/dense`, `head`, `loop/*`). It guards the other stage
metrics of the cell: what is under no stage, they cannot see."""
from lib import stage_times

LAYER, UNIT, MOVES = "decode/prefill math", "%", "serve_tok_s"


def read(run):
    return stage_times.named_share(run)
