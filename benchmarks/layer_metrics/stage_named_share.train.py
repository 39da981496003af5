"""Coverage: of the first chip's busy time in the traced window, the share of
the training step whose operation lies under a name scope of the Program
(`pt.name_scope` stamps `op_namescope`, `lower_op` traces under it: `embed`,
`norm`, `attn`, `ffn`, `head`, `loss`, `optimizer`). It guards the other stage
metrics of the cell: what is under no stage, they cannot see."""
from lib import stage_times

LAYER, UNIT, MOVES = "program ops and AMP", "%", "train_tok_s"


def read(run):
    return stage_times.named_share(run)
