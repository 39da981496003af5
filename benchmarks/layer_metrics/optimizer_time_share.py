"""Device self time under `optimizer` (what `apply_gradients` appends:
clipping, weight decay, the update of every parameter) over busy time."""
from lib import stage_times

LAYER, UNIT, MOVES = "program ops and AMP", "%", "train_tok_s"


def read(run):
    return stage_times.share(run, ("optimizer",))
