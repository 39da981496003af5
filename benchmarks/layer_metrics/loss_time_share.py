"""Device self time under `head` and `loss` (the final norm, the product over
the tied table, the shifted slices, softmax with cross entropy and the mean;
forward and `_grad` ops alike) over busy time."""
from lib import stage_times

LAYER, UNIT, MOVES = "program ops and AMP", "%", "train_tok_s"


def read(run):
    return stage_times.share(run, ("head", "loss"))
