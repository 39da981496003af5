"""Host time of one `Executor.run` outside its wait for the device: per step,
the program's `executor/run` span less its `executor/fetch`, over the steps
that lie whole inside the traced window."""
from lib import program_spans

LAYER, UNIT, MOVES = "executor", "ms", "train_tok_s"


def read(run):
    return program_spans.value(run, "step_host_ms")
