"""Host time of `executor/release` per step: `Executor.run` letting go of the
step's donated inputs and fetched arrays after the fetch, over the steps that
lie whole inside the traced window (`program_spans.per_parent`)."""
from lib import stage_times

LAYER, UNIT, MOVES = "executor", "ms", "train_tok_s"


def read(run):
    return stage_times.release_ms(run)
