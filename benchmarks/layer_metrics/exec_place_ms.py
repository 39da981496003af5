"""Per step, the program's `executor/place` span: scope reads, the feed's way
to the device, `shard_feed` and `place_scope` under a mesh plan."""
from lib import program_spans

LAYER, UNIT, MOVES = "executor", "ms", "train_tok_s"


def read(run):
    return program_spans.value(run, "step_place_ms")
