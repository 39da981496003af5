"""Device self time under `loop/*` (the engine's own work in a decode step:
`loop/sample` the per-slot sampler and its key split, `loop/finish` the finish
rule and the carry's update) inside `jit_chunk_impl`, per step it holds."""
from lib import stage_times

LAYER, UNIT, MOVES = "decode/prefill math", "us", "serve_tok_s"


def read(run):
    return stage_times.loop_us_per_step(run)
