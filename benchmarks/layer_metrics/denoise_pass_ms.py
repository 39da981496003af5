"""Device time of the fused chunk program per PASS it holds (a scan iteration is one pass
over every slot's block), from the trace: `decode_step_ms`'s arithmetic where a step
yields no token until its block commits."""
from lib import readers

LAYER, UNIT, MOVES = "fused decode loop (block diffusion)", "ms", "serve_tok_s"


def read(run):
    seconds, runs = readers.module_time(run, "jit_chunk_impl")
    if not seconds or not runs or "blocks_committed" not in (run.get("model1") or {}):
        return None
    return 1e3 * seconds / (runs * run["decode_chunk"])
