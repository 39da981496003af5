"""Device time of the fused decode program per step it holds, from the trace
(decode_step_ms's arithmetic, in the cell whose roofline readers are its own)."""
from lib import readers

LAYER, UNIT, MOVES = "decode/prefill math", "ms", "serve_tok_s"


def read(run):
    seconds, runs = readers.module_time(run, "jit_chunk_impl")
    if not seconds or not runs:
        return None
    return 1e3 * seconds / (runs * run["decode_chunk"])
