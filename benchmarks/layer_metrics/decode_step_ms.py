"""Device time of the fused decode program per step it holds, from the trace."""
from lib import readers

LAYER, UNIT, MOVES = "decode/prefill math", "ms", "tpot_mean_ms"


def read(run):
    seconds, runs = readers.module_time(run, "jit_chunk_impl")
    if not seconds or not runs:
        return None
    return 1e3 * seconds / (runs * run["decode_chunk"])
