"""The least time a decode step could take on the chip's memory bandwidth
(every weight once and the live cache rows) over the time it took."""
from lib import costs, readers

LAYER, UNIT, MOVES = "decode/prefill math", "%", "tpot_mean_ms"


def read(run):
    seconds, runs = readers.module_time(run, "jit_chunk_impl")
    live = readers.mean(readers.samples(run, 2))
    if not seconds or not runs or live is None:
        return None
    step_s = seconds / (runs * run["decode_chunk"])
    least_s = costs.decode_step_bytes(run["config"], live) / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / step_s
