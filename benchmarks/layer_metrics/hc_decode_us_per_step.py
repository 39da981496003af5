"""Device self time under `hc/*` inside the fused decode program per step it
holds: twelve mixers of 16 rows and twenty 4 x 4 normalisations each, bound by
latency, where a share of a roofline says nothing."""
from lib import scope_reduce

LAYER, UNIT, MOVES = "residual streams", "us", "serve_tok_s"


def read(run):
    seconds = scope_reduce.scope_seconds(run, "jit_chunk_impl", "hc/")
    traced = scope_reduce.runs_in_window(run, "jit_chunk_impl")
    if not seconds or not traced:
        return None
    return 1e6 * seconds / (traced * run["decode_chunk"])
