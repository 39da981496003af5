"""The least time the decode steps' latent attention could take on the chip's
memory bandwidth (every live position's row in every layer, once a step) over
the latent paged kernel's device time in the traced window."""
from lib import costs_moonlight as costs, readers, scope_reduce

LAYER, UNIT, MOVES = "latent attention", "%", "serve_tok_s"


def read(run):
    tables = run.get("scopes") or {}
    seconds = (tables.get("jit_chunk_impl") or {}).get("kernels", {}).get("latent_paged_attention")
    traced = scope_reduce.runs_in_window(run, "jit_chunk_impl")
    live = readers.mean(readers.samples(run, 2))
    if not seconds or not traced or live is None:
        return None
    steps = traced * run["decode_chunk"]
    least_s = steps * costs.mla_decode_bytes(run["config"], live) / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
