"""`mla_decode_hbm_roofline`'s arithmetic over `lib/costs_longcat_flash.py`: the least time
the decode steps' latent attention could take on the chip's memory bandwidth (every live
position's row of 1,152 B in each of the EIGHT cache layers, two attentions a layer, once a
step: the in-graph counter `mla_decode_rows`) over the latent paged kernel's device time in
the traced window."""
from lib import costs_longcat_flash as costs, readers, scope_reduce

LAYER, UNIT, MOVES = "latent attention", "%", "serve_tok_s"


def read(run):
    tables = run.get("scopes") or {}
    seconds = (tables.get("jit_chunk_impl") or {}).get("kernels", {}).get("latent_paged_attention")
    traced = scope_reduce.runs_in_window(run, "jit_chunk_impl")
    dispatches = readers.delta(run, "dispatches")
    a, b = run.get("model0") or {}, run.get("model1") or {}
    if not seconds or not traced or not dispatches or "mla_decode_rows" not in b \
            or "zero_expert_num" not in run["config"]:
        return None
    nbytes = costs.mla_decode_bytes(run["config"], b["mla_decode_rows"] - a["mla_decode_rows"])
    least_s = nbytes / dispatches * traced / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
