"""`moe_decode_hbm_roofline`'s arithmetic over `lib/costs_sdar.py` (128 experts of width
768, a router 128 wide, no shared expert): the least time the passes' expert layers could
take on the chip's memory bandwidth (the experts that had a row, `decode_experts_touched`,
and the router of every pass of a layer, `decode_moe_passes`) over the device self time
under `moe/*` inside `jit_chunk_impl` in the traced window. A pass hands the layer S x B
rows, so nearly every expert is touched."""
from lib import costs_sdar as costs, readers, scope_reduce

LAYER, UNIT, MOVES = "routed and shared experts", "%", "serve_tok_s"


def read(run):
    seconds = scope_reduce.scope_seconds(run, "jit_chunk_impl", "moe/")
    traced = scope_reduce.runs_in_window(run, "jit_chunk_impl")
    dispatches = readers.delta(run, "dispatches")
    a, b = run.get("model0") or {}, run.get("model1") or {}
    if not seconds or not traced or not dispatches or "blocks_committed" not in b:
        return None
    nbytes = costs.moe_decode_bytes(
        run["config"], b["decode_experts_touched"] - a["decode_experts_touched"],
        b["decode_moe_passes"] - a["decode_moe_passes"])
    least_s = nbytes / dispatches * traced / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
