"""`moe_decode_hbm_roofline`'s arithmetic over `lib/costs_qwen3_next.py` (64 held experts of
width 512, a shared expert of 512 with its token gate, a router 512 wide): the least time
the decode steps' expert layers could take on the chip's memory bandwidth (the HELD experts
that had a row, `decode_experts_touched`, and the shared expert and the router of every
pass, `decode_moe_passes`) over the device self time under `moe/*` inside `jit_chunk_impl`
in the traced window."""
from lib import costs_qwen3_next as costs, readers, scope_reduce

LAYER, UNIT, MOVES = "routed and shared experts", "%", "serve_tok_s"


def read(run):
    seconds = scope_reduce.scope_seconds(run, "jit_chunk_impl", "moe/")
    traced = scope_reduce.runs_in_window(run, "jit_chunk_impl")
    dispatches = readers.delta(run, "dispatches")
    a, b = run.get("model0") or {}, run.get("model1") or {}
    if not seconds or not traced or not dispatches or "decode_moe_passes" not in b \
            or "linear_num_value_heads" not in run["config"]:
        return None
    nbytes = costs.moe_decode_bytes(
        run["config"], b["decode_experts_touched"] - a["decode_experts_touched"],
        b["decode_moe_passes"] - a["decode_moe_passes"])
    least_s = nbytes / dispatches * traced / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
