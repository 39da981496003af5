"""The least time the decode steps' dense feed-forwards could take on the chip's memory
bandwidth (`lib/costs_longcat_flash.py`: both SwiGLUs of a layer, 453 M parameters, whole,
once for every layer of every step that had a live slot: the in-graph counter
`decode_moe_passes` counts exactly those) over the device self time under `ffn/*` inside
`jit_chunk_impl` in the traced window. 64 rows a step: the weights' read is the work."""
from lib import costs_longcat_flash as costs, readers, scope_reduce

LAYER, UNIT, MOVES = "decode/prefill math", "%", "serve_tok_s"


def read(run):
    seconds = scope_reduce.scope_seconds(run, "jit_chunk_impl", "ffn/")
    traced = scope_reduce.runs_in_window(run, "jit_chunk_impl")
    dispatches = readers.delta(run, "dispatches")
    a, b = run.get("model0") or {}, run.get("model1") or {}
    if not seconds or not traced or not dispatches or "decode_moe_passes" not in b \
            or "zero_expert_num" not in run["config"]:
        return None
    nbytes = costs.dense_decode_bytes(run["config"],
                                      b["decode_moe_passes"] - a["decode_moe_passes"])
    least_s = nbytes / dispatches * traced / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
