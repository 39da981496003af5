"""The least time the decode steps' recurrence could take on the chip's memory bandwidth
(`lib/costs_qwen3_next.py`: each live slot's 2 MB state block of each Gated-DeltaNet layer
read once and written once a step, by the in-graph counter `gdn_state_steps`; the
convolution's history moves under `gdn/conv` and is not counted) over the device self time
under `gdn/recur` inside `jit_chunk_impl` in the traced window."""
from lib import costs_qwen3_next as costs, readers, scope_reduce

LAYER, UNIT, MOVES = "gated delta-rule mixer", "%", "serve_tok_s"


def read(run):
    seconds = scope_reduce.scope_seconds(run, "jit_chunk_impl", "gdn/recur")
    traced = scope_reduce.runs_in_window(run, "jit_chunk_impl")
    dispatches = readers.delta(run, "dispatches")
    a, b = run.get("model0") or {}, run.get("model1") or {}
    if not seconds or not traced or not dispatches or "gdn_state_steps" not in b:
        return None
    nbytes = costs.gdn_decode_bytes(run["config"], b["gdn_state_steps"] - a["gdn_state_steps"])
    least_s = nbytes / dispatches * traced / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
