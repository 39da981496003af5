"""`gqa_decode_hbm_roofline`'s arithmetic over `lib/costs_qwen3_next.py` (2 KV heads of 256:
2,048 B a row a layer, three attention layers): the least time the decode steps' attention
could take on the chip's memory bandwidth over the grouped paged kernel's device time in the
traced window. Bytes: the rows the steps had to attend, counted in-graph
(`decode_rows_full`), over the window's decode dispatches, a dispatch's mean times the
dispatches the trace holds."""
from lib import costs_qwen3_next as costs, readers, scope_reduce

LAYER, UNIT, MOVES = "attention (grouped heads, window + full)", "%", "serve_tok_s"
KERNEL = "paged_attention_grouped"


def read(run):
    tables = run.get("scopes") or {}
    seconds = (tables.get("jit_chunk_impl") or {}).get("kernels", {}).get(KERNEL)
    traced = scope_reduce.runs_in_window(run, "jit_chunk_impl")
    dispatches = readers.delta(run, "dispatches")
    a, b = run.get("model0") or {}, run.get("model1") or {}
    if not seconds or not traced or not dispatches or "decode_rows_full" not in b \
            or "linear_num_value_heads" not in run["config"]:
        return None
    rows = b["decode_rows_full"] - a["decode_rows_full"]
    least_s = (costs.decode_rows_bytes(run["config"], rows) / dispatches * traced
               / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds
