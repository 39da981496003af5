"""`gqa_decode_hbm_roofline`'s arithmetic over `lib/costs_command_a.py` (8 KV heads of
128: 4,096 B a row): the least time the decode steps' attention could take on the chip's
memory bandwidth over the grouped paged kernel's device time in the traced window.
Bytes: the rows the steps had to attend, counted in-graph by kind of layer
(`decode_rows_full` + `decode_rows_window`), over the window's decode dispatches, a
dispatch's mean times the dispatches the trace holds."""
from lib import costs_command_a as costs, readers, scope_reduce

LAYER, UNIT, MOVES = "attention (grouped heads, window + full)", "%", "serve_tok_s"
KERNEL = "paged_attention_grouped"


def read(run):
    tables = run.get("scopes") or {}
    seconds = (tables.get("jit_chunk_impl") or {}).get("kernels", {}).get(KERNEL)
    traced = scope_reduce.runs_in_window(run, "jit_chunk_impl")
    dispatches = readers.delta(run, "dispatches")
    a, b = run.get("model0") or {}, run.get("model1") or {}
    if not seconds or not traced or not dispatches or "decode_rows_full" not in b:
        return None
    rows = sum(b[k] - a[k] for k in ("decode_rows_full", "decode_rows_window"))
    least_s = (costs.decode_rows_bytes(run["config"], rows) / dispatches * traced
               / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds
