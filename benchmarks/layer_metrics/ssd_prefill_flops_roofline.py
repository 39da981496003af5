"""The recurrence's own operations of the prompts prefilled (`lib/costs_granite_hybrid.py`:
6 x 64 x 128 a row a head a mamba layer, a floor under what any chunked form does; the rows
by the in-graph counter `ssd_prefill_rows`), at the chip's peak, over the device self time
under `ssd/scan` inside `jit_prefill_impl`: a mean prefill of the window times the prefills
the trace holds."""
from lib import costs_granite_hybrid as costs, readers, scope_reduce

LAYER, UNIT, MOVES = "state-space mixer", "%", "serve_tok_s"


def read(run):
    seconds = scope_reduce.scope_seconds(run, "jit_prefill_impl", "ssd/scan")
    traced = scope_reduce.runs_in_window(run, "jit_prefill_impl")
    prefills = readers.delta(run, "prefills")
    a, b = run.get("model0") or {}, run.get("model1") or {}
    if not seconds or not traced or not prefills or "ssd_prefill_rows" not in b:
        return None
    rows = (b["ssd_prefill_rows"] - a["ssd_prefill_rows"]) / prefills
    flops = traced * costs.ssd_prefill_flops(run["config"], rows)
    return 100.0 * flops / run["peaks"]["bf16_flops"] / seconds
