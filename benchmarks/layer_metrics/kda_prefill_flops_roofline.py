"""The recurrence's own operations of the prompts prefilled (`lib/costs_kimi_linear.py`:
three products of 2 x 128 x 128 a row a head a KDA layer, a floor under what any chunked
form does; the rows by the in-graph counter `kda_prefill_rows`), at the chip's peak, over
the device self time under `kda/recur` inside `jit_prefill_impl`: a mean prefill of the
window times the prefills the trace holds."""
from lib import costs_kimi_linear as costs, readers, scope_reduce

LAYER, UNIT, MOVES = "linear attention", "%", "serve_tok_s"


def read(run):
    seconds = scope_reduce.scope_seconds(run, "jit_prefill_impl", "kda/recur")
    traced = scope_reduce.runs_in_window(run, "jit_prefill_impl")
    prefills = readers.delta(run, "prefills")
    a, b = run.get("model0") or {}, run.get("model1") or {}
    if not seconds or not traced or not prefills or "kda_prefill_rows" not in b:
        return None
    rows = (b["kda_prefill_rows"] - a["kda_prefill_rows"]) / prefills
    flops = traced * costs.kda_prefill_flops(run["config"], rows)
    return 100.0 * flops / run["peaks"]["bf16_flops"] / seconds
