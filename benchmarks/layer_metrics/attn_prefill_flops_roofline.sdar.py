"""`attn_prefill_flops_roofline`'s arithmetic over `lib/costs_sdar.py` (32 query heads of
128): the traced prompts' attention operations over the BLOCK-CAUSAL triangle of their
whole blocks at the chip's peak, over the device self time under `attn/full` inside
`jit_prefill_impl`: a mean prompt of the window's admissions times the prefills the trace
holds."""
from lib import costs_sdar as costs, scope_reduce

LAYER, UNIT, MOVES = "attention (grouped heads, window + full)", "%", "serve_tok_s"


def read(run):
    seconds = scope_reduce.scope_seconds(run, "jit_prefill_impl", "attn/full")
    traced = scope_reduce.runs_in_window(run, "jit_prefill_impl")
    lens = [r["prompt_len"] for r in run["records"]
            if r["ok"] and run["t0"] <= r["sent"] < run["t0"] + run["seconds"]]
    if not seconds or not traced or not lens or "generation" not in run["config"].get("assumed", {}):
        return None
    flops = traced * sum(costs.attention_prefill_flops(run["config"], n) for n in lens) / len(lens)
    return 100.0 * flops / run["peaks"]["bf16_flops"] / seconds
