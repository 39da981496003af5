"""`attn_prefill_flops_roofline`'s arithmetic over `lib/costs_command_a.py` (128 query
heads of 128, a window of 4,096): the traced prompts' attention operations (the causal
triangle on full layers, the BAND on sliding ones) at the chip's peak, over the device
self time under `attn/window` + `attn/full` inside `jit_prefill_impl`: a mean prompt of
the window's admissions times the prefills the trace holds."""
from lib import costs_command_a as costs, scope_reduce

LAYER, UNIT, MOVES = "attention (grouped heads, window + full)", "%", "serve_tok_s"


def read(run):
    parts = [scope_reduce.scope_seconds(run, "jit_prefill_impl", "attn/" + kind)
             for kind in ("window", "full")]
    traced = scope_reduce.runs_in_window(run, "jit_prefill_impl")
    lens = [r["prompt_len"] for r in run["records"]
            if r["ok"] and run["t0"] <= r["sent"] < run["t0"] + run["seconds"]]
    if None in parts or not sum(parts) or not traced or not lens \
            or "sliding_window" not in run["config"]:
        return None
    flops = traced * sum(costs.attention_prefill_flops(run["config"], n) for n in lens) / len(lens)
    return 100.0 * flops / run["peaks"]["bf16_flops"] / sum(parts)
