"""`moe_prefill_flops_roofline`'s arithmetic over `lib/costs_sdar.py`: the router's and
eight experts' operations of the prompts' WHOLE blocks (the rows a prefill routes) at the
chip's peak, over the device self time under `moe/*` inside `jit_prefill_impl`: a mean
prompt of the window's admissions times the prefills the trace holds."""
from lib import costs_sdar as costs, scope_reduce

LAYER, UNIT, MOVES = "routed and shared experts", "%", "serve_tok_s"


def read(run):
    seconds = scope_reduce.scope_seconds(run, "jit_prefill_impl", "moe/")
    traced = scope_reduce.runs_in_window(run, "jit_prefill_impl")
    lens = [r["prompt_len"] for r in run["records"]
            if r["ok"] and run["t0"] <= r["sent"] < run["t0"] + run["seconds"]]
    if not seconds or not traced or not lens or "generation" not in run["config"].get("assumed", {}):
        return None
    tokens = sum(costs.whole_blocks(run["config"], n) for n in lens) / len(lens)
    flops = traced * costs.moe_flops(run["config"], tokens)
    return 100.0 * flops / run["peaks"]["bf16_flops"] / seconds
