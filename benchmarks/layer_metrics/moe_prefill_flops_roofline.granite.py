"""`moe_prefill_flops_roofline`'s arithmetic over `lib/costs_granite_hybrid.py`: the shared
feed-forward's and the router's operations of the prompts prefilled and a routed expert's
for each pick that fell on an expert HELD here (the window's prefill picks by the in-graph
counters: `moe_picks_held` less the decode steps'), at the chip's peak, over the device self
time under `moe/*` inside `jit_prefill_impl`: a mean prompt of the window's admissions times
the prefills the trace holds."""
from lib import costs_granite_hybrid as costs, scope_reduce

LAYER, UNIT, MOVES = "routed and shared experts", "%", "serve_tok_s"


def read(run):
    seconds = scope_reduce.scope_seconds(run, "jit_prefill_impl", "moe/")
    traced = scope_reduce.runs_in_window(run, "jit_prefill_impl")
    lens = [r["prompt_len"] for r in run["records"]
            if r["ok"] and run["t0"] <= r["sent"] < run["t0"] + run["seconds"]]
    a, b = run.get("model0") or {}, run.get("model1") or {}
    if not seconds or not traced or not lens or "moe_picks_held" not in b \
            or "mamba_n_heads" not in run["config"]:
        return None
    grew = lambda name: b[name] - a[name]
    routed = grew("moe_picks_routed") - grew("decode_moe_picks_routed")
    held = grew("moe_picks_held") - grew("decode_moe_picks_held")
    if not routed:
        return None
    cfg, tokens = run["config"], sum(lens) / len(lens)
    picks = tokens * costs.expert_layers(cfg) * cfg["num_experts_per_tok"] * held / routed
    flops = traced * costs.moe_flops(cfg, tokens, picks)
    return 100.0 * flops / run["peaks"]["bf16_flops"] / seconds
