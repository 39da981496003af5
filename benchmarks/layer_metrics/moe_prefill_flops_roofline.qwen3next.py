"""`moe_prefill_flops_roofline`'s arithmetic over `lib/costs_qwen3_next.py`: the shared
expert's and the router's operations of the TRACED prompts (`lib/traced_prompts.py`) and a
routed expert's for each pick that fell on an expert HELD here (the share of the window's
prefill picks that did, by the in-graph counters: `moe_picks_held` less the decode steps',
over `moe_picks_routed` less theirs), at the chip's peak, over the device self time under
`moe/*` inside `jit_prefill_impl`."""
from lib import costs_qwen3_next as costs, scope_reduce, traced_prompts

LAYER, UNIT, MOVES = "routed and shared experts", "%", "serve_tok_s"


def read(run):
    seconds = scope_reduce.scope_seconds(run, "jit_prefill_impl", "moe/")
    traced = scope_reduce.runs_in_window(run, "jit_prefill_impl")
    a, b = run.get("model0") or {}, run.get("model1") or {}
    if not seconds or "moe_picks_held" not in b \
            or "linear_num_value_heads" not in run["config"]:
        return None
    grew = lambda name: b[name] - a[name]
    routed = grew("moe_picks_routed") - grew("decode_moe_picks_routed")
    held = grew("moe_picks_held") - grew("decode_moe_picks_held")
    if not routed:
        return None
    cfg = run["config"]
    picks_a_token = costs.expert_layers(cfg) * cfg["num_experts_per_tok"] * held / routed
    flops = traced_prompts.scaled(run, traced,
                                  lambda n: costs.moe_flops(cfg, n, n * picks_a_token))
    if flops is None:
        return None
    return 100.0 * flops / run["peaks"]["bf16_flops"] / seconds
