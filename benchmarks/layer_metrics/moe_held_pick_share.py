"""Of the picks the router made in the window (live tokens x `num_experts_per_tok`,
every expert layer, prefill and decode: the in-graph counter `moe_picks_routed`), the
share that fell on an expert HELD on this chip (`moe_picks_held`): 16 / 128 = 0.125
where the routing is even over the published experts. What the absent experts would add
is left out, so this is the share of the routed work the chip does. A program without
the counters reports nothing."""
LAYER, UNIT, MOVES = "routed and shared experts", "ratio", "serve_tok_s"


def read(run):
    a, b = run.get("model0") or {}, run.get("model1") or {}
    if "moe_picks_routed" not in a or "moe_picks_routed" not in b:
        return None
    routed = b["moe_picks_routed"] - a["moe_picks_routed"]
    return (b["moe_picks_held"] - a["moe_picks_held"]) / routed if routed else None
