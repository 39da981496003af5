"""Of the picks the router made in the window (live tokens x `moe_topk`, every expert
layer, prefill and decode: the in-graph counter `moe_picks_routed`), the share that fell on
an IDENTITY expert (`moe_identity_picks`): a pick that lays out no row and computes no tile.
256 / 768 = 33% where the routing is even over the router's outputs. A program without the
counters reports nothing."""
LAYER, UNIT, MOVES = "routed and shared experts", "%", "serve_tok_s"


def read(run):
    a, b = run.get("model0") or {}, run.get("model1") or {}
    if "moe_identity_picks" not in a or "moe_identity_picks" not in b:
        return None
    routed = b["moe_picks_routed"] - a["moe_picks_routed"]
    return 100.0 * (b["moe_identity_picks"] - a["moe_identity_picks"]) / routed if routed else None
