"""The expert products the costliest twentieth of tokens take: the 95th percentile of the
real (non-identity) picks a token, from the in-graph histogram `moe_real_picks_hist` over
the window (live tokens by how many of their `moe_topk` picks were real experts; every
expert layer counts, prefill and decode). The mean is 8 of 12 where the routing is even
over 512 experts and 256 identity experts; this is how far above it a deployment has to
provision. A program without the histogram reports nothing."""
LAYER, UNIT, MOVES = "routed and shared experts", "picks", "serve_tok_s"


def read(run):
    a = (run.get("model0") or {}).get("moe_real_picks_hist")
    b = (run.get("model1") or {}).get("moe_real_picks_hist")
    if not a or not b:
        return None
    hist = [y - x for x, y in zip(a, b)]
    tokens, below = sum(hist), 0
    for picks, count in enumerate(hist):
        below += count
        if tokens and below >= 0.95 * tokens:
            return picks
    return None
