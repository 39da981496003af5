"""Rows routed to the busiest expert over the mean of all experts, from the
in-graph `expert_tokens` counter over the window (every expert layer, prefill
and decode): 1 is an even load."""
LAYER, UNIT, MOVES = "routed and shared experts", "ratio", "serve_tok_s"


def read(run):
    a, b = (run.get("model0") or {}).get("expert_tokens"), (run.get("model1") or {}).get("expert_tokens")
    if not a or not b:
        return None
    rows = [y - x for x, y in zip(a, b)]
    return max(rows) * len(rows) / sum(rows) if sum(rows) else None
