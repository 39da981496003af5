"""Passes of the model a committed block took, by the loop's in-graph counters over the
window: live slot-passes (`block_passes`) over blocks committed (`blocks_committed`). 2
is the least (one pass fixes every position, one commits), denoising steps + 1 the most
(the static schedule, which seeded weights always take: no confidence clears the
threshold)."""
LAYER, UNIT, MOVES = "fused decode loop (block diffusion)", "passes", "serve_tok_s"


def read(run):
    a, b = run.get("model0") or {}, run.get("model1") or {}
    if "blocks_committed" not in b or b["blocks_committed"] == a.get("blocks_committed"):
        return None
    return (b["block_passes"] - a["block_passes"]) / (b["blocks_committed"] - a["blocks_committed"])
