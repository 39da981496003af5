"""Peak blocks of the window cache group in use since the engine started over the
group's pool (the engine's own count, read when the window has closed): how full the
ring pool ran. A program without cache groups reports nothing."""
LAYER, UNIT, MOVES = "paged KV cache", "%", "serve_tok_s"


def read(run):
    pool = (run.get("cache_groups") or {}).get("window")
    if not pool or not pool.get("blocks_total"):
        return None
    return 100.0 * pool["peak_blocks_used"] / pool["blocks_total"]
