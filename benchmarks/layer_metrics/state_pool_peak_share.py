"""Peak blocks of the STATE cache groups in use since the engine started over the groups'
pools (the engine's own count, `engine.stats()["state"]`, read when the window has
closed): a slot holds one block of each, so this is how full the slots ran. A program
without a state group reports nothing."""
LAYER, UNIT, MOVES = "paged KV cache", "%", "serve_tok_s"


def read(run):
    state = run.get("state")
    if not state or not state.get("blocks_total"):
        return None
    return 100.0 * state["peak_blocks_used"] / state["blocks_total"]
