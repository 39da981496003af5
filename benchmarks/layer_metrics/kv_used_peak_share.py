"""Largest share of the block pool in use at a sample inside the window."""
from lib import readers

LAYER, UNIT, MOVES = "paged KV cache", "%", "serve_tok_s"


def read(run):
    used = readers.samples(run, 3)
    total = (run.get("counters1") or {}).get("blocks_total")
    return 100.0 * max(used) / total if used and total else None
