"""Prefix-cache hits over hits and misses in the window. Expected 0 where
prompts share nothing: the control for a later cell with shared prefixes."""
from lib import readers

LAYER, UNIT, MOVES = "paged KV cache", "%", "ttft_p95_ms"


def read(run):
    hits, misses = readers.delta(run, "prefix_cache_hits"), readers.delta(run, "prefix_cache_misses")
    if hits is None or misses is None or hits + misses == 0:
        return None
    return 100.0 * hits / (hits + misses)
