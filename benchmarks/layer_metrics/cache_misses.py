"""Programs the persistent cache did not hold. 0 on a second run."""
LAYER, UNIT, MOVES = "compile cache", "count", "setup_s"


def read(run):
    return run["compile_at_end"]["cache_misses"]
