"""Seconds in the backend's compiler, or loading from its cache, in set-up."""
LAYER, UNIT, MOVES = "compile cache", "s", "setup_s"


def read(run):
    return run["compile_at_setup"]["compile_s"]
