"""Process start to the first measured request or step."""
UNIT, BETTER = "s", "lower"


def read(run):
    return run["setup_s"]
