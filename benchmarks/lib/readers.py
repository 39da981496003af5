"""Small helpers the per-layer readers share. A reader returns None when what
it reads is not in the run, and the harness then leaves the metric out."""


def delta(run, key):
    a, b = run.get("counters0"), run.get("counters1")
    if not a or not b or key not in a or key not in b:
        return None
    return b[key] - a[key]


def ratio(num, den):
    return None if num is None or not den else num / den


def samples(run, column):
    """Gauge samples taken inside the window; columns: 1 slots in use, 2 live
    positions, 3 blocks in use."""
    return [s[column] for s in run.get("samples") or []
            if run["t0"] <= s[0] < run["t0"] + run["seconds"]]


def mean(values):
    return sum(values) / len(values) if values else None


def module_time(run, name):
    """(device seconds, runs) of the program `name`, over its runs that lie
    whole inside the traced window."""
    trace = run.get("trace")
    if not trace:
        return None, None
    return trace["module_whole_s"].get(name), trace["module_runs"].get(name)
