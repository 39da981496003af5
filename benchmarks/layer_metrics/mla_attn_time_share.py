"""Device self time in the latent paged kernel and under `mla/attend` (the
kernel runs under that scope: an operation counts once) over busy time."""
LAYER, UNIT, MOVES = "latent attention", "%", "serve_tok_s"


def read(run):
    tables, trace = run.get("scopes"), run.get("trace")
    if not tables or not trace:
        return None
    seconds = sum(t["attend_s"] for t in tables.values())
    return 100.0 * seconds / trace["busy_s"] if seconds else None
