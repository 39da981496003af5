"""Device time in the prefill programs over device busy time (prefill_share's
arithmetic, in the cell whose roofline readers are its own)."""
LAYER, UNIT, MOVES = "decode/prefill math", "%", "serve_tok_s"


def read(run):
    trace = run.get("trace")
    if not trace or "jit_prefill_impl" not in trace["module_s"]:
        return None
    return 100.0 * trace["module_s"]["jit_prefill_impl"] / trace["busy_s"]
