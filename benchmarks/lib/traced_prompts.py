"""The prompts whose prefill the TRACED part of a window holds, for the per-layer readers of
a cell whose prompts differ sixteenfold (`longmix-offline`: 1,024 to 16,384 tokens): there a
mean prompt of the whole window times the prefills the trace holds can be half or twice what
the trace's own prefills were, and the attention's operations go with the square. A request's
first token leaves right behind its prefill, so the prompts prefilled while the profiler ran
are those whose first token fell inside the trace, which starts with the window."""


def lens(run):
    """Prompt lengths of the requests, served in full, whose first token fell inside the
    traced seconds; None without a trace or without any."""
    trace = run.get("trace")
    if not trace or not trace.get("window_s"):
        return None
    t0, t1 = run["t0"], run["t0"] + trace["window_s"]
    found = [r["prompt_len"] for r in run["records"]
             if r["ok"] and r.get("first") is not None and t0 <= r["first"] < t1]
    return found or None


def scaled(run, traced, per_prompt):
    """`sum(per_prompt(n))` over the traced prompts, times `traced` (the prefills the trace
    holds, a run cut by its edge counted by the part inside) over their count: the two
    counts differ by the prefills at the trace's edges."""
    found = lens(run)
    if not found or not traced:
        return None
    return sum(per_prompt(n) for n in found) * traced / len(found)
