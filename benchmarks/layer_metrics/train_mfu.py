"""Operations training needs per token (6 N and causal attention) times the
tokens per second of the window (of its untraced part, in a traced run), over
the chips' peak."""
from lib import costs, metrics

LAYER, UNIT, MOVES = "program ops and AMP", "%", "train_tok_s"


def read(run):
    # in a traced run, from where the profiler had stopped: while it writes
    # the trace out the loop stands still, which is no property of the program
    start = run.get("quiet_from", run["t0"])
    rate = metrics.train_tok_s(run.get("steps") or [], start,
                               run["t0"] + run["seconds"] - start)
    if rate is None:
        return None
    per_token = costs.train_flops_per_token(run["config"], run["seq_len"])
    return 100.0 * per_token * rate / (run["chips"] * run["peaks"]["bf16_flops"])
