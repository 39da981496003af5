"""Operations the prompts prefilled in the window need, at the chip's peak,
over the device time the prefill programs took there (the trace covers part of
the window, so the time is scaled by prefills counted over prefills traced)."""
from lib import costs, readers

LAYER, UNIT, MOVES = "decode/prefill math", "%", "serve_tok_s"


def read(run):
    seconds, runs = readers.module_time(run, "jit_prefill_impl")
    if not seconds or not runs:
        return None
    # mean operations of a prefill in this mix, over the requests admitted in
    # the window, times the prefills the trace saw
    lens = [r["prompt_len"] for r in run["records"]
            if r["ok"] and run["t0"] <= r["sent"] < run["t0"] + run["seconds"]]
    if not lens:
        return None
    flops = runs * sum(costs.prefill_flops(run["config"], n) for n in lens) / len(lens)
    return 100.0 * flops / run["peaks"]["bf16_flops"] / seconds
