"""The recurrence's own operations of the prompts prefilled (`lib/costs_qwen3_next.py`: three
products of 2 x 128 x 128 a row a value head a Gated-DeltaNet layer, a floor under what any
chunked form does), at the chip's peak, over the device self time under `gdn/recur` inside
`jit_prefill_impl`. The rows are the TRACED prompts' own (`lib/traced_prompts.py`: this
cell's prompts differ sixteenfold, so a mean prompt of the window is not the trace's), every
Gated-DeltaNet layer's; the in-graph counter `gdn_prefill_rows` has to be there, or the
program is not this model's."""
from lib import costs_qwen3_next as costs, scope_reduce, traced_prompts

LAYER, UNIT, MOVES = "gated delta-rule mixer", "%", "serve_tok_s"


def read(run):
    seconds = scope_reduce.scope_seconds(run, "jit_prefill_impl", "gdn/recur")
    traced = scope_reduce.runs_in_window(run, "jit_prefill_impl")
    if not seconds or "gdn_prefill_rows" not in (run.get("model1") or {}):
        return None
    cfg = run["config"]
    layers = costs.kinds(cfg)[0]
    flops = traced_prompts.scaled(run, traced,
                                  lambda n: costs.gdn_prefill_flops(cfg, n * layers))
    if flops is None:
        return None
    return 100.0 * flops / run["peaks"]["bf16_flops"] / seconds
