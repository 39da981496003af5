"""`attn_prefill_flops_roofline`'s arithmetic over `lib/costs_qwen3_next.py` (16 query heads
of 256 over 2 KV heads, three attention layers): the TRACED prompts' attention operations
(the causal triangle of each prompt's own rows, below `real_len`: a bucket's padding is not
counted; `lib/traced_prompts.py`, because the triangle goes with the square of a prompt and
this cell's prompts differ sixteenfold) at the chip's peak, over the device self time under
`attn/full` inside `jit_prefill_impl`."""
from lib import costs_qwen3_next as costs, scope_reduce, traced_prompts

LAYER, UNIT, MOVES = "attention (grouped heads, window + full)", "%", "serve_tok_s"


def read(run):
    seconds = scope_reduce.scope_seconds(run, "jit_prefill_impl", "attn/full")
    traced = scope_reduce.runs_in_window(run, "jit_prefill_impl")
    if not seconds or "linear_num_value_heads" not in run["config"]:
        return None
    cfg = run["config"]
    flops = traced_prompts.scaled(run, traced, lambda n: costs.attn_prefill_flops(cfg, [n]))
    if flops is None:
        return None
    return 100.0 * flops / run["peaks"]["bf16_flops"] / seconds
