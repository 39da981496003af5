"""`serve-closed-model`'s loop, server, window and tie-aware verdict for
Mellum2-12B-A2.5B-Instruct. As `serve-closed-xing` does, this file loads a copy of
that mode of its OWN and sets in the copy (in memory; the file on disk is Moonlight's
and is not touched) the architecture's builder and reference, this model's verdict
constants, a by-scope reduction of the trace that knows the attention's scopes
(`attn/project`, `attn/window`, `attn/full`), and a choice of the checked requests
that always holds prompts beyond the window. Everything else is the copy's.

What is checked: prompt + served tokens of four greedy requests served in full in the
window, TWO of them with prompts of LONG_PROMPT rows or more (every checked position
of those lies beyond the window of 1024, and a slot of 4096 rows has wrapped its ring
of 9 pages three times), through `reference/mellum_ref.sequence_logits` in float32:
every served token's reference logit against its position's largest (the deficit).

The verdict's constants, measured on the chip (my chip runs, PR 33; PERF.md section 6
has every reading). Mellum's logits are y W_head with y of unit RMS over 2304 values and
W_head normal(0, 0.02): standard deviation 0.96 measured. The picks are 8 of 64 by a
softmax in eight layers; the gap between the 8th and the 9th router LOGIT (the softmax
keeps their order; a token's 64 logits have a standard deviation of 0.96), least over the
layers, is 0.0006 / 0.0027 / 0.0067 / 0.013 / 0.029 at the 5th / 25th / 50th / 75th / 95th
percentile: 12% of the positions are 0.02 clear of a tie, 34% are 0.01 clear. A flipped
pick costs LESS here than in the latent block: the 8th and 9th experts carry nearly equal
small weights of a sum renormalised to one, so every checked position of every run, tie or
not, read within 0.08 of the reference's best logit.
  1. JUDGED positions (gap at least PICK_GAP in every layer): every one's deficit within
     LOGIT_MARGIN. Readings: served, at a gap of 0.01 (eight runs): 0.0-0.076 (one
     reading of 0.076, the others under 0.04); at 0.02 (nine runs, 80-240 judged a run):
     0.0-0.042, seven of them under 0.02; so PICK_GAP is 0.02. Tokens picked by
     three WRONG references, judged against the true one, two runs each (checked prompts
     up to 3,072 rows in one, 15,360 in the other), at a gap of 0.02: weights rounded
     to float8_e4m3, the precision below the stated bfloat16: 0.18 and 0.37; every layer
     full (the window ignored): 2.2 and 4.8; the window off by one block (`i - 1152 < j`):
     0.46 and 0.50. LOGIT_MARGIN 0.1 lies between 0.042 and 0.18.
  2. Of ALL checked positions at least MIN_SHARE_WITHIN within LOGIT_MARGIN. Readings:
     served 0.9990-1.0 of 640-2,048 positions a run (fifteen runs); float8 0.935 and 0.783, every layer
     full 0.501 and 0.103, the window off by a block 0.908 and 0.747 (the higher reading
     of each is the run whose longest checked prompt was 3,072 rows). MIN_SHARE_WITHIN
     0.97 lies between 0.9990 and 0.935.
All three wrong references fail BOTH limits in both runs; the window off by one block is
told from the true model by tokens (a request of 3,072 rows already reads 0.70 alone), so
no in-graph check was added for it (`decode_rows_window`, which counts
`min(ts + 1, window)` rows a slot a window layer a step, is pinned exactly by
tests/test_mellum.py). A seed whose greedy prompts are all inside the window (1 in 70)
judges the window only through outputs that leave it."""

import importlib.util
import os
import re

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _own_copy(folder, file_name, module_name):
    spec = importlib.util.spec_from_file_location(
        module_name, os.path.join(BENCH, folder, file_name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


base = _own_copy("modes", "serve-closed-model", "bench_modes_serve_closed_model_mellum")
# `lib.` in the name: the copy imports its neighbour trace_reduce relatively
scopes = _own_copy("lib", "scope_reduce", "lib.scope_reduce_with_attn")
scopes.SCOPE = re.compile(r"(?:^|[/\"(])((?:mla|moe|ffn|attn)/[a-z_]+|head)(?=[/\")]|$)")

base.ARCHITECTURES["mellum"] = ("mellum", "mellum_ref")
base.scope_reduce = scopes
base.LOGIT_MARGIN = 0.1
base.PICK_GAP = 0.02
base.MIN_SHARE_WITHIN = 0.97
LONG_PROMPT = 4096
LONG_CHECKED = 2


class MellumServed(base.ModelServed):
    last_stats = {}       # of the newest server of this process, for `run`

    def shutdown(self):
        # the pools' peaks and the paths that ran, read before the engine goes
        MellumServed.last_stats = self.engine.stats()
        super().shutdown()

    def check_outputs(self, measured):
        """The copy's check over a choice of this mode's own: LONG_CHECKED of the
        greedy requests served in full with the LONGEST kind of prompt the seed's greedy
        requests offer (LONG_PROMPT rows or more; else beyond the window; else any), and
        the rest of CHECKED_REQUESTS from the others, both by the seed."""
        greedy = [r for r in measured if r["ok"] and r["greedy"] and r["output"]]
        rng = np.random.default_rng([int(self.ctx.seed), 33])
        # greedy requests are the even ones, so a seed's greedy prompts are every other
        # entry of its shuffled cycle of eight: 5 seeds in 70 offer no greedy prompt of
        # LONG_PROMPT rows and 1 in 70 none beyond the window (its outputs of 512 still
        # leave it): such a run is judged on the longest kind its traffic has
        mix = self.ctx.traffic["requests"]
        offered = {base.traffic_lib.closed_request(mix, self.ctx.seed, k)["prompt_len"]
                   for k in range(0, 2 * len(mix["prompt_lens"]), 2)}
        floor = next((n for n in (LONG_PROMPT, self.cfg["sliding_window"] + 1, 0)
                      if any(p >= n for p in offered)))
        long = [r for r in greedy if r["prompt_len"] >= floor]
        short = [r for r in greedy if r["prompt_len"] < floor]
        take = lambda pool, n: [pool[int(i)] for i in
                                rng.choice(len(pool), size=min(n, len(pool)), replace=False)]
        chosen = take(long, LONG_CHECKED)
        chosen += take(short, base.CHECKED_REQUESTS - len(chosen))
        ok, facts = super().check_outputs(chosen)
        facts.update(checked_prompt_lens=sorted(r["prompt_len"] for r in chosen),
                     long_floor=floor, long_offered=sum(p >= floor for p in offered),
                     long_checked=sum(r["prompt_len"] >= floor for r in chosen))
        if chosen and not facts["long_checked"]:
            # the longest kind of prompt the seed offers was served in full by no greedy
            # request of the window: the rows it would have judged were not, and the run
            # is not a correct run of THIS cell
            ok = False
        return ok, facts


base.ModelServed = MellumServed


def run(ctx):
    run = base.run(ctx)
    stats = MellumServed.last_stats
    groups = {g["name"]: g for g in stats.get("groups") or []}
    counted = run["model1"]
    run["cache_groups"] = groups
    decode_paths = stats.get("decode_attention")
    prefill = stats.get("prefill_attention") or {}
    run["facts"].update(
        cache_groups=groups, prefill_attention=prefill,
        prefix_cache=stats.get("prefix_cache"),
        moe_kernel_passes=counted.get("moe_kernel_passes"),
        decode_rows_full=counted.get("decode_rows_full"),
        decode_rows_window=counted.get("decode_rows_window"))
    facts = run["facts"]
    if facts.get("checked") and not facts.get("long_checked"):
        run["why_incorrect"].append(
            f"none of the {facts['checked']} checked requests has a prompt of "
            f"{facts.get('long_floor')} rows or more, though the seed's greedy requests offer "
            f"{facts.get('long_offered')} such lengths")
    if not isinstance(decode_paths, dict) or "gather" in decode_paths.values():
        run["why_incorrect"].append(
            f"the decode step gathered in some cache group: {decode_paths}")
        run["correct"] = False
    if prefill.get("path") != "flash" or prefill.get("cold_gather"):
        run["why_incorrect"].append(f"a prefill gathered: {prefill}")
        run["correct"] = False
    return run
