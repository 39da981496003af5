"""`serve-closed-model`'s loop, server, window and tie-aware verdict for
command-a-plus-05-2026 served as one chip's share of its deployment (16 of 128 routed
experts, 32,768 of 262,144 vocabulary rows, 4 of 32 layers). As `serve-closed-xing` and
`serve-closed-mellum` do, this file loads a copy of that mode of its OWN and sets in the
copy (in memory; the file on disk is Moonlight's and is not touched) the architecture's
builder and reference, this model's verdict constants, a by-scope reduction of the trace
that knows this block's scopes (`attn/*`, `moe/*`, `norm`, `embed`, `head`), and a choice
of the checked requests that holds prompts beyond the window and the longest the seed's
greedy requests offer. Everything else is the copy's.

What is checked: prompt + served tokens of four greedy requests served in full in the
window, through `reference/command_a_ref.sequence_logits` in float32 with the SAME held
range (experts 0..15 of 128: what the absent experts would add is left out on both
sides): every served token's reference logit against its position's largest (the
deficit). At least TWO of the four have prompts beyond the window of 4,096 (every checked
position of those has left the first rows behind, and a slot of 8,192 + 1,024 rows has
wrapped its ring of 33 pages twice), one of them of LONG_PROMPT = 8,192 rows where the
seed's greedy requests offer that length (the generator's even requests are the greedy
ones and see four of the eight lengths: half of the seeds offer 8,192, 69 in 70 a prompt
beyond the window; a run is judged on the longest kind its traffic has).

THE VERDICT, as the other expert cells' in kind (the served token's reference logit
against its position's largest: the deficit; positions near a tie in the picks set
apart) and this cell's own in two things, both from what the chip showed (my chip runs,
PR 38: eight seeds, 32 checked requests, 17,664 positions, the deficits and gaps of every
position kept; PERF.md section 6 has the table):

  * Greedy decoding under seeded weights LOOPS (a request's 256-1,024 served tokens are
    2-110 distinct ones), so a position near a tie comes back with the loop, its flipped
    pick is written into the cache again and again, and from position 170-450 on it
    reaches positions that are themselves clear of any tie: 3 of the 32 requests hold
    such a burst (29, 45 and 56 positions over 0.03, judged ones up to 0.37), and "every
    judged position within the margin" failed 3 seeds of 8. Before position 128 no request
    has one. Limit 1 therefore judges the first EARLY served positions of each checked
    request, and is a SHARE (positions of one loop are not independent, and one stray
    position is no other function).
  * The deficits of a wrong reference that is wrong a little everywhere (float8 weights,
    a window one block wider) are small and many, so the margin is where bfloat16 ends.

  1. Of the positions among each request's first EARLY = 96 whose picks are PICK_GAP =
     0.015 clear of a tie in every layer (99-225 a run), at least MIN_JUDGED_WITHIN =
     0.985 within LOGIT_MARGIN = 0.03. Readings, the share OVER the margin: served 0.0%
     on all eight seeds (the worst deficit 0.021); float8 4.4-11.5%; the window a block
     wider 2.0-4.3%; rotary on the full layers 10-40%; the held range shifted 17-67%;
     the shared experts summed 99-100%. 1.5% lies between 0.0 and 2.0. Seven more seeds
     run with these constants: served 0.0% on five and ONE position on two (0.48% and
     0.51%, deficits 0.072 and 0.107: a maximum would have failed them); float8 7.8% and
     1.8% (4 of 227: it fails); the wider window 2.9% and 0.4% (1 of 227: on that seed
     it PASSES; its two prompts beyond the window were of 5,120 and 6,144 rows), and
     twelve more, served alone: 0.0% on all twelve (the worst deficit 0.018).
  2. Of ALL checked positions at least MIN_SHARE_WITHIN = 0.94 within SHARE_MARGIN =
     0.1: the backstop that reads every position served, bursts and ties with them.
     Readings within 0.1: served 0.9805-1.0 (thirty-five seeds); float8 0.946-0.993 (it
     fails by limit 1 and not by this one); rotary 0.773-0.931; the held range shifted
     0.618-0.870; the shared experts summed 0.00-0.05.

COMMAND_A_WRONG_REFERENCE (a builder's facility, unset in every measured run): a comma
list of `reference/command_a_ref.WRONG` names, or `all`. For each, the tokens that WRONG
reference picks along the checked sequences are judged against the true reference by the
same two limits, and the readings go to the facts line under `wrong_references`; the
run's `correct` is not touched."""

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


base = _own_copy("modes", "serve-closed-model", "bench_modes_serve_closed_model_command_a")
# `lib.` in the name: the copy imports its neighbour trace_reduce relatively
scopes = _own_copy("lib", "scope_reduce", "lib.scope_reduce_command_a")
scopes.SCOPE = re.compile(
    r"(?:^|[/\"(])((?:mla|moe|ffn|attn)/[a-z_]+|head|norm|embed)(?=[/\")]|$)")

base.ARCHITECTURES["cohere2_moe"] = ("command_a", "command_a_ref")
base.scope_reduce = scopes
# The logits are y W_e^T with y of unit variance over 4096 values and W_e normal(0,
# 0.02): standard deviation 1.28. The picks are 8 of 128 by sigmoid scores in four
# layers (the gap is read in the router's logit), and a flipped pick matters here only
# where it moves a HELD expert in or out (an eighth of them) or the sum that divides the
# weights. The readings that place the constants: the docstring, and PERF.md section 6.
base.LOGIT_MARGIN = 0.03
base.PICK_GAP = 0.015
base.MIN_SHARE_WITHIN = 0.94
EARLY = 96                  # limit 1 judges a request's first served positions
MIN_JUDGED_WITHIN = 0.985   # ... and is a share of them
SHARE_MARGIN = 0.1          # the margin of limit 2
LONG_PROMPT = 8192
BEYOND_WINDOW_CHECKED = 2


class CommandAServed(base.ModelServed):
    last_stats = {}       # of the newest server of this process, for `run`

    def shutdown(self):
        # the pools' peaks and the paths that ran, read before the engine goes
        CommandAServed.last_stats = self.engine.stats()
        super().shutdown()

    def _offered(self):
        """The prompt lengths the seed's greedy (even) requests see."""
        mix = self.ctx.traffic["requests"]
        return {base.traffic_lib.closed_request(mix, self.ctx.seed, k)["prompt_len"]
                for k in range(0, 2 * len(mix["prompt_lens"]), 2)}

    def check_outputs(self, measured):
        """The module docstring's two limits over a choice of this mode's own: among the
        greedy requests served in full, ONE of LONG_PROMPT rows where the seed offers
        that, up to BEYOND_WINDOW_CHECKED with prompts beyond the window, the rest of
        CHECKED_REQUESTS from the others, all by the seed. The reference runs once a
        request (and once more for each WRONG reference asked for)."""
        greedy = [r for r in measured if r["ok"] and r["greedy"] and r["output"]]
        rng = np.random.default_rng([int(self.ctx.seed), 38])
        window = self.cfg["sliding_window"]
        offered = self._offered()

        def take(pool, n):
            n = max(0, min(n, len(pool)))
            return [pool[int(i)] for i in rng.choice(len(pool), size=n, replace=False)] if n else []

        chosen = take([r for r in greedy if r["prompt_len"] >= LONG_PROMPT], 1)
        rest = [r for r in greedy if r not in chosen]
        chosen += take([r for r in rest if r["prompt_len"] > window],
                       BEYOND_WINDOW_CHECKED - len(chosen))
        rest = [r for r in greedy if r not in chosen]
        chosen += take([r for r in rest if r["prompt_len"] <= window] or rest,
                       base.CHECKED_REQUESTS - len(chosen))
        facts = {"checked": 0, "max_logit_deficit": None, "logit_deficits": [],
                 "logit_margin": base.LOGIT_MARGIN, "pick_gap": base.PICK_GAP}
        if not chosen:
            return False, facts
        wrong = os.environ.get("COMMAND_A_WRONG_REFERENCE", "")
        names = self.reference.WRONG if wrong == "all" else tuple(filter(None, wrong.split(",")))
        deficits, gaps, stds = {name: [] for name in ("served",) + names}, [], []
        for r in chosen:
            prompt = base.traffic_lib.prompt_tokens(self.ctx.seed, r["index"], r["prompt_len"],
                                                    self.cfg["vocab_size"])
            seq = prompt + r["output"]
            seq = seq + [0] * (-len(seq) % base.PAD_TO)
            rows = np.arange(r["prompt_len"] - 1, r["prompt_len"] - 1 + len(r["output"]))
            true, gap = self.reference.sequence_logits(self.params, self.cfg, seq, rows, gaps=True)
            true = np.asarray(true)
            under = lambda picked: true.max(-1) - true[np.arange(len(rows)), picked]
            deficits["served"].append(under(np.asarray(r["output"])))
            gaps.append(np.asarray(gap))
            stds.append(float(true.std()))
            for name in names:
                # the tokens a WRONG reference picks along the served sequence
                deficits[name].append(under(np.asarray(self.reference.sequence_logits(
                    self.params, self.cfg, seq, rows, wrong=name)).argmax(-1)))
        # limit 1's positions: a request's first EARLY, clear of a tie in every layer
        judged = np.concatenate([np.arange(len(g)) < EARLY for g in gaps]) \
            & (np.concatenate(gaps) >= base.PICK_GAP)
        read = {name: _two_limits(np.concatenate(parts), judged)
                for name, parts in deficits.items()}
        beyond = sum(r["prompt_len"] > window for r in chosen)
        longest = sum(r["prompt_len"] >= LONG_PROMPT for r in chosen)
        missing = []
        if beyond < min(BEYOND_WINDOW_CHECKED, sum(p > window for p in offered)):
            missing.append(f"{beyond} checked prompts lie beyond the window of {window}")
        if LONG_PROMPT in offered and not longest:
            missing.append(f"no checked prompt has {LONG_PROMPT} rows")
        facts.update(read["served"], checked=len(chosen),
                     logit_deficits=[float(d.max()) for d in deficits["served"]],
                     early=EARLY, min_judged_within=MIN_JUDGED_WITHIN,
                     share_margin=SHARE_MARGIN, min_share_within=base.MIN_SHARE_WITHIN,
                     logit_std=max(stds),
                     checked_prompt_lens=sorted(r["prompt_len"] for r in chosen),
                     greedy_prompt_lens_offered=sorted(offered),
                     beyond_window_checked=beyond, long_checked=longest, checks_missing=missing)
        if names:
            facts["wrong_references"] = {name: read[name] for name in names}
        # the longest kinds of prompt the seed offers, served in full by no greedy request of
        # the window: the rows they would have judged were not, and the run is not a correct
        # run of THIS cell
        return not read["served"]["fails"] and not missing, facts


def _two_limits(deficits, judged):
    """One set of tokens' deficits under the true reference (every checked position, in
    order) against the two limits; `judged` marks limit 1's positions."""
    early = deficits[judged]
    within = float((early <= base.LOGIT_MARGIN).mean()) if early.size else None
    share = float((deficits <= SHARE_MARGIN).mean())
    fails = [limit for limit, failed in (
        ("judged", within is not None and within < MIN_JUDGED_WITHIN),
        ("share", share < base.MIN_SHARE_WITHIN)) if failed]
    return {"positions": int(deficits.size), "judged": int(early.size),
            "left_out": int(deficits.size - early.size), "judged_within_margin": within,
            "max_logit_deficit": float(early.max()) if early.size else None,
            "share_within_margin": share, "fails": fails}


base.ModelServed = CommandAServed


def run(ctx):
    run = base.run(ctx)
    stats = CommandAServed.last_stats
    groups = {g["name"]: g for g in stats.get("groups") or []}
    counted = run["model1"]
    run["cache_groups"] = groups
    decode_paths = stats.get("decode_attention")
    prefill = stats.get("prefill_attention") or {}
    facts = run["facts"]
    facts.update(
        cache_groups=groups, prefill_attention=prefill,
        prefix_cache=stats.get("prefix_cache"),
        experts_held=stats.get("experts_held"), vocab_slice=stats.get("vocab_slice"),
        **{name: counted.get(name) for name in (
            "moe_kernel_passes", "moe_rows_computed", "moe_picks_routed", "moe_picks_held",
            "decode_moe_picks_routed", "decode_moe_picks_held", "decode_rows_full",
            "decode_rows_window")})
    run["why_incorrect"] = [
        (f"of the {facts.get('judged')} served greedy positions among each checked request's "
         f"first {EARLY} that are clear of a tie in the picks by {base.PICK_GAP}, "
         f"{facts.get('judged_within_margin')} are within {base.LOGIT_MARGIN} of the "
         f"reference's best logit (at least {MIN_JUDGED_WITHIN}; the worst "
         f"{facts.get('max_logit_deficit')}), and of all {facts.get('positions')} "
         f"{facts.get('share_within_margin')} are within {SHARE_MARGIN} (at least "
         f"{base.MIN_SHARE_WITHIN})")
        if why.startswith("of ") and "served greedy positions" in why else why
        for why in run["why_incorrect"]]
    if facts.get("checked") and facts.get("checks_missing"):
        run["why_incorrect"].append(
            f"of the {facts['checked']} checked requests " + "; ".join(facts["checks_missing"])
            + f", though the seed's greedy requests offer {facts['greedy_prompt_lens_offered']}")
    if not isinstance(decode_paths, dict) or "gather" in decode_paths.values():
        run["why_incorrect"].append(
            f"the decode step gathered in some cache group: {decode_paths}")
        run["correct"] = False
    if prefill.get("path") != "flash" or prefill.get("cold_gather"):
        run["why_incorrect"].append(f"a prefill gathered: {prefill}")
        run["correct"] = False
    return run
