"""`serve-closed-model`'s loop, server and window for SDAR-30B-A3B-Chat, which generates
by DIFFUSION OVER BLOCKS (6 of 48 layers, every expert, the whole vocabulary). As the
other models' modes do, this file loads a copy of that mode of its OWN and sets in the copy
(in memory; the file on disk is Moonlight's and is not touched) the architecture's builder
and reference and a by-scope reduction of the trace. The reduction is `lib/stage_times.py`
AS IT STANDS (its STAGES name `attn/*`, `moe/*`, `loop/*`, `head`, `embed`, `norm`), handed
on in the tables' form that the accepted readers of `run["scopes"]` read: no copy of
`scope_reduce`. What is this mode's own: a request's record keeps `fixed_at` and
`confidence` from the stream's last frame, token ids are drawn below the traffic's
`token_ids_below` (never the mask token), and the verdict.

What is checked: TWELVE greedy requests served in full in the window, six of them with
the two longest prompt lengths the seed's greedy requests offer, REPLAYED by
`reference/sdar_ref.replay` in float32: for each served block and each of its denoising
passes s, the block with the tokens of `fixed_at` < s in place and the mask elsewhere,
behind the prompt and the committed blocks, gives the logits and confidences the engine's
pass saw. The stream returns, beside `fixed_at`, each token's CONFIDENCE (the probability
the pass that fixed it gave it), so the comparison is of numbers and not only of picks.
Three limits and a backstop:

  1. TOKEN. Each served token's reference logit against its position's largest AT THE
     PASS IT WAS FIXED (the deficit; the mask token's own logit at -inf on both sides).
     Tie-aware as the other expert cells': of the JUDGED positions, those whose router
     gaps are PICK_GAP clear in every layer, at least MIN_JUDGED_WITHIN within
     LOGIT_MARGIN; the backstop: of ALL positions at least MIN_SHARE_WITHIN within
     SHARE_MARGIN.
  2. POSITION, judged at EVERY pass that had several masked positions: how far the best
     position the pass left masked is ahead, in the reference's log-confidence, of the
     one it fixed (the slack; 0 where it fixed the reference's). At least
     MIN_POSITION_SHARE of the passes within POSITION_MARGIN.
  3. CONFIDENCE. The log of the confidence the stream returned for a token against the
     reference's log-probability of that token at that pass (the drift): of the judged
     positions at least MIN_DRIFT_WITHIN within DRIFT_MARGIN.

THE READINGS that place the constants (my chip runs, PR 40: nine seeds of twelve checked
requests; the five WRONG programs replayed on two of them, float8 and the causal block on
five; PERF.md section 6 has the table). Each limit lies between what served runs read at
the most and what the WRONG programs it is there to tell read at the least:

  * Limit 3 is the precision's. bfloat16 moves a token's log-confidence by 0.006 (the
    root mean square over the judged positions: 0.0058-0.0072, nine seeds): of the judged
    positions (1,125-1,834 a run) 0.0-0.27% drift by more than 0.03. float8 weights drift
    by 0.038-0.045 and 43-57% of the judged positions by more than 0.03; a block attended
    causally by 0.076-0.123 and 70-88%; the commit pass left out by 0.115-0.147 and
    61-72%; a block of 8 by 0.089-0.116 and 79-82%; in the LEAST moved single request
    float8 moves 11% against a served request's 0.3% at the most. DRIFT_MARGIN = 0.03,
    MIN_DRIFT_WITHIN = 0.9 (served 0.9973 at the least, a wrong program 0.57 at the most).
    Positions fixed left to right hold the true tokens and confidences (0.0): limit 2's.
  * Limit 1. A served token is the reference's own best nearly everywhere (the median
    deficit is 0.0), because the stream's own tokens are the context on both sides: a
    token flipped at a tie is replayed as served. Of the judged positions 0.0-0.27% lie
    more than 0.015 under the best (nine seeds of twelve requests; 0.0-0.71% with four
    requests, ten seeds); float8 weights 4.7-16.6%, causal 13-36%, no commit 20% and 30%,
    block 8 25% and 23%. How many flip is a REQUEST's property (the token it loops on:
    0-60% a request under one wrong program), which is why four requests left float8 at
    1.4% on one seed and twelve are checked. LOGIT_MARGIN = 0.015, MIN_JUDGED_WITHIN =
    0.99. The backstop over ALL positions, bursts and ties with them: MIN_SHARE_WITHIN =
    0.9 within SHARE_MARGIN = 0.1 (served 1.0 always; the causal block 0.82 on one seed).
  * Limit 2. Seeded logits are nearly flat, so a block's masked positions lie close in
    confidence (the best a median 0.010 ahead of the second) and a pass often fixes
    another position than the reference's: the same as the reference's in 73-75% of the
    passes, but more than 0.03 behind it in only 0.73-1.08% of them (nine seeds,
    4,200-6,300 passes a run). Left to right 13.3% and 13.6%; float8 4.5-6.1%, causal 3.6-8.5%, block 8 5.7%
    and 3.6%, no commit 7.7% and 8.5%. POSITION_MARGIN = 0.03, MIN_POSITION_SHARE = 0.98.
    (The older reading, the share of the passes 0.03 CLEAR that fixed the reference's
    position, is kept in the facts: served 93-96%, left to right 48%; it judges a tenth of
    the passes.)
  * TOGETHER, on every seed tried: float8 weights and the causal block fail limits 1, 2
    and 3 each (five seeds), the missing commit pass and the block of 8 likewise (two
    seeds); left to right fails limit 2 (two seeds).

SDAR_WRONG_REFERENCE (a builder's facility, unset in every measured run): a comma list of
`reference/sdar_ref.WRONG` names, or `all`. For each, what that WRONG program would have
fixed along the checked blocks (its tokens at the served positions, its positions at the
served passes) is judged against the true reference by the same limits, and the readings
go to the facts line under `wrong_references`; the run's `correct` is not touched."""

import importlib.util
import os
import time

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _own_copy(folder, file_name, module_name):
    spec = importlib.util.spec_from_file_location(
        module_name, os.path.join(BENCH, folder, file_name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


base = _own_copy("modes", "serve-closed-model", "bench_modes_serve_closed_model_sdar")
base.ARCHITECTURES["sdar_moe"] = ("sdar", "sdar_ref")


class StageTables:
    """`lib/stage_times.py`'s tables of a trace directory in the form the readers of
    `run["scopes"]` read: {program: {"scopes": {stage: s}, "kernels": {kernel: s}}}. A
    stage's seconds by kind of operation name a Mosaic kernel for itself."""

    @staticmethod
    def reduce_dir(trace_dir):
        from lib import stage_times

        tables = stage_times.tables_at(trace_dir)
        if not tables:
            return None
        out = {}
        for program, entry in tables["modules"].items():
            kernels = {}
            for kinds in entry["kinds"].values():
                for kind, seconds in kinds.items():
                    if kind in KERNELS:
                        kernels[kind] = kernels.get(kind, 0.0) + seconds
            out[program] = {"scopes": dict(entry["stages"]), "kernels": kernels, "attend_s": 0.0}
        return out


# the Mosaic kernels this model's programs call, by the names they carry in a trace
KERNELS = ("paged_attention_grouped", "_causal_rows_call", "grouped_swiglu", "routed_combine")
base.scope_reduce = StageTables

# The logits are y W_head with y of unit RMS over 2048 values and W_head normal(0, 0.02):
# standard deviation 0.9, the largest of 151,936 some 4.4 of them; a confidence (the
# largest's softmax probability) is about 1e-4. The readings that place the constants: the
# docstring, and PERF.md section 6.
base.LOGIT_MARGIN = 0.015
base.PICK_GAP = 0.015
base.MIN_SHARE_WITHIN = 0.9
base.CHECKED_REQUESTS = 12
MIN_JUDGED_WITHIN = 0.99    # limit 1's judged positions: a share, not every one
SHARE_MARGIN = 0.1          # the margin of limit 1's backstop over ALL positions
POSITION_MARGIN = 0.03      # limit 2: the slack of a pass, in the reference's log-confidence
MIN_POSITION_SHARE = 0.98
DRIFT_MARGIN = 0.03         # limit 3: a returned confidence against the reference's, in logs
MIN_DRIFT_WITHIN = 0.9
CONF_GAP = 0.03             # a fact, no limit: the passes this clear that fixed the reference's
LONG_CHECKED = 6
LONG_KINDS = 2              # "long": the seed's LONG_KINDS longest greedy prompt lengths
PAD_TO = 2048


class SdarServed(base.ModelServed):
    last_stats = {}       # of the newest server of this process, for `run`

    def shutdown(self):
        SdarServed.last_stats = self.engine.stats()
        super().shutdown()

    def prompt(self, index, length):
        return base.traffic_lib.prompt_tokens(
            self.ctx.seed, index, length, self.ctx.traffic["requests"]["token_ids_below"])

    def request(self, spec, due, measured=True):
        """`lib/serving.Served.request` with token ids below the mask token's and the
        stream's `fixed_at` and `confidence` kept in the record."""
        body = {"prompt": self.prompt(spec["index"], spec["prompt_len"]),
                "max_new_tokens": spec["max_new_tokens"]}
        if spec["temperature"]:
            body.update(temperature=spec["temperature"], seed=spec["seed"])
        sent = time.monotonic()
        reply = base.serving.sse.generate(self.port, body, base.serving.REQUEST_TIMEOUT_S)
        done = reply["done"]
        greedy = not spec["temperature"]
        record = {
            "index": spec["index"], "due": due, "sent": sent,
            "first": reply["first"], "last": reply["last"], "end": reply["end"],
            "tokens": len(reply["tokens"]), "prompt_len": spec["prompt_len"],
            "max_new_tokens": spec["max_new_tokens"], "greedy": greedy,
            "status": reply["status"],
            "queue_wait": (done.get("metrics") or {}).get("queue_wait"),
            "ok": (reply["status"] == 200 and reply["error"] is None
                   and len(reply["tokens"]) == spec["max_new_tokens"]
                   and len(done.get("fixed_at") or ()) == spec["max_new_tokens"]
                   and len(done.get("confidence") or ()) == spec["max_new_tokens"]
                   and done.get("finish_reason") in ("length", "stop")),
            "error": reply["error"], "measured": measured,
            "output": reply["tokens"] if greedy else None,
            "fixed_at": done.get("fixed_at") if greedy else None,
            "confidence": done.get("confidence") if greedy else None,
        }
        with self._lock:
            self.records.append(record)
        return record

    def _chosen(self, measured):
        """LONG_CHECKED of the greedy requests served in full with the LONG_KINDS longest
        prompt lengths the seed's greedy requests offer, the rest of CHECKED_REQUESTS from
        the others."""
        greedy = [r for r in measured if r["ok"] and r["greedy"] and r["output"]]
        rng = np.random.default_rng([int(self.ctx.seed), 40])
        mix = self.ctx.traffic["requests"]
        offered = sorted({base.traffic_lib.closed_request(mix, self.ctx.seed, k)["prompt_len"]
                          for k in range(0, 2 * len(mix["prompt_lens"]), 2)})
        floor = offered[-min(LONG_KINDS, len(offered))]
        take = lambda pool, n: [pool[int(i)] for i in
                                rng.choice(len(pool), size=min(n, len(pool)), replace=False)]
        chosen = take([r for r in greedy if r["prompt_len"] >= floor], LONG_CHECKED)
        chosen += take([r for r in greedy if r["prompt_len"] < floor],
                       base.CHECKED_REQUESTS - len(chosen))
        return chosen, floor, offered

    def check_outputs(self, measured):
        """The module docstring's limits over `_chosen`'s requests. The reference runs
        once a request (and once more for each WRONG reference asked for)."""
        chosen, floor, offered = self._chosen(measured)
        facts = {"checked": 0, "max_logit_deficit": None, "logit_deficits": [],
                 "logit_margin": base.LOGIT_MARGIN, "pick_gap": base.PICK_GAP}
        if not chosen:
            return False, facts
        ref = self.reference
        gen = ref.generation(self.cfg)
        B = gen["block_length"]
        wrong = os.environ.get("SDAR_WRONG_REFERENCE", "")
        names = ref.WRONG if wrong == "all" else tuple(filter(None, wrong.split(",")))
        parts = {name: [] for name in ("served",) + names}
        for r in chosen:
            prompt = self.prompt(r["index"], r["prompt_len"])
            true = ref.replay(self.params, self.cfg, prompt, r["output"], r["fixed_at"],
                              pad_to=PAD_TO)
            # the served confidences by block, as `replay` cuts the blocks
            sure = np.asarray([np.nan] * (len(prompt) % B) + list(r["confidence"]), float)
            sure = sure[:len(true) * B].reshape(len(true), B)
            parts["served"].append(_readings(ref, true, None, sure, self.params, self.cfg))
            for name in names:
                other = true if name == "left_to_right" else ref.replay(
                    self.params, self.cfg, prompt, r["output"], r["fixed_at"], wrong=name,
                    pad_to=PAD_TO)
                parts[name].append(_readings(ref, true, other, name, self.params, self.cfg))
        read = {name: _limits(rows) for name, rows in parts.items()}
        long_checked = sum(r["prompt_len"] >= floor for r in chosen)
        facts.update(read["served"], checked=len(chosen),
                     logit_deficits=[float(p["deficit"].max()) for p in parts["served"]],
                     limits={"min_judged_within": MIN_JUDGED_WITHIN, "share_margin": SHARE_MARGIN,
                             "min_share_within": base.MIN_SHARE_WITHIN,
                             "position_margin": POSITION_MARGIN,
                             "min_position_share": MIN_POSITION_SHARE,
                             "drift_margin": DRIFT_MARGIN,
                             "min_drift_within": MIN_DRIFT_WITHIN, "conf_gap": CONF_GAP},
                     checked_prompt_lens=sorted(r["prompt_len"] for r in chosen),
                     greedy_prompt_lens_offered=offered, long_floor=floor,
                     long_checked=long_checked,
                     fixed_at_counts=np.bincount(np.concatenate(
                         [np.asarray(r["fixed_at"]) for r in chosen]),
                         minlength=gen["denoising_steps"]).tolist())
        if names:
            facts["wrong_references"] = {name: read[name] for name in names}
        dump = os.environ.get("SDAR_DUMP_READINGS")
        if dump:
            np.savez(dump, **{f"{name}.{key}": np.concatenate([p[key] for p in rows])
                              for name, rows in parts.items() for key in rows[0]})
        return not read["served"]["fails"] and long_checked > 0, facts


def _readings(ref, true, other, given, params, cfg):
    """One checked request's readings, position by position and pass by pass, of what a
    program fixed along the served blocks, judged by the TRUE reference `true`
    (`sdar_ref.replay`'s blocks). `other` None: the served stream itself (its tokens, its
    `fixed_at`, and `given` (blocks, B) the confidences it returned). Else the WRONG
    program `given` whose replay is `other`: its tokens and confidences at the served
    positions, its positions at the served passes.
      deficit (positions,): the true logit of the token under the true largest, at the
        pass the served stream fixed the position; gap: that row's least pick gap;
      drift (positions,): the log of the confidence the program gave its token less the
        true log-probability of that token there;
      agrees (passes,): whether the program fixed the true reference's positions at that
        pass; clear: the pass's gap between its best and second masked true
        log-confidence (inf where one position is masked); slack: how far the best
        position the program left masked is ahead of the worst it fixed, 0 if behind."""
    gen = ref.generation(cfg)
    name = None if other is None else given
    gap, logc, agrees, clear, slack, items = [], [], [], [], [], []
    for b, block in enumerate(true):
        fixed = block["fixed_at"]
        want = ref.picks(block, gen)
        got = want if other is None else ref.picks(
            other[b], gen, "left_to_right" if name == "left_to_right" else None)
        for s in range(len(block["best"])):
            at = np.flatnonzero(fixed == s)
            gap.append(block["gaps"][s, at])
            if other is None:
                items.append((b, s, at, block["tokens"][at]))
                logc.append(np.log(given[b, at]))
            else:
                items.append((b, s, at, other[b]["x0"][s, at]))
                logc.append(other[b]["conf"][s, at])
            masked = np.flatnonzero(fixed >= s)
            conf = block["conf"][s]
            ranked = np.sort(conf[masked])[::-1]
            clear.append(ranked[0] - ranked[1] if len(ranked) > 1 else np.inf)
            served_at = at if other is None else got[s][0]
            agrees.append(np.array_equal(np.sort(served_at), want[s][0]))
            left = np.setdiff1d(masked, served_at)
            slack.append(max(0.0, conf[left].max() - conf[served_at].min())
                         if left.size and len(served_at) else 0.0)
    if other is None:
        logit = [true[b]["served"][s, at] for b, s, at, _ in items]
    else:
        # the true logits of the WRONG program's tokens, read from the true replay's rows
        logit = ref.logits_of(params, cfg, true, items)
    cat = lambda parts: np.concatenate(parts) if parts else np.zeros(0)
    best = cat([true[b]["best"][s, at] for b, s, at, _ in items])
    lse = best - cat([true[b]["conf"][s, at] for b, s, at, _ in items])
    logit = cat(logit)
    return {"deficit": best - logit, "gap": cat(gap), "drift": cat(logc) - (logit - lse),
            "agrees": np.asarray(agrees, bool),
            "clear": np.asarray(clear, float), "slack": np.asarray(slack, float)}


def _limits(rows):
    """A program's readings over the checked requests against the limits."""
    read = {k: np.concatenate([r[k] for r in rows]) for k in rows[0]}
    deficit, drift, slack = read["deficit"], np.abs(read["drift"]), read["slack"]
    judged = read["gap"] >= base.PICK_GAP
    several = np.isfinite(read["clear"])
    sure = several & (read["clear"] >= CONF_GAP)
    share_of = lambda hits: float(hits.mean()) if hits.size else None
    within = share_of(deficit[judged] <= base.LOGIT_MARGIN)
    share = share_of(deficit <= SHARE_MARGIN)
    position = share_of(slack[several] <= POSITION_MARGIN)
    near = share_of(drift[judged] <= DRIFT_MARGIN)
    fails = [limit for limit, reading, least in (
        ("judged", within, MIN_JUDGED_WITHIN), ("share", share, base.MIN_SHARE_WITHIN),
        ("position", position, MIN_POSITION_SHARE), ("confidence", near, MIN_DRIFT_WITHIN))
        if reading is not None and reading < least]
    return {"positions": int(deficit.size), "judged": int(judged.sum()),
            "left_out": int((~judged).sum()), "judged_within_margin": within,
            "max_logit_deficit": float(deficit[judged].max()) if judged.any() else None,
            "share_within_margin": share, "passes": int(several.size),
            "passes_judged": int(several.sum()), "position_within_margin": position,
            "confidence_within_margin": near,
            "confidence_drift_rms": float(np.sqrt((drift[judged] ** 2).mean()))
            if judged.any() else None,
            "passes_clear": int(sure.sum()),
            "clear_position_share": share_of(read["agrees"][sure]),
            "position_share_all": share_of(read["agrees"][several]), "fails": fails}


base.ModelServed = SdarServed


def run(ctx):
    run = base.run(ctx)
    stats = SdarServed.last_stats
    counted = run["model1"]
    decode_paths = stats.get("decode_attention")
    prefill = stats.get("prefill_attention") or {}
    facts = run["facts"]
    facts.update(
        prefill_attention=prefill, prefix_cache=stats.get("prefix_cache"),
        diffusion=stats.get("diffusion"),
        **{name: counted.get(name) for name in (
            "moe_kernel_passes", "moe_rows_computed", "decode_rows_full", "block_passes",
            "blocks_committed", "tokens_fixed_by_threshold", "tokens_fixed_by_rank")})
    run["why_incorrect"] = [
        (f"of the {facts.get('judged')} served greedy positions clear of a tie in the picks by "
         f"{base.PICK_GAP}, {facts.get('judged_within_margin')} are within {base.LOGIT_MARGIN} "
         f"of the reference's best logit at the pass they were fixed (at least "
         f"{MIN_JUDGED_WITHIN}) and {facts.get('confidence_within_margin')} returned a "
         f"confidence within {DRIFT_MARGIN} of the reference's in logs (at least "
         f"{MIN_DRIFT_WITHIN}); of all {facts.get('positions')}, "
         f"{facts.get('share_within_margin')} within {SHARE_MARGIN} (at least "
         f"{base.MIN_SHARE_WITHIN}); of the {facts.get('passes_judged')} passes with several "
         f"masked positions, {facts.get('position_within_margin')} fixed one within "
         f"{POSITION_MARGIN} of the reference's best in log-confidence (at least "
         f"{MIN_POSITION_SHARE}); {facts.get('long_checked')} of the checked "
         f"prompts have {facts.get('long_floor')} rows or more")
        if why.startswith("served greedy tokens") else why for why in run["why_incorrect"]]
    if not isinstance(decode_paths, dict) or "gather" in decode_paths.values():
        run["why_incorrect"].append(f"the block pass gathered: {decode_paths}")
        run["correct"] = False
    if prefill.get("path") != "flash" or prefill.get("cold_gather"):
        run["why_incorrect"].append(f"a prefill gathered: {prefill}")
        run["correct"] = False
    return run
