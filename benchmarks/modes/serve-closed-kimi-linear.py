"""`serve-closed-model`'s loop, server, window and tie-aware verdict for
Kimi-Linear-48B-A3B-Instruct served as one chip's share of its deployment (32 of 256
routed experts, 20,480 of 163,840 vocabulary rows, 13 of 27 layers: ten of delta-rule
linear attention whose per-slot state is a cache group of its own, three of position-free
latent attention). As the other models' modes do, this file loads a copy of that mode of
its OWN and sets in the copy (in memory; the file on disk is Moonlight's and is not
touched) the architecture's builder and reference, this model's verdict, and a by-scope
reduction of the trace: `lib/stage_times.reduce_path(path, stages=STAGES + ("kda/*",))`,
handed on in the tables' form that the accepted readers of `run["scopes"]` read (no copy
of `scope_reduce`). The builder is imported HERE, at the top, so a checkout whose program
lacks the model fails at once.

What is checked: prompt + served tokens of EIGHT greedy requests served in full in the
window, through `reference/kimi_linear_ref.sequence_logits` in float32, TOKEN BY TOKEN
through the recurrence, with the SAME held range and vocabulary slice: every served
token's reference logit against its position's largest (the deficit). Two of the eight
have the longest prompts the seed's greedy requests offer, two the longest answers, two a
prompt SHORTER than its bucket where the window holds such (only there do the state and
the history at `real_len` differ from those at the bucket's end).

THE VERDICT, three limits on the served tokens and one on the served state (the readings
that place each constant: PERF.md section 6, PR 45; each lies between the served runs'
worst reading and the least reading of the WRONG programs it is there to tell;
`reference/kimi_linear_ref.WRONG` lists them). The served program computes in bfloat16
through 13 layers, so a tenth of its picks lie a few hundredths under the reference's best
logit whatever the router does: each token limit is a SHARE, and a wrong program whose
effect is below that noise cannot be told by tokens at all (a state kept in bfloat16:
limit 4 is a NUMBER for it; the latent layers rotated under seeded weights: PERF.md says
so with its readings).

  1. EARLY. Of the positions among a request's first EARLY = 96 generated ones whose
     picks are PICK_GAP clear of a tie in every expert layer, the share within
     LOGIT_MARGIN of the reference's best logit, REQUEST BY REQUEST (those with at least
     MIN_JUDGED of them): the LEAST is at least MIN_JUDGED_WITHIN. The limit that reads
     the hand-over from the prefill (the history not carried; the state of the bucket's
     end, which is wrong only for a prompt shorter than its bucket, two of the eight on
     some seeds: a share over all eight would hide it).
  2. ALL. Of ALL checked positions at least MIN_SHARE_WITHIN within SHARE_MARGIN: the
     backstop that reads every position served (no decay, beta = 1, the kinds shifted,
     float8).
  3. LATE. Of the LAST LATE = 64 positions of the two longest answers checked (position
     ~1,500 of generation: the state has been decayed and rewritten fifteen hundred
     times), ALL of them (the 128 clear of a tie would be 40-50, and a share of those a
     coin's): at least MIN_LATE_WITHIN within LATE_MARGIN. A wrong decay shows here
     apart from the hand-over.
  4. STATE STEP. The recurrence AS SERVED, on the blocks it served: behind the window,
     STATE_STEP_SLOTS state blocks of every KDA layer, as the timed run left them in the
     engine's own arena (its type with them), are moved ONE position on by the program's
     own step (`models/kimi_linear.kda_step_inputs`, then `kda_state_update` on the path
     the chunk program took: the kernel on the chip) from the checked requests' last
     tokens, and the block that comes back is held against the reference's float32
     `kda_step` on that block and the program's own q, k, v, g, beta: the largest
     relative error (Frobenius, a slot a layer) is at most MAX_STATE_STEP_ERROR. Beside
     it stands the same step with the state kept in bfloat16 (the reference's own,
     rounded before and after): the reading of the wrong program `state_bf16`, which
     every token limit passes. A state computed, accumulated or stored below float32
     fails here; a label is not asked.

KIMI_WRONG_REFERENCE (a builder's facility, unset in every measured run): a comma list of
`reference/kimi_linear_ref.WRONG` names, or `all`. For each, the tokens that WRONG program
picks along the checked sequences are judged against the true reference by the same
limits, and the readings go to the facts line under `wrong_references`; the run's
`correct` is not touched. KIMI_DUMP_READINGS=<file.npz> keeps every judged reading."""

import importlib.util
import os

import numpy as np

from lib import kimi_linear as _builder  # noqa: F401  (fails at once without the model)

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _own_copy(folder, file_name, module_name):
    spec = importlib.util.spec_from_file_location(
        module_name, os.path.join(BENCH, folder, file_name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


base = _own_copy("modes", "serve-closed-model", "bench_modes_serve_closed_model_kimi_linear")
base.ARCHITECTURES["kimi_linear"] = ("kimi_linear", "kimi_linear_ref")

# the Mosaic kernels this model's programs call, by the names they carry in a trace
KERNELS = ("latent_paged_attention", "_causal_rows_call", "grouped_swiglu", "routed_combine",
           "kda_step")


class StageTables:
    """`lib/stage_times.py`'s tables of a trace directory, with this model's `kda/*`
    among the stages, in the form the readers of `run["scopes"]` read: {program:
    {"scopes": {stage: s}, "kernels": {kernel: s}, "attend_s": s under `mla/attend`}}."""

    @staticmethod
    def reduce_dir(trace_dir):
        from lib import stage_times, trace_reduce

        path = trace_reduce.find_xplane(trace_dir)
        tables = path and stage_times.reduce_path(
            path, stages=stage_times.STAGES + ("kda/*",))
        if not tables:
            return None
        out = {"busy_s": tables["busy_s"]}
        for program, entry in tables["modules"].items():
            kernels = {}
            for kinds in entry["kinds"].values():
                for kind, seconds in kinds.items():
                    if kind in KERNELS:
                        kernels[kind] = kernels.get(kind, 0.0) + seconds
            out[program] = {"scopes": dict(entry["stages"]), "kernels": kernels,
                            "attend_s": entry["stages"].get("mla/attend", 0.0)}
        return out


class _Scopes:
    """What `base.run` asks of its `scope_reduce`: the tables, without the one entry
    that is no program's."""

    @staticmethod
    def reduce_dir(trace_dir):
        tables = StageTables.reduce_dir(trace_dir)
        if tables is not None:
            _Scopes.busy_s = tables.pop("busy_s")
        return tables


base.scope_reduce = _Scopes

# The logits are y W_head with y of unit RMS over 2304 values and W_head normal(0, 0.02):
# standard deviation 0.96. The readings that place the constants: PERF.md section 6.
base.LOGIT_MARGIN = 0.03
base.PICK_GAP = 0.0005      # the least gap over 12 layers of 256 experts: median 0.0004
base.MIN_SHARE_WITHIN = 0.85
base.CHECKED_REQUESTS = 8
EARLY = 96                  # limit 1 judges a request's first generated positions
MIN_JUDGED = 16             # a request with fewer judged positions is not read by limit 1
MIN_JUDGED_WITHIN = 0.62
SHARE_MARGIN = 0.1          # the margin of limit 2
LATE = 64                   # limit 3 judges the last positions of the longest answers
LATE_ANSWERS = 2
LATE_MARGIN = 0.1
MIN_LATE_WITHIN = 0.87
STATE_STEP_SLOTS = 8        # limit 4 steps this many state blocks of every KDA layer
MAX_STATE_STEP_ERROR = 1e-5


class KimiLinearServed(base.ModelServed):
    last_stats = {}       # of the newest server of this process, for `run`

    def shutdown(self):
        # the pools' peaks and the paths that ran, read before the engine goes
        KimiLinearServed.last_stats = self.engine.stats()
        super().shutdown()

    def _bucket(self, prompt_len):
        return min(b for b in self.sizes["prefill_buckets"] if b >= prompt_len)

    def _chosen(self, measured):
        """Eight of the greedy requests served in full, by the seed: two of the longest
        prompts served, two of the longest answers, two whose prompt is shorter than its
        bucket, the rest from the others."""
        greedy = [r for r in measured if r["ok"] and r["greedy"] and r["output"]]
        rng = np.random.default_rng([int(self.ctx.seed), 45])
        chosen = []

        def take(wanted, n):
            pool = [r for r in greedy if r not in chosen and wanted(r)]
            n = max(0, min(n, len(pool), base.CHECKED_REQUESTS - len(chosen)))
            if n:
                chosen.extend(pool[int(i)] for i in rng.choice(len(pool), size=n,
                                                               replace=False))

        if greedy:
            longest = max(r["prompt_len"] for r in greedy)
            answer = max(len(r["output"]) for r in greedy)
            take(lambda r: r["prompt_len"] == longest, 2)
            take(lambda r: len(r["output"]) == answer,
                 2 - sum(len(r["output"]) == answer for r in chosen))
            take(lambda r: self._bucket(r["prompt_len"]) > r["prompt_len"],
                 2 - sum(self._bucket(r["prompt_len"]) > r["prompt_len"] for r in chosen))
            take(lambda r: True, base.CHECKED_REQUESTS)
        return chosen

    def check_outputs(self, measured):
        """The module docstring's three limits over `_chosen`'s requests. The reference
        runs once a request (and once more for each WRONG program asked for)."""
        chosen = self._chosen(measured)
        facts = {"checked": 0, "max_logit_deficit": None, "logit_deficits": [],
                 "logit_margin": base.LOGIT_MARGIN, "pick_gap": base.PICK_GAP}
        if not chosen:
            return False, facts
        wrong = os.environ.get("KIMI_WRONG_REFERENCE", "")
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
                # the tokens a WRONG program picks along the served sequence
                deficits[name].append(under(np.asarray(self.reference.sequence_logits(
                    self.params, self.cfg, seq, rows, wrong=name, prompt_len=r["prompt_len"],
                    bucket=self._bucket(r["prompt_len"]))).argmax(-1)))
        clear = [g >= base.PICK_GAP for g in gaps]
        request = np.concatenate([np.full(len(c), i) for i, c in enumerate(clear)])
        early = np.concatenate([np.arange(len(c)) < EARLY for c in clear]) & np.concatenate(clear)
        # the last LATE positions of the longest answers checked
        order = sorted(range(len(chosen)), key=lambda i: -len(chosen[i]["output"]))
        tail = set(order[:LATE_ANSWERS])
        late = np.concatenate([(np.arange(len(c)) >= len(c) - LATE) if i in tail
                               else np.zeros(len(c), bool) for i, c in enumerate(clear)])
        step = state_step_readings(
            _builder.program, self.reference, self.model_cfg, self.params,
            self.engine.kv.arena, [r["output"][-1] for r in chosen], self.ctx.seed)
        told_by_state = {"served": step["error"], "state_bf16": step["error_state_bf16"]}
        read = {name: _limits(np.concatenate(parts), early, late, request,
                              told_by_state.get(name))
                for name, parts in deficits.items()}
        dump = os.environ.get("KIMI_DUMP_READINGS")
        if dump:
            np.savez(dump, gaps=np.concatenate(gaps), early=early, late=late, request=request,
                     lengths=np.asarray([len(c) for c in clear]),
                     prompt_lens=np.asarray([r["prompt_len"] for r in chosen]),
                     **{name: np.concatenate(parts) for name, parts in deficits.items()})
        padded = sum(self._bucket(r["prompt_len"]) > r["prompt_len"] for r in chosen)
        facts.update(read["served"], checked=len(chosen),
                     logit_deficits=[float(d.max()) for d in deficits["served"]],
                     early=EARLY, min_judged_within=MIN_JUDGED_WITHIN,
                     share_margin=SHARE_MARGIN, min_share_within=base.MIN_SHARE_WITHIN,
                     late=LATE, late_margin=LATE_MARGIN, min_late_within=MIN_LATE_WITHIN,
                     state_step=step, max_state_step_error=MAX_STATE_STEP_ERROR,
                     logit_std=max(stds),
                     checked_prompt_lens=sorted(r["prompt_len"] for r in chosen),
                     checked_answer_lens=sorted(len(r["output"]) for r in chosen),
                     padded_prompts_checked=padded)
        if names:
            facts["wrong_references"] = {name: read[name] for name in names}
        return not read["served"]["fails"], facts


def state_step_readings(program, reference, cfg, params, arena, tokens, seed):
    """Limit 4's readings. `arena` is the engine's (latent, state, history) as the run
    left it; `tokens` are cycled over STATE_STEP_SLOTS slots, whose blocks the seed draws
    among those a slot can hold (block 0 is scratch). For each KDA layer the drawn blocks
    go, in the arena's own type, into an arena of that one layer, the program's
    `kda_step_inputs` makes q, k, v, g, beta from the tokens' normed embedding rows and
    the blocks' histories, and `kda_state_update` moves the states one position on by the
    path the served step takes. Returns {"error": the largest relative error of a slot's
    new state against `reference.kda_step` in float32 on the same block and operands,
    "error_state_bf16": the LEAST such error of that reference with its state kept in
    bfloat16, "path", "slots", "layers", "state_dtype"}."""
    import jax
    import jax.numpy as jnp

    _, state, conv = arena
    n = min(STATE_STEP_SLOTS, state.shape[2] - 1)
    rng = np.random.default_rng([int(seed), 4545])
    blocks = np.concatenate([[0], 1 + rng.choice(state.shape[2] - 1, size=n, replace=False)])
    tokens = jnp.asarray([tokens[i % len(tokens)] for i in range(n)], jnp.int32)
    ids = jnp.arange(1, n + 1, dtype=jnp.int32)
    path = program.recurrence_path(cfg)
    f32 = jnp.float32

    @jax.jit
    def step(lp, wte, st, cv):
        x = wte[tokens].astype(f32)
        u = (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + cfg.rms_eps)
             * lp["norm1"].astype(f32)).astype(wte.dtype)
        arenas = {program.STATE: st, program.CONV: cv}
        q, k, v, g, beta, _, arenas = program.kda_step_inputs(cfg, lp, u, arenas, 0, ids, None)
        _, arenas = program.kda_state_update(arenas, 0, ids, None, q, k, v, g, beta, path)
        before, after = st[0, 0, ids].astype(f32), arenas[program.STATE][0, 0, ids].astype(f32)
        # the reference's contractions are products: float32 ones only at `highest`
        with jax.default_matmul_precision("highest"):
            true = jax.vmap(reference.kda_step)(before, q, k, v, g, beta)[0]
            kept_low = reference.as_bfloat16(jax.vmap(reference.kda_step)(
                reference.as_bfloat16(before), q, k, v, g, beta)[0])
        size = lambda a: jnp.sqrt(jnp.sum(a * a, (1, 2, 3)))
        return size(after - true) / size(true), size(kept_low - true) / size(true)

    errors, lows = [], []
    for li, lp in enumerate(params["layers"]):
        if cfg.kind(li) == "kda":
            lg = cfg.index_in_group(li)
            error, kept_low = step(lp, params["wte"], state[lg:lg + 1, :, blocks],
                                   conv[lg:lg + 1, :, blocks])
            errors.append(np.asarray(error))
            lows.append(np.asarray(kept_low))
    return {"error": float(np.max(errors)), "error_state_bf16": float(np.min(lows)),
            "path": path, "slots": n, "layers": len(errors), "state_dtype": str(state.dtype)}


def _limits(deficits, early, late, request, state_error=None):
    """One set of tokens' deficits under the true reference (every checked position, in
    order) against the three token limits; `early` and `late` mark limit 1's and limit 3's
    positions, `request` says whose each position is. `state_error`: limit 4's reading of
    the program that picked the tokens (None: its recurrence is the reference's)."""
    def within(mask, margin):
        picked = deficits[mask]
        return float((picked <= margin).mean()) if picked.size else None

    by_request = [within(early & (request == r), base.LOGIT_MARGIN)
                  for r in np.unique(request) if (early & (request == r)).sum() >= MIN_JUDGED]
    judged = min(by_request) if by_request else None
    share = float((deficits <= SHARE_MARGIN).mean())
    tail = within(late, LATE_MARGIN)
    fails = [limit for limit, failed in (
        ("early", judged is not None and judged < MIN_JUDGED_WITHIN),
        ("all", share < base.MIN_SHARE_WITHIN),
        ("late", tail is not None and tail < MIN_LATE_WITHIN),
        ("state_step", state_error is not None
         and not state_error <= MAX_STATE_STEP_ERROR)) if failed]
    return {"positions": int(deficits.size), "judged": int(early.sum()),
            "left_out": int(deficits.size - early.sum()), "judged_within_margin": judged,
            "judged_within_by_request": by_request,
            "max_logit_deficit": float(deficits[early].max()) if early.any() else None,
            "share_within_margin": share, "late_judged": int(late.sum()),
            "late_within_margin": tail, "state_step_error": state_error, "fails": fails}


base.ModelServed = KimiLinearServed


def run(ctx):
    run = base.run(ctx)
    stats = KimiLinearServed.last_stats
    groups = {g["name"]: g for g in stats.get("groups") or []}
    counted = run["model1"]
    run["cache_groups"] = groups
    run["state"] = stats.get("state")
    if run.get("scopes") is not None:
        run["scopes_busy_s"] = _Scopes.busy_s
    decode_paths = stats.get("decode_attention")
    prefill = stats.get("prefill_attention") or {}
    facts = run["facts"]
    facts.update(
        cache_groups=groups, state=stats.get("state"), prefill_attention=prefill,
        prefix_cache=stats.get("prefix_cache"),
        experts_held=stats.get("experts_held"), vocab_slice=stats.get("vocab_slice"),
        **{name: counted.get(name) for name in (
            "moe_kernel_passes", "moe_rows_computed", "moe_picks_routed", "moe_picks_held",
            "decode_moe_picks_routed", "decode_moe_picks_held", "kda_state_steps",
            "kda_prefill_rows", "mla_decode_rows")})
    run["why_incorrect"] = [
        (f"of the {facts.get('judged')} served greedy positions among each checked request's "
         f"first {EARLY} that are clear of a tie in the picks by {base.PICK_GAP}, the least "
         f"share a request within {base.LOGIT_MARGIN} of the reference's best logit is "
         f"{facts.get('judged_within_margin')} (at least {MIN_JUDGED_WITHIN}); of all "
         f"{facts.get('positions')}, {facts.get('share_within_margin')} are within "
         f"{SHARE_MARGIN} (at least {base.MIN_SHARE_WITHIN}); of the {facts.get('late_judged')} "
         f"last of the longest answers, {facts.get('late_within_margin')} are "
         f"within {LATE_MARGIN} (at least {MIN_LATE_WITHIN}); a served state block one step "
         f"on is {facts.get('state_step_error')} from the float32 recurrence's (at most "
         f"{MAX_STATE_STEP_ERROR}): fails {facts.get('fails')}")
        if why.startswith("of ") and "served greedy positions" in why else why
        for why in run["why_incorrect"]]
    state = stats.get("state") or {}
    if not isinstance(decode_paths, dict) or "gather" in decode_paths.values():
        run["why_incorrect"].append(f"the decode step gathered: {decode_paths}")
        run["correct"] = False
    if state.get("recurrence_path") != "kernel":
        run["why_incorrect"].append(f"the recurrence did not run as the kernel: {state}")
        run["correct"] = False
    if prefill.get("path") != "flash" or prefill.get("cold_gather"):
        run["why_incorrect"].append(f"a prefill gathered: {prefill}")
        run["correct"] = False
    return run
