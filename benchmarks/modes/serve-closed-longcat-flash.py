"""`serve-closed-model`'s loop, server, window and tie-aware verdict for LongCat-Flash-Omni's
language model served as one chip's share of its deployment (16 of 512 routed experts and
all 256 identity experts behind a router 768 wide, 16,384 of 131,072 vocabulary rows, 4 of
28 double layers: 8 latent attentions, 8 dense feed-forwards, 4 expert layers on their
shortcuts). As the other models' modes do, this file loads a copy of that mode of its OWN
and sets in the copy (in memory; the file on disk is Moonlight's and is not touched) the
architecture's builder and reference, this model's verdict, and a by-stage reduction of the
trace: `lib/stage_times.reduce_path(path, stages=STAGES)` (the harness's stages hold every
one of this leaf's: `mla/*`, `moe/*`, `ffn/*`, `head`, `embed`, `norm`), handed on in the
tables' form that the accepted readers of `run["scopes"]` read (no copy of `scope_reduce`).
The builder is imported HERE, at the top, so a checkout whose program lacks the model
fails at once.

What is checked: prompt + served tokens of SIX greedy requests served in full in the window,
through `reference/longcat_flash_ref.sequence_logits` in float32 with the SAME held range
and vocabulary slice: every served token's reference logit against its position's largest
(the deficit). Two of the six have the longest prompts the window's greedy requests offer,
two the longest answers, two a prompt SHORTER than its bucket where the window holds such.

THE VERDICT: two limits on the served tokens and one NUMBER of the expert layer itself (the
readings that place each constant: PERF.md section 6, PR 50; each lies between the served
runs' worst reading and the least reading of the WRONG programs it is there to tell;
`reference/longcat_flash_ref.WRONG` lists them).

  1. EARLY. Of the positions among a request's first EARLY = 96 generated ones whose picks are
     PICK_GAP = 0.005 clear of a tie in every expert layer (the 12th pick's ranked score ahead
     of the 13th's by that share of itself; 369-394 of 576 are judged, the median gap is
     0.009), the share within LOGIT_MARGIN = 0.05 of the reference's best logit, REQUEST BY
     REQUEST (those with at least MIN_JUDGED = 16 of them): the LEAST is at least
     MIN_JUDGED_WITHIN = 0.8. The logits' standard deviation is 1.57 and the served program
     computes in bfloat16, so 3-4% of its picks lie a few hundredths under the best whatever
     the router does (setting the near-ties apart moves the share by less than 0.01: a flip
     of two near-equal picks matters only where it moves a HELD expert or an identity
     expert in or out). Readings, the least request: served 0.903-0.966 on eighteen seeds;
     `shortcut_from_second` 0.485, `shortcut_early` 0.409, `no_identity` 0.515,
     `renormalised` 0.258, `float8` 0.194, `no_mla_scale` 0.0 (each fails here AND by limit
     2); `held_shifted` 0.855, `products_bf16` 0.964, `bias_weighs` 1.0 (tokens cannot tell
     them: limit 3 does).
  2. ALL. Of ALL checked positions at least MIN_SHARE_WITHIN = 0.985 within SHARE_MARGIN =
     0.15: the backstop that reads every position served (5,632-8,704 a run). Readings:
     served 0.9943-0.9979 on nineteen seeds (a miss rate of 0.2-0.6% against the 1.5% allowed);
     `held_shifted` 0.9688 and 0.9689 (it fails here too), the six above 0.0003-0.65.
  3. SHORTCUT. The expert layer AS SERVED: behind the window, layer 0's shortcut branch on
     SHORTCUT_ROWS = 4,096 of the checked positions' rows (the reference's normed u0 there,
     rounded to the served type; rows whose 12th and 13th pick of layer 0 lie within
     SHORTCUT_GAP = 1e-4 left out: 5-13 of them), computed by the program's own
     `models/_experts.moe` under the served config on the path the prompts took (the
     grouped kernel on the chip), against the reference's float32 `shortcut` of the same
     rows with its output rounded ONCE to the served type, as every program's here is (the
     output's own rounding is the configuration's and cancels; unrounded, the served layer
     read 0.00173 on three seeds, all of it that rounding, beside 0.0021 for `bias_weighs`):
     the relative error (Frobenius, over the rows) is at most MAX_SHORTCUT_ERROR = 0.0016.
     The held experts' term is small beside the stream (a quarter of the rows have a held
     pick), so no token limit can see a wrong expert product; this number can. Readings:
     served 0.00076-0.00091 on sixteen seeds (0.0016-0.0019 over the rows with a held pick);
     the WRONG programs that change the layer, on the same rows: `bias_weighs` 0.00293,
     `products_bf16` 0.00298 (every product, partial sum and the identity term rounded to
     bfloat16 for real: a program a precision below the served one, which sums in float32
     and rounds once), `float8` 0.061, `held_shifted` 0.256, `no_identity` 0.984,
     `renormalised` 2.56. 0.0016 is 1.8 times the served runs' worst and 1.8 times under
     the wrong programs' least.

LONGCAT_FLASH_WRONG_REFERENCE (a builder's facility, unset in every measured run): a comma
list of `reference/longcat_flash_ref.WRONG` names, or `all`. For each, the tokens that WRONG
program picks along the checked sequences are judged against the true reference by the same
limits, and the readings go to the facts line under `wrong_references`; the run's `correct`
is not touched. LONGCAT_FLASH_DUMP_READINGS=<file.npz> keeps every judged reading."""

import importlib.util
import os

import numpy as np

from lib import longcat_flash as _builder  # noqa: F401  (fails at once without the model)

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _own_copy(folder, file_name, module_name):
    spec = importlib.util.spec_from_file_location(
        module_name, os.path.join(BENCH, folder, file_name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


base = _own_copy("modes", "serve-closed-model", "bench_modes_serve_closed_model_longcat_flash")
base.ARCHITECTURES["longcat_flash"] = ("longcat_flash", "longcat_flash_ref")

# the Mosaic kernels this model's programs call, by the names they carry in a trace
KERNELS = ("latent_paged_attention", "_causal_rows_call", "grouped_swiglu", "routed_combine")


class StageTables:
    """`lib/stage_times.py`'s tables of a trace directory in the form the readers of
    `run["scopes"]` read: {program: {"scopes": {stage: s}, "kernels": {kernel: s},
    "attend_s": s under `mla/attend`, "busy_s": the program's own}}."""

    @staticmethod
    def reduce_dir(trace_dir):
        from lib import stage_times, trace_reduce

        path = trace_reduce.find_xplane(trace_dir)
        tables = path and stage_times.reduce_path(path, stages=stage_times.STAGES)
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
                            "attend_s": entry["stages"].get("mla/attend", 0.0),
                            "busy_s": entry["busy_s"]}
        return out


class _Scopes:
    """What `base.run` asks of its `scope_reduce`: the tables, without the one entry that
    is no program's."""
    busy_s = None

    @staticmethod
    def reduce_dir(trace_dir):
        tables = StageTables.reduce_dir(trace_dir)
        if tables is not None:
            _Scopes.busy_s = tables.pop("busy_s")
        return tables


base.scope_reduce = _Scopes

# The logits are y W_head with y of unit RMS over 6144 values and W_head normal(0, 0.02):
# standard deviation 1.57. The readings that place the constants: PERF.md section 6, PR 50.
base.LOGIT_MARGIN = 0.05
base.PICK_GAP = 0.005       # relative: (12th - 13th) / 12th of the ranked score
base.MIN_SHARE_WITHIN = 0.985
base.CHECKED_REQUESTS = 6
EARLY = 96                  # limit 1 judges a request's first generated positions
MIN_JUDGED = 16             # a request with fewer judged positions is not read by limit 1
MIN_JUDGED_WITHIN = 0.8
SHARE_MARGIN = 0.15         # the margin of limit 2
SHORTCUT_ROWS = 4096        # limit 3 runs the served expert layer on this many checked rows
SHORTCUT_GAP = 1e-4         # ... those whose layer-0 picks are this clear of a tie
MAX_SHORTCUT_ERROR = 0.0016
# the WRONG programs that change what the expert layer computes from the same rows
LAYER_WRONG = ("float8", "products_bf16", "no_identity", "bias_weighs", "renormalised",
               "held_shifted")


class LongcatFlashServed(base.ModelServed):
    last_stats = {}       # of the newest server of this process, for `run`

    def shutdown(self):
        # the paths that ran, read before the engine goes
        LongcatFlashServed.last_stats = self.engine.stats()
        super().shutdown()

    def _bucket(self, prompt_len):
        return min(b for b in self.sizes["prefill_buckets"] if b >= prompt_len)

    def _chosen(self, measured):
        """Six of the greedy requests served in full, by the seed: two of the longest
        prompts served, two of the longest answers, two whose prompt is shorter than its
        bucket, the rest from the others."""
        greedy = [r for r in measured if r["ok"] and r["greedy"] and r["output"]]
        rng = np.random.default_rng([int(self.ctx.seed), 50])
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
            padded = lambda r: self._bucket(r["prompt_len"]) > r["prompt_len"]
            take(lambda r: r["prompt_len"] == longest, 2)
            take(lambda r: len(r["output"]) == answer,
                 2 - sum(len(r["output"]) == answer for r in chosen))
            take(padded, 2 - sum(padded(r) for r in chosen))
            take(lambda r: True, base.CHECKED_REQUESTS)
        return chosen

    def check_outputs(self, measured):
        """The module docstring's three limits over `_chosen`'s requests. The reference runs
        once a request (and once more for each WRONG program asked for)."""
        chosen = self._chosen(measured)
        facts = {"checked": 0, "max_logit_deficit": None, "logit_deficits": [],
                 "logit_margin": base.LOGIT_MARGIN, "pick_gap": base.PICK_GAP}
        if not chosen:
            return False, facts
        wrong = os.environ.get("LONGCAT_FLASH_WRONG_REFERENCE", "")
        names = self.reference.WRONG if wrong == "all" else tuple(filter(None, wrong.split(",")))
        deficits, gaps, stds = {name: [] for name in ("served",) + names}, [], []
        inputs, real = [], []
        for r in chosen:
            prompt = base.traffic_lib.prompt_tokens(self.ctx.seed, r["index"], r["prompt_len"],
                                                    self.cfg["vocab_size"])
            seq = prompt + r["output"]
            seq = seq + [0] * (-len(seq) % base.PAD_TO)
            rows = np.arange(r["prompt_len"] - 1, r["prompt_len"] - 1 + len(r["output"]))
            true, gap, taps = self.reference.sequence_logits(self.params, self.cfg, seq, rows,
                                                             gaps=True, tap=True)
            true = np.asarray(true)
            under = lambda picked: true.max(-1) - true[np.arange(len(rows)), picked]
            deficits["served"].append(under(np.asarray(r["output"])))
            gaps.append(np.asarray(gap))
            stds.append(float(true.std()))
            inputs.append(np.asarray(taps["u0"]))
            real.append(np.asarray(taps["real"]))
            del taps
            for name in names:
                # the tokens a WRONG program picks along the served sequence
                deficits[name].append(under(np.asarray(self.reference.sequence_logits(
                    self.params, self.cfg, seq, rows, wrong=name)).argmax(-1)))
        clear = [g >= base.PICK_GAP for g in gaps]
        request = np.concatenate([np.full(len(c), i) for i, c in enumerate(clear)])
        early = np.concatenate([np.arange(len(c)) < EARLY for c in clear]) & np.concatenate(clear)
        layer = shortcut_readings(
            _builder.program, self.reference, self.model_cfg, self.cfg, self.params,
            np.concatenate(inputs), self.ctx.seed,
            tuple(name for name in names if name in LAYER_WRONG))
        read = {name: _limits(np.concatenate(parts), early, request,
                              layer["error"] if name == "served" else layer["wrong"].get(name))
                for name, parts in deficits.items()}
        dump = os.environ.get("LONGCAT_FLASH_DUMP_READINGS")
        if dump:
            np.savez(dump, gaps=np.concatenate(gaps), early=early, request=request,
                     lengths=np.asarray([len(c) for c in clear]),
                     prompt_lens=np.asarray([r["prompt_len"] for r in chosen]),
                     real=np.concatenate(real, -1),
                     **{name: np.concatenate(parts) for name, parts in deficits.items()})
        padded = sum(self._bucket(r["prompt_len"]) > r["prompt_len"] for r in chosen)
        real = np.concatenate(real, -1)
        facts.update(read["served"], checked=len(chosen),
                     logit_deficits=[float(d.max()) for d in deficits["served"]],
                     early=EARLY, min_judged_within=MIN_JUDGED_WITHIN,
                     share_margin=SHARE_MARGIN, min_share_within=base.MIN_SHARE_WITHIN,
                     shortcut=layer, max_shortcut_error=MAX_SHORTCUT_ERROR,
                     logit_std=max(stds),
                     checked_real_picks_mean=float(real.mean()),
                     checked_prompt_lens=sorted(r["prompt_len"] for r in chosen),
                     checked_answer_lens=sorted(len(r["output"]) for r in chosen),
                     padded_prompts_checked=padded)
        if names:
            facts["wrong_references"] = {name: read[name] for name in names}
        return not read["served"]["fails"], facts


def shortcut_readings(program, reference, model_cfg, cfg, params, u0, seed, wrong=()):
    """Limit 3's readings. `u0` (N, h) float32: the reference's normed input of layer 0's
    expert layer at every checked position. SHORTCUT_ROWS of them (all, where there are
    fewer; the seed draws which), rounded to the served type, go through the program's own
    `models/_experts.moe` under the served config, as one jitted call of that many rows
    (the path a prompt's rows take: the grouped kernel on the chip), and through the
    reference's float32 `shortcut` with the same held range. Returns {"error": the relative
    Frobenius error of the served rows over those whose picks are SHORTCUT_GAP clear of a
    tie, "error_held_rows": the same over the rows with a pick on a held expert, "rows",
    "rows_left_out", "rows_with_held_pick", "expert_product_path", "real_picks_mean",
    "wrong": {name: the same error of the reference's WRONG program `name`}}."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import _experts

    n = min(SHORTCUT_ROWS, len(u0))
    rng = np.random.default_rng([int(seed), 5050])
    at = np.sort(rng.choice(len(u0), size=n, replace=False))
    mp = params["layers"][0]["moe"]
    dtype = params["wte"].dtype
    rows = jnp.asarray(u0[at]).astype(dtype)
    served, counted = jax.jit(lambda mp, x: _experts.moe(
        model_cfg, mp, x, jnp.ones((x.shape[0],), bool)))(mp, rows)
    served = np.asarray(served.astype(jnp.float32))
    wide = rows.astype(jnp.float32)

    def reference_rows(name=None):
        with jax.default_matmul_precision("highest"):
            routed, term, gap, real = reference.shortcut(wide, mp, cfg, wrong=name)
        return (np.asarray(routed + term), np.asarray(routed), np.asarray(gap),
                np.asarray(real))

    # every side is read AS SERVED, rounded once to the served type: the rounding of the
    # layer's output is the configuration's own and cancels; what is left is what a program
    # does before it
    as_served = lambda a: np.asarray(jnp.asarray(a).astype(dtype).astype(jnp.float32))
    true, routed, gap, real = reference_rows()
    true = as_served(true)
    keep = gap >= SHORTCUT_GAP
    held = keep & (np.abs(routed).max(-1) > 0)
    size = lambda a: float(np.sqrt(np.sum(np.square(a, dtype=np.float64))))
    error = lambda got, rows: (size((as_served(got) - true)[rows]) / size(true[rows])
                               if rows.any() else None)
    return {"error": error(served, keep), "error_held_rows": error(served, held),
            "rows": int(keep.sum()), "rows_left_out": int((~keep).sum()),
            "rows_with_held_pick": int(held.sum()),
            "expert_product_path": _experts.expert_product_path(mp),
            "real_picks_mean": float(real.mean()),
            "held_picks": int(counted["moe_held_picks"]),
            "wrong": {name: error(reference_rows(name)[0], keep) for name in wrong}}


def _limits(deficits, early, request, shortcut_error=None):
    """One set of tokens' deficits under the true reference (every checked position, in
    order) against the two token limits; `early` marks limit 1's positions, `request` says
    whose each position is. `shortcut_error`: limit 3's reading of the program that picked
    the tokens (None: its expert layer is the reference's on the same rows)."""
    def within(mask, margin):
        picked = deficits[mask]
        return float((picked <= margin).mean()) if picked.size else None

    by_request = [within(early & (request == r), base.LOGIT_MARGIN)
                  for r in np.unique(request) if (early & (request == r)).sum() >= MIN_JUDGED]
    judged = min(by_request) if by_request else None
    share = float((deficits <= SHARE_MARGIN).mean())
    fails = [limit for limit, failed in (
        ("early", judged is not None and judged < MIN_JUDGED_WITHIN),
        ("all", share < base.MIN_SHARE_WITHIN),
        ("shortcut", shortcut_error is not None
         and not shortcut_error <= MAX_SHORTCUT_ERROR)) if failed]
    return {"positions": int(deficits.size), "judged": int(early.sum()),
            "left_out": int(deficits.size - early.sum()), "judged_within_margin": judged,
            "judged_within_by_request": by_request,
            "max_logit_deficit": float(deficits[early].max()) if early.any() else None,
            "share_within_margin": share, "shortcut_error": shortcut_error, "fails": fails}


base.ModelServed = LongcatFlashServed


def picks_histogram(run):
    """The window's `moe_real_picks_hist` (13 bins: live tokens by how many of their 12
    picks were real experts, every expert layer, prefill and decode) or None."""
    a = (run.get("model0") or {}).get("moe_real_picks_hist")
    b = (run.get("model1") or {}).get("moe_real_picks_hist")
    return [y - x for x, y in zip(a, b)] if a and b else None


def run(ctx):
    run = base.run(ctx)
    stats = LongcatFlashServed.last_stats
    counted, before = run["model1"], run["model0"]
    if run.get("scopes") is not None:
        run["scopes_busy_s"] = _Scopes.busy_s
    decode_path = stats.get("decode_attention")
    prefill = stats.get("prefill_attention") or {}
    facts = run["facts"]
    hist = picks_histogram(run)
    tokens = sum(hist) if hist else 0
    grew = lambda name: (counted.get(name) or 0) - (before.get(name) or 0)
    held_rows = [y - x for x, y in zip(before.get("expert_tokens") or [],
                                       counted.get("expert_tokens") or [])]
    facts.update(
        prefill_attention=prefill, prefix_cache=stats.get("prefix_cache"),
        experts_held=stats.get("experts_held"), vocab_slice=stats.get("vocab_slice"),
        identity_experts=stats.get("identity_experts"), router_width=stats.get("router_width"),
        cache_layers=stats.get("cache_layers"),
        expert_product_path=(facts.get("shortcut") or {}).get("expert_product_path"),
        real_picks_hist=hist,
        real_picks_mean=(sum(i * n for i, n in enumerate(hist)) / tokens) if tokens else None,
        identity_pick_share=(grew("moe_identity_picks") / grew("moe_picks_routed")
                             if grew("moe_picks_routed") else None),
        held_expert_max_over_even_share=(
            max(held_rows) * stats.get("router_width", 0) / grew("moe_picks_routed")
            if held_rows and grew("moe_picks_routed") else None),
        **{name: counted.get(name) for name in (
            "moe_kernel_passes", "moe_rows_computed", "moe_picks_routed", "moe_picks_held",
            "moe_identity_picks", "moe_expert_picks", "moe_held_picks",
            "decode_moe_picks_routed", "decode_moe_picks_held", "mla_decode_rows")})
    run["why_incorrect"] = [
        (f"of the {facts.get('judged')} served greedy positions among each checked request's "
         f"first {EARLY} that are clear of a tie in the picks by {base.PICK_GAP}, the least "
         f"share a request within {base.LOGIT_MARGIN} of the reference's best logit is "
         f"{facts.get('judged_within_margin')} (at least {MIN_JUDGED_WITHIN}); of all "
         f"{facts.get('positions')}, {facts.get('share_within_margin')} are within "
         f"{SHARE_MARGIN} (at least {base.MIN_SHARE_WITHIN}); the served expert layer's "
         f"shortcut is {facts.get('shortcut_error')} from the float32 reference's (at most "
         f"{MAX_SHORTCUT_ERROR}): fails {facts.get('fails')}")
        if why.startswith("of ") and "served greedy positions" in why else why
        for why in run["why_incorrect"]]
    if decode_path not in ("latent_paged_kernel", "gather"):
        run["why_incorrect"].append(f"the decode step gathered: {decode_path}")
        run["correct"] = False
    if facts.get("expert_product_path") != "grouped_swiglu_kernel" \
            or not counted.get("moe_kernel_passes"):
        run["why_incorrect"].append(
            f"the expert product did not run as the kernel: {facts.get('expert_product_path')}, "
            f"{counted.get('moe_kernel_passes')} kernel passes")
        run["correct"] = False
    if prefill.get("path") != "flash" or prefill.get("cold_gather"):
        run["why_incorrect"].append(f"a prefill gathered: {prefill}")
        run["correct"] = False
    return run
