"""`serve-closed`'s loop with the server built from the configuration's
`architecture` key: a model that is not a GPT-2 brings its own builder under
lib/ and its own reference under reference/. The loop, the window, the gauges,
the counters and the verdict are lib/serving.py's; what differs is how the
weights and the program's config are made, what the served tokens are checked
against, the model's own in-graph counters at both ends of the window, and the
by-scope reduction of the trace that the model's per-layer readers read."""

import importlib
import itertools
import threading
import time

import numpy as np

from lib import scope_reduce, serving, traffic as traffic_lib

# architecture (the configuration file's key) -> (builder module under lib/
# with `<name>_config` and `serving_params`, reference module under reference/)
ARCHITECTURES = {"DeepseekV3ForCausalLM": ("moonlight", "moonlight_ref")}

# How served greedy tokens are judged against the float32 reference (prompt +
# served tokens through `sequence_logits`; every served token's reference logit
# against its position's largest: the deficit). Moonlight's logits are y W_head
# with y of unit RMS over 2048 values and W_head normal(0, 0.02): standard
# deviation 0.9 (measured 0.905), the largest of 163,840 some 4 of them, so a
# token from a wrong page, position, expert or weight sits whole units below.
#
# The picks of 6 experts of 64 are DISCONTINUOUS in the router's scores, and
# with seeded weights those scores are nearly flat: the last expert picked is a
# median 0.011 ahead of the first left out. The engine computes in bfloat16, so
# where that gap is within its rounding it picks another expert, and from there
# on the position's logits are another function's: measured on the chip (PR 27,
# PERF.md section 6), a third of the positions had a pick flipped in some
# layer; positions with none differed from the reference by a standard
# deviation of 0.015 a logit and at most 0.036 in deficit, positions with one
# by 0.19 and up to 1.23. No tolerance is widened for that. Two limits instead:
#   1. JUDGED positions, those whose gap is at least PICK_GAP in every expert
#      layer of the reference (no flip was seen at 0.008 and more; 0.01 keeps
#      about 2% of the positions): every one's deficit within LOGIT_MARGIN.
#      How many were judged and how many left out is in the facts. Readings
#      (my chip runs, PR 27): the served tokens' largest judged deficit 0.0 (53
#      judged over two seeds: every one the reference's own best); tokens
#      picked by the reference with its weights rounded to float8_e4m3, the
#      precision below the stated bfloat16, 0.77 and 1.02 over 16 judged each.
#   2. Of ALL checked positions, at least MIN_SHARE_WITHIN within LOGIT_MARGIN:
#      flips cost a share of the positions and no more. Readings: served
#      0.908 and 0.898 of 1,408 and 896 positions; the float8 reference 0.388
#      and 0.352 of 1,024. A run fails on either limit; float8 fails both.
LOGIT_MARGIN = 0.1
PICK_GAP = 0.01
MIN_SHARE_WITHIN = 0.65
CHECKED_REQUESTS = 4
# sequences are checked padded to a multiple of this, so that the reference's
# pieces compile for at most four lengths
PAD_TO = 2048


class ModelServed(serving.Served):
    def __init__(self, ctx):
        import jax.numpy as jnp
        import paddle_tpu as pt
        from paddle_tpu.serving import ServingConfig

        self.ctx = ctx
        self.cfg = ctx.config
        builder, reference = ARCHITECTURES[self.cfg["architecture"]]
        self.builder = importlib.import_module("lib." + builder)
        self.reference = importlib.import_module("reference." + reference)
        self.model_cfg = getattr(self.builder, builder + "_config")(self.cfg)
        self.params = self.builder.serving_params(self.cfg, ctx.seed, jnp.bfloat16)
        self.sizes = dict(ctx.traffic["engine"])
        self.sizes["prefill_buckets"] = tuple(self.sizes["prefill_buckets"])
        ctx.mark("weights_asked")
        self.server = pt.server.serve(self.params, self.model_cfg, pt.server.ServerConfig(
            port=0, replicas=1, serving=ServingConfig(**self.sizes)))
        self.port = self.server.port
        self.engine = self.server.router.replicas[0].engine
        self.records = []
        self._lock = threading.Lock()
        ctx.mark("server_up")

    def model_counters(self):
        """The model's in-graph counters as the engine has summed them."""
        return {name: np.asarray(value).tolist()
                for name, value in self.engine.scheduler.model_counters.items()}

    def check_outputs(self, measured):
        """For a few greedy requests picked by the seed among those served in
        full: the two limits above. The reference runs prompt + served tokens
        in blocks (a layer's weights widened at a time, an expert at a time)."""
        facts = {"checked": 0, "max_logit_deficit": None, "logit_deficits": [],
                 "logit_margin": LOGIT_MARGIN, "pick_gap": PICK_GAP}
        greedy = [r for r in measured if r["ok"] and r["greedy"] and r["output"]]
        if not greedy:
            return False, facts
        rng = np.random.default_rng([int(self.ctx.seed), 31])
        picks = rng.choice(len(greedy), size=min(CHECKED_REQUESTS, len(greedy)), replace=False)
        deficits, gaps, stds = [], [], []
        for i in picks:
            r = greedy[int(i)]
            prompt = traffic_lib.prompt_tokens(self.ctx.seed, r["index"], r["prompt_len"],
                                               self.cfg["vocab_size"])
            seq = prompt + r["output"]
            width = -(-len(seq) // PAD_TO) * PAD_TO
            rows = np.arange(r["prompt_len"] - 1, len(seq) - 1)
            logits, gap = self.reference.sequence_logits(
                self.params, self.cfg, seq + [0] * (width - len(seq)), rows, gaps=True)
            logits = np.asarray(logits)
            chosen = logits[np.arange(len(r["output"])), np.asarray(r["output"])]
            deficits.append(logits.max(-1) - chosen)
            gaps.append(np.asarray(gap))
            stds.append(float(logits.std()))
        every, gap = np.concatenate(deficits), np.concatenate(gaps)
        judged = gap >= PICK_GAP
        share = float((every <= LOGIT_MARGIN).mean())
        worst_judged = float(every[judged].max()) if judged.any() else None
        facts.update(checked=len(picks), positions=int(every.size), judged=int(judged.sum()),
                     left_out=int((~judged).sum()), max_logit_deficit=worst_judged,
                     logit_deficits=[float(d.max()) for d in deficits],
                     share_within_margin=share, min_share_within=MIN_SHARE_WITHIN,
                     logit_std=max(stds))
        ok = share >= MIN_SHARE_WITHIN and (worst_judged is None or worst_judged <= LOGIT_MARGIN)
        return ok, facts


def run(ctx):
    mix = ctx.traffic
    served = ModelServed(ctx)
    try:
        executables = served.warm_up()
        clients = mix["clients"]
        counter = itertools.count()
        take = threading.Lock()
        stop = threading.Event()

        def client(delay):
            if stop.wait(delay):
                return
            while not stop.is_set():
                with take:
                    k = next(counter)
                spec = traffic_lib.closed_request(mix["requests"], ctx.seed, k)
                served.request(spec, time.monotonic())

        start = time.monotonic()
        threads = [threading.Thread(target=client, args=(mix["ramp_s"] * i / clients,),
                                    name=f"bench-client-{i}", daemon=True)
                   for i in range(clients)]
        for t in threads:
            t.start()
        t0 = start + mix["ramp_s"] + mix["settle_s"]
        time.sleep(max(0.0, t0 - time.monotonic()))
        ctx.mark_setup_done(ramp_s=mix["ramp_s"] + mix["settle_s"])
        model0 = served.model_counters()
        observed = serving.observe_window(served, t0)
        model1 = served.model_counters()
        time.sleep(mix["tail_s"])
        stop.set()
        peak = ctx.memory_peak()
        stats = served.engine.stats()
        served.shutdown()
        for t in threads:
            t.join(10.0)
    except BaseException:
        served.shutdown()
        raise
    served.records.sort(key=lambda r: r["end"])
    measured = [r for r in served.records if t0 <= r["end"] < t0 + ctx.seconds]
    run = serving.finish(served, "serve-closed-model", t0, observed, measured, len(measured),
                         executables, peak)
    run.update(model0=model0, model1=model1,
               scopes=scope_reduce.reduce_dir(ctx.out_path("trace")) if ctx.trace else None)
    run["facts"].update(model=stats.get("model"), decode_attention=stats.get("decode_attention"),
                        cache_row_bytes=stats.get("cache_row_bytes"),
                        weight_bytes=stats.get("weight_bytes"),
                        expert_tokens_in_window=[b - a for a, b in zip(
                            model0.get("expert_tokens", []), model1.get("expert_tokens", []))],
                        scopes=run["scopes"])
    facts = run["facts"]
    run["why_incorrect"] = [
        (f"of {facts.get('positions')} served greedy positions {facts.get('share_within_margin')} are "
         f"within {LOGIT_MARGIN} of the reference's best logit (at least {MIN_SHARE_WITHIN}), and the "
         f"{facts.get('judged')} clear of a tie in the picks by {PICK_GAP} are at most "
         f"{facts.get('max_logit_deficit')} under it (at most {LOGIT_MARGIN})")
        if why.startswith("served greedy tokens") else why for why in run["why_incorrect"]]
    if stats.get("decode_attention") == "gather":
        run["why_incorrect"].append("the decode step gathered: the latent kernel did not run")
        run["correct"] = False
    return run
