"""What the two serving modes share: the server over seeded weights, the
warm-up of the cell's own shapes, one request as a record, the sampler of the
engine's gauges, and the check of served tokens against the reference."""

import threading
import time

import numpy as np

from . import metrics, model, sse, tracing, traffic as traffic_lib

# A served greedy token's logit on the float32 reference must be within this
# of that position's largest. The engine computes in bfloat16 over a bfloat16
# cache and another batch shape, which is worth a few roundings of 2**-8
# relative on logits of standard deviation about 0.55 (largest about 2.5, so
# one rounding is 0.01); a token from a wrong page, position or weight sits
# whole units below. PR 21 measured 0.0088 at most on GPT-2-small.
LOGIT_MARGIN = 0.1
CHECKED_REQUESTS = 4
REQUEST_TIMEOUT_S = 120.0


class Served:
    def __init__(self, ctx):
        import jax.numpy as jnp
        import paddle_tpu as pt
        from paddle_tpu.serving import ServingConfig

        self.ctx = ctx
        self.cfg = ctx.config
        self.gpt = model.gpt_config(self.cfg)
        self.params = model.serving_params(self.cfg, ctx.seed, jnp.bfloat16)
        # every engine option but the sizes (slots, queue depth, buckets,
        # length) stays at its default, so a PR that changes a default is
        # measured
        self.sizes = dict(ctx.traffic["engine"])
        if "prefill_buckets" in self.sizes:
            self.sizes["prefill_buckets"] = tuple(self.sizes["prefill_buckets"])
        ctx.mark("weights_asked")
        self.server = pt.server.serve(self.params, self.gpt, pt.server.ServerConfig(
            port=0, replicas=1, serving=ServingConfig(**self.sizes)))
        self.port = self.server.port
        self.engine = self.server.router.replicas[0].engine
        self.records = []
        self._lock = threading.Lock()
        ctx.mark("server_up")

    def shutdown(self):
        self.server.shutdown(drain=False)

    def request(self, spec, due, measured=True):
        """Sends one request now, blocks until its stream closes, and keeps
        its record. `due` is when it should have been sent (clock time)."""
        seed, vocab = self.ctx.seed, self.cfg["vocab_size"]
        prompt = traffic_lib.prompt_tokens(seed, spec["index"], spec["prompt_len"], vocab)
        body = {"prompt": prompt, "max_new_tokens": spec["max_new_tokens"]}
        if spec["temperature"]:
            body.update(temperature=spec["temperature"], seed=spec["seed"])
        sent = time.monotonic()
        reply = sse.generate(self.port, body, REQUEST_TIMEOUT_S)
        done = reply["done"]
        record = {
            "index": spec["index"], "due": due, "sent": sent,
            "first": reply["first"], "last": reply["last"], "end": reply["end"],
            "tokens": len(reply["tokens"]), "prompt_len": spec["prompt_len"],
            "max_new_tokens": spec["max_new_tokens"], "greedy": not spec["temperature"],
            "status": reply["status"],
            "queue_wait": (done.get("metrics") or {}).get("queue_wait"),
            "ok": (reply["status"] == 200 and reply["error"] is None
                   and len(reply["tokens"]) == spec["max_new_tokens"]
                   and done.get("finish_reason") in ("length", "stop")),
            "error": reply["error"], "measured": measured,
            "output": reply["tokens"] if not spec["temperature"] else None,
        }
        with self._lock:
            self.records.append(record)
        return record

    def warm_up(self):
        """One request to each prefill bucket, one after the other, long
        enough to run the decode chunk: the cell's shapes and no others.
        The first request to need an executable compiles it (the program has
        no warm-up entry point), so this is where a cold run spends minutes."""
        buckets = self.sizes.get("prefill_buckets") or (self.sizes["max_len"] // 2,)
        chunk = self.engine.config.decode_chunk
        for i, bucket in enumerate(buckets):
            spec = {"index": 10 ** 8 + i, "prompt_len": min(bucket, self.sizes["max_len"] - 2 * chunk - 1),
                    "max_new_tokens": 2 * chunk, "temperature": 0.8 if i % 2 else 0.0,
                    "seed": 1 + i}
            reply = self.request(spec, time.monotonic(), measured=False)
            if not reply["ok"]:
                raise RuntimeError(f"warm-up request to bucket {bucket} failed: {reply}")
        self.records.clear()
        self.ctx.mark("warmed_up")
        return self.engine.stats()["compiled_executables"]

    def check_outputs(self, measured):
        """For a few greedy requests picked by the seed among those that were
        served in full, every served token's reference logit is within
        LOGIT_MARGIN of its position's largest."""
        from reference import gpt2_ref

        facts = {"checked": 0, "max_logit_deficit": None, "logit_deficits": []}
        # a request that failed is counted in `failed` (and the run is not
        # correct); the tokens of those that were served are checked all the same
        greedy = [r for r in measured if r["ok"] and r["greedy"] and r["output"]]
        if not greedy:
            return False, facts
        rng = np.random.default_rng([int(self.ctx.seed), 31])
        picks = rng.choice(len(greedy), size=min(CHECKED_REQUESTS, len(greedy)), replace=False)
        deficits = []
        for i in picks:
            r = greedy[int(i)]
            prompt = traffic_lib.prompt_tokens(self.ctx.seed, r["index"], r["prompt_len"],
                                               self.cfg["vocab_size"])
            # pad to the model's positions, so that one compiled reference
            # serves every pick of every seed (the causal mask keeps the
            # padding out of every real position)
            seq = prompt + r["output"]
            width = self.cfg["n_positions"]
            logits = np.asarray(gpt2_ref.sequence_logits(
                self.params, seq + [0] * (width - len(seq)), self.cfg["n_head"],
                self.cfg.get("layer_norm_epsilon", 1e-5)))
            rows = logits[np.arange(r["prompt_len"] - 1, len(seq) - 1)]
            chosen = rows[np.arange(len(r["output"])), np.asarray(r["output"])]
            deficits.append(float((rows.max(-1) - chosen).max()))
        facts.update(checked=len(picks), max_logit_deficit=max(deficits), logit_deficits=deficits)
        return max(deficits) <= LOGIT_MARGIN, facts


class GaugeSampler(threading.Thread):
    """Reads the engine's gauges at a fixed period for the length of the
    window: slots in use, live positions, blocks in use."""

    def __init__(self, engine, period_s=0.05):
        super().__init__(name="bench-gauges", daemon=True)
        self.engine, self.period_s = engine, period_s
        self.samples = []
        self._halt = threading.Event()

    def run(self):
        while not self._halt.wait(self.period_s):
            now = self.engine.kv.occupancy()
            self.samples.append((time.monotonic(), now["active_slots"],
                                 now["live_positions"], now["blocks_used"]))

    def finish(self):
        self._halt.set()
        self.join(5.0)
        return self.samples


def counters(engine):
    """The engine's counts that the readers and the facts line use."""
    s = engine.stats()
    keep = ("shed", "tokens_out", "prefills", "dispatches", "prefix_cache_hits",
            "prefix_cache_misses", "blocks_total", "compiled_executables", "pool_bytes")
    return {k: s[k] for k in keep}


def observe_window(served, t0):
    """On the caller's thread, from the window's start to its end: the
    engine's counters at both ends, gauge samples between them, and in a traced
    run the profiler over the first `trace_s` seconds."""
    ctx = served.ctx
    observed = {"counters0": counters(served.engine)}
    sampler = GaugeSampler(served.engine)
    sampler.start()
    observed["traced"] = tracing.trace_for(ctx, min(ctx.traffic["trace_s"], ctx.seconds))
    time.sleep(max(0.0, t0 + ctx.seconds - time.monotonic()))
    observed["counters1"] = counters(served.engine)
    observed["samples"] = sampler.finish()
    return observed


def finish(served, mode, t0, observed, measured, attempted, executables, peak):
    """The run as the readers see it, after the server has shut down: records,
    counters, the reduced trace, the facts line and the verdict."""
    ctx = served.ctx
    traced = observed.pop("traced")
    run = dict(observed, mode=mode, t0=t0, seconds=ctx.seconds, records=served.records,
               measured=measured, attempted=attempted,
               failed=attempted - sum(1 for r in measured if r["ok"]),
               trace=traced.summary() if traced else None,
               decode_chunk=served.engine.config.decode_chunk, memory_peak_bytes=peak)
    ends = sorted(r["end"] for r in run["records"]
                  if r["ok"] and t0 <= r["end"] < t0 + ctx.seconds)
    gaps = np.diff(ends) if len(ends) > 2 else np.array([])
    inside = [s for s in run["samples"] if t0 <= s[0] < t0 + ctx.seconds]
    waits = [r["queue_wait"] for r in measured if r.get("queue_wait") is not None]
    ok, checked = served.check_outputs(measured)
    run["facts"] = dict(
        checked,
        queue_wait_p90_ms=1e3 * metrics.quantile(waits, 0.9) if waits else None,
        active_slots_mean=sum(s[1] for s in inside) / len(inside) if inside else None,
        active_slots_peak=max((s[1] for s in inside), default=None),
        blocks_used_peak=max((s[3] for s in inside), default=None),
        gen_late_max_ms=max((1e3 * (r["sent"] - r["due"]) for r in measured), default=None),
        measured_requests=len(measured), completions_in_window=len(ends),
        completion_gap_cv=float(gaps.std() / gaps.mean()) if gaps.size and gaps.mean() > 0 else None,
        executables=run["counters1"]["compiled_executables"],
        executables_after_warm_up=executables,
        pool_bytes=run["counters1"]["pool_bytes"],
        shed=run["counters1"]["shed"] - run["counters0"]["shed"])
    # every reason a run is not correct, for run.py to say on stderr
    why = []
    if not ok:
        why.append(f"served greedy tokens up to {checked['max_logit_deficit']} under the "
                   f"reference's best logit (margin {LOGIT_MARGIN}; {checked['checked']} checked)")
    if run["failed"]:
        bad = [r for r in measured if not r["ok"]]
        why.append(f"{run['failed']} of {attempted} measured requests failed "
                   f"({run['facts']['shed']} shed in the window), the first: "
                   + str({k: bad[0][k] for k in ("index", "status", "error", "tokens",
                                                 "max_new_tokens")} if bad else "never sent"))
    if run["counters1"]["compiled_executables"] != executables:
        why.append(f"{run['counters1']['compiled_executables']} executables at the window's "
                   f"end against {executables} after warm-up")
    run["why_incorrect"] = why
    run["correct"] = not why
    return run
