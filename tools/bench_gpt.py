"""GPT-2-small causal-LM train-step MFU on one chip (the decoder-only
flagship; BENCH_MODEL=gpt2 from bench.py). Same discipline as the BERT
bench: device-resident feed, async-chained steps, one sync."""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402


def main():
    import paddle_tpu as pt
    from paddle_tpu.observability import train_stats
    from paddle_tpu.observability.device_peaks import (device_peaks,
                                                       device_report)
    from paddle_tpu.models.gpt import (GPTConfig, flops_per_step,
                                       gpt_lm_program)

    seq = int(os.environ.get("BENCH_SEQ", 512))
    batch = int(os.environ.get("BENCH_BATCH", 16))
    steps = int(os.environ.get("BENCH_STEPS", 30))
    tele_steps = int(os.environ.get("BENCH_TELEMETRY_STEPS", 5))
    peak = device_peaks()["bf16_flops"]
    amp = os.environ.get("BENCH_AMP", "1") == "1"
    cfg = GPTConfig(max_pos=max(1024, seq),
                    attn_impl=os.environ.get("BENCH_ATTN", "fused"))

    # Build with the telemetry tap attached (the StepLogger must be
    # installed at minimize() time), then UNinstall for the timed loop:
    # without a logger the executor adds no telemetry fetches, so XLA
    # dead-code-eliminates the tap and the MFU numbers stay honest. A
    # short telemetry-enabled segment afterwards sources the registry
    # columns (steps/s, recompiles, nan_steps).
    if tele_steps:
        train_stats.install_step_logger(
            train_stats.StepLogger(policy="warn", peak_flops=peak))
    main_prog, startup, fetches = gpt_lm_program(
        cfg, seq, learning_rate=1e-4, amp=amp,
        recompute=os.environ.get("BENCH_RECOMPUTE", "0") == "1")
    train_stats.uninstall_step_logger()

    # static pre-flight: the program must verify clean BEFORE any bench
    # time is spent on it. This runs once at build (here), never inside
    # the timed loop — verify_ms in `extra` pins the build-time-only cost.
    from paddle_tpu import analysis
    t_v = time.perf_counter()
    vrep = analysis.verify_program(main_prog,
                                   fetch_list=[fetches["loss"]])
    verify_ms = (time.perf_counter() - t_v) * 1e3
    assert not vrep.errors, f"program failed verification:\n{vrep.render()}"

    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    feed = {"tokens": jnp.asarray(rng.randint(
        0, cfg.vocab_size, (batch, seq)).astype(np.int64))}

    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        loss_var = fetches["loss"]
        l, = exe.run(main_prog, feed=feed, fetch_list=[loss_var])
        assert np.isfinite(l).all(), f"non-finite loss {l}"
        t0 = time.perf_counter()
        last = None
        for _ in range(steps):
            last = exe.run(main_prog, feed=feed, fetch_list=[loss_var],
                           return_numpy=False)[0]
        last.block_until_ready()
        dt = (time.perf_counter() - t0) / steps
        assert np.isfinite(np.asarray(last)).all()

        prof = os.environ.get("BENCH_PROFILE", "")
        if prof:  # 3 profiled steps for tools/profile_summary.py
            with pt.profiler.profiler(profile_path=prof):
                for _ in range(3):
                    last = exe.run(main_prog, feed=feed,
                                   fetch_list=[loss_var],
                                   return_numpy=False)[0]
                last.block_until_ready()

        extra = {}
        if tele_steps:
            # telemetry segment: re-install the logger and run a few
            # per-step-synced steps; the registry sources the columns
            # (one recompile is expected here — the telemetry fetches
            # change the fetch set, counted as cause=fetch_list)
            logger = train_stats.install_step_logger(
                train_stats.StepLogger(policy="warn", peak_flops=peak))
            try:
                for _ in range(tele_steps):
                    exe.run(main_prog, feed=feed, fetch_list=[loss_var])
            finally:
                train_stats.uninstall_step_logger()
            snap = pt.observability.get_registry().snapshot()

            def _total(name):
                fam = snap.get(name)
                if not fam:
                    return 0.0
                return sum(s.get("value", 0.0) for s in fam["series"])

            hist = snap.get("train_step_seconds", {}).get("series")
            p50 = hist[0].get("p50") if hist else None
            extra = {
                "steps_per_s": round(1.0 / p50, 3) if p50 else None,
                "recompiles_total": _total("executor_recompiles_total"),
                "nan_steps": _total("nan_steps_total"),
                "telemetry_steps": logger.step_count,
                "grad_norm": (logger.recent(1) or [{}])[-1].get(
                    "grad_norm"),
            }

    fl = flops_per_step(cfg, batch, seq)
    mfu = fl / dt / peak
    extra["verify_ms"] = round(verify_ms, 1)
    print(json.dumps({
        "metric": "gpt2_small_train_mfu",
        "value": round(mfu, 4),
        "unit": "MFU (batch=%d seq=%d, %.1f samples/s, %.1f ms/step)"
                % (batch, seq, batch / dt, dt * 1e3),
        "vs_baseline": round(mfu / 0.45, 4),
        "device": device_report(),
        "extra": extra,
    }))


if __name__ == "__main__":
    main()
