"""Training: the causal-LM program through pt.Executor, one step after the
other, every step fetching its loss as a user's loop does; that fetch is the
synchronisation. The steps run through the warm-up, the window and one step
past it without a break, and the window selects the completions counted."""

import time

import numpy as np

from lib import model, traffic as traffic_lib

# The first step's loss (bfloat16 products under AMP, float32 master weights,
# float32 softmax and mean) against the float32 reference on the same batch,
# relative. Rounding each product's inputs to bfloat16 perturbs a logit by
# about 2**-9 of its size, and over thousands of positions the mean loss
# moves by parts in 1e5 (4.1e-6 measured on the chip in PR 23; PERF.md).
# A loss computed wholly in bfloat16 could only take values 0.0625 apart near
# 10.9, that is 5.7e-3 relative, and a mean accumulated in bfloat16 is off by
# far more, so either fails.
LOSS_REL_TOL = 2e-4


def run(ctx):
    import jax
    import paddle_tpu as pt
    from paddle_tpu.models.gpt import gpt_lm_program
    from reference import gpt2_ref

    mix = ctx.traffic
    cfg = ctx.config
    seq, chips = mix["seq_len"], ctx.cell["chips"]
    batch = mix["per_chip_batch"] * chips
    main, startup, fetches = gpt_lm_program(model.gpt_config(cfg), seq,
                                            learning_rate=mix["learning_rate"], amp=True)
    startup.random_seed = main.random_seed = model.fold_seed(ctx.seed)
    loss_var = fetches["loss"]
    target = main
    if mix["data_parallel"]:
        target = pt.CompiledProgram(main).with_data_parallel(loss_name=loss_var.name)
    batches = traffic_lib.train_batches(ctx.seed, mix["distinct_batches"], batch, seq,
                                        cfg["vocab_size"])
    exe = pt.Executor()
    scope = pt.Scope()
    steps = []
    with pt.scope_guard(scope):
        exe.run(startup)
        initial = model.scope_params(scope, cfg)
        ctx.mark("weights_made")

        def step(k):
            with jax.profiler.TraceAnnotation("feed"):
                feed = {"tokens": batches[k % len(batches)]}
            with jax.profiler.TraceAnnotation("step"):
                out, = exe.run(target, feed=feed, fetch_list=[loss_var])
            steps.append({"done": time.monotonic(), "tokens": batch * seq,
                          "loss": float(np.asarray(out).reshape(-1)[0])})

        for k in range(mix["warm_up_steps"]):
            step(k)
        compiles0 = exe.compile_count
        t0 = time.monotonic()
        ctx.mark_setup_done(ramp_s=0.0)
        traced = None
        if ctx.trace:
            from lib import tracing
            traced = tracing.TracedWindow(ctx.out_path("trace"))
            traced.start()
            trace_until = t0 + min(mix["trace_s"], ctx.seconds)
        k = mix["warm_up_steps"]
        quiet_from = t0
        while time.monotonic() < t0 + ctx.seconds:
            step(k)
            k += 1
            if traced is not None and trace_until is not None and time.monotonic() >= trace_until:
                traced.stop()             # takes seconds: the host writes the trace out
                trace_until, quiet_from = None, time.monotonic()
        if traced is not None and trace_until is not None:
            traced.stop()
            quiet_from = time.monotonic()
        recompiles = exe.compile_count - compiles0
        placed = len(scope.find_var("gpt/wte").sharding.device_set)
    peak = ctx.memory_peak()
    losses = [s["loss"] for s in steps]
    reference = gpt2_ref.batch_loss(initial, batches[0], cfg["n_head"],
                                    cfg.get("layer_norm_epsilon", 1e-5))
    gap = abs(losses[0] - reference) / abs(reference)
    in_window = [s for s in steps if t0 <= s["done"] < t0 + ctx.seconds]
    checks = {f"first loss within {LOSS_REL_TOL} of the reference's (gap {gap})": gap <= LOSS_REL_TOL,
              "every loss finite": bool(all(np.isfinite(losses))),
              "loss falling": bool(np.mean(losses[-8:]) < np.mean(losses[:8])),
              f"no recompile in the window ({recompiles})": recompiles == 0,
              f"weights on all {chips} chips ({placed})": placed == chips}
    correct = all(checks.values())
    return {"mode": "train", "t0": t0, "seconds": ctx.seconds, "steps": steps,
            "quiet_from": quiet_from,
            "attempted": len(in_window), "failed": 0, "correct": correct,
            "recompiles": recompiles, "chips": chips, "seq_len": seq,
            "why_incorrect": ["failed: " + k for k, v in checks.items() if not v],
            "trace": traced.summary() if traced else None, "memory_peak_bytes": peak,
            "facts": {"first_loss": losses[0], "reference_loss": reference,
                      "loss_rel_gap": gap, "last_losses_mean": float(np.mean(losses[-8:])),
                      "first_losses_mean": float(np.mean(losses[:8])),
                      "steps_in_window": len(in_window), "global_batch": batch,
                      "devices_holding_weights": placed}}
