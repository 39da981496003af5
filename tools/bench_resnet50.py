"""ResNet-50 ImageNet train-step benchmark through the framework.

Same protocol as tools/bench_resnet_jax.py (the raw-JAX roofline probe):
N async-chained steps on device, one sync at the end. FLOPs use the
standard 2*MAC convention (4.089 GMAC/img fwd, x3 for fwd+bwd).

Flags: BATCH, STEPS, FMT (NCHW|NHWC), AMP (1|0). The peak comes from
paddle_tpu/observability/device_peaks.py for the device jax reports.
"""

import json
import os
import sys
import time

import numpy as np


def main():
    import paddle_tpu as pt
    from paddle_tpu.models import resnet
    from paddle_tpu.observability.device_peaks import (device_peaks,
                                                       device_report)

    def env(name, default):
        # accept both this tool's flags and bench.py's BENCH_* spellings
        return os.environ.get(name, os.environ.get("BENCH_" + name, default))

    batch = int(env("BATCH", 128))
    steps = int(env("STEPS", 50))
    fmt = env("FMT", "NCHW")
    amp = env("AMP", "1") == "1"
    peak = device_peaks()["bf16_flops"]

    main_prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_prog, startup):
        shape = [3, 224, 224] if fmt == "NCHW" else [224, 224, 3]
        img = pt.layers.data("img", shape, dtype="float32")
        label = pt.layers.data("label", [1], dtype="int64")
        logits = resnet.resnet50(img, 1000, data_format=fmt)
        loss = pt.layers.mean(
            pt.layers.softmax_with_cross_entropy(logits, label))
        opt = pt.optimizer.MomentumOptimizer(0.1, 0.9)
        if amp:
            opt = pt.contrib.mixed_precision.decorate(opt)
        opt.minimize(loss)

    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    ishape = (batch, 3, 224, 224) if fmt == "NCHW" \
        else (batch, 224, 224, 3)
    feed = {"img": jnp.asarray(rng.rand(*ishape).astype(np.float32)),
            "label": jnp.asarray(
                rng.randint(0, 1000, (batch, 1)).astype(np.int64))}

    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        l, = exe.run(main_prog, feed=feed, fetch_list=[loss])
        assert np.isfinite(l).all(), f"non-finite loss {l}"
        t0 = time.perf_counter()
        last = None
        for _ in range(steps):
            last = exe.run(main_prog, feed=feed, fetch_list=[loss],
                           return_numpy=False)[0]
        lv = float(np.asarray(last).reshape(()))  # host sync
        dt = (time.perf_counter() - t0) / steps
        assert np.isfinite(lv), f"non-finite loss {lv}"

    flops = 3 * 2 * 4.089e9 * batch
    mfu = flops / dt / peak
    print(json.dumps({
        "metric": "resnet50_train_mfu",
        "value": round(mfu, 4),
        "unit": "MFU (batch=%d %s amp=%d, %.1f img/s, %.1f ms/step)"
                % (batch, fmt, amp, batch / dt, dt * 1e3),
        "vs_baseline": round(mfu / 0.45, 4),
        # the measured raw-JAX ceiling for this model on this chip is
        # ~30% MFU, not 45% — see BASELINE.md's roofline section
        "vs_jax_probe": round(mfu / 0.303, 4),
        "device": device_report(),
    }))


if __name__ == "__main__":
    sys.exit(main())
