"""Benchmark: BERT-base MLM pretrain step (fwd+bwd+adam) on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"device"}. vs_baseline is measured MFU / 0.45 (the BASELINE.md
north-star target). The peak is the bf16 rate published for the device
jax reports (paddle_tpu/observability/device_peaks.py); a device that
is not in that table is an error, so this runs on the chip only.

BENCH_MODEL=gpt2 switches to the GPT-2-small causal-LM benchmark
(tools/bench_gpt.py; same keys, vs_baseline shares the 0.45 north-star).
BENCH_MODEL=resnet50 switches to the ResNet-50 train benchmark
(tools/bench_resnet50.py): same keys, plus "vs_jax_probe" giving the
ratio to the measured raw-JAX ceiling on this chip (~30% MFU — see
BASELINE.md's roofline section; 45% is not attainable for conv nets
here, so vs_baseline < 1 is expected for this mode).
"""

import json
import os
import sys
import time

import numpy as np


def main():
    model = os.environ.get("BENCH_MODEL", "bert")
    if model in ("resnet50", "gpt2"):
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "tools"))
        if model == "resnet50":
            import bench_resnet50
            return bench_resnet50.main()
        import bench_gpt
        return bench_gpt.main()
    import paddle_tpu as pt
    from paddle_tpu.models.bert import (BertConfig, bert_pretrain_program,
                                        flops_per_step)
    from paddle_tpu.observability.device_peaks import (device_peaks,
                                                       device_report)

    seq = int(os.environ.get("BENCH_SEQ", 128))
    cfg = BertConfig(attn_impl=os.environ.get("BENCH_ATTN", "einsum"),
                     max_pos=max(512, seq))  # BERT-base
    batch = int(os.environ.get("BENCH_BATCH", 128))
    steps = int(os.environ.get("BENCH_STEPS", 30))
    peak = device_peaks()["bf16_flops"]

    amp = os.environ.get("BENCH_AMP", "1") == "1"
    recompute = os.environ.get("BENCH_RECOMPUTE", "0") == "1"
    main_prog, startup, fetches = bert_pretrain_program(
        cfg, seq, learning_rate=1e-4, amp=amp, recompute=recompute)

    rng = np.random.RandomState(0)
    feed = {
        "src_ids": rng.randint(0, cfg.vocab_size,
                               (batch, seq)).astype(np.int64),
        "sent_ids": rng.randint(0, 2, (batch, seq)).astype(np.int64),
        "input_mask": np.ones((batch, seq), np.float32),
        "mlm_labels": rng.randint(0, cfg.vocab_size,
                                  (batch, seq)).astype(np.int64),
    }

    import jax.numpy as jnp

    # device-resident feed: a real input pipeline keeps batches on device
    feed = {k: jnp.asarray(v) for k, v in feed.items()}

    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        loss_var = fetches["loss"]
        # warmup / compile
        l, = exe.run(main_prog, feed=feed, fetch_list=[loss_var])
        assert np.isfinite(l).all(), f"non-finite loss {l}"
        # steps chain through the donated scope on device; sync once at the
        # end (a per-step host sync would serialize dispatch and compute)
        t0 = time.perf_counter()
        last = None
        for _ in range(steps):
            last = exe.run(main_prog, feed=feed, fetch_list=[loss_var],
                           return_numpy=False)[0]
        last.block_until_ready()
        dt = (time.perf_counter() - t0) / steps
        l = np.asarray(last)
        assert np.isfinite(l).all(), f"non-finite loss {l}"

    fl = flops_per_step(cfg, batch, seq)
    mfu = fl / dt / peak
    sps = batch / dt
    print(json.dumps({
        "metric": "bert_base_train_mfu",
        "value": round(mfu, 4),
        "unit": "MFU (batch=%d seq=%d, %.1f samples/s, %.1f ms/step)"
                % (batch, seq, sps, dt * 1e3),
        "vs_baseline": round(mfu / 0.45, 4),
        "device": device_report(),
    }))


if __name__ == "__main__":
    sys.exit(main())
