"""Switch-MoE single-chip bench (VERDICT r4 item 8): a BERT-base-
comparable encoder whose FFNs are top-1 Switch MoE (E=8 experts of the
same 768->3072 shape), trained fwd+bwd+adam on one chip.

MFU accounting uses the MoE's ACTUAL matmul flops (experts process
capacity_factor x the tokens of a dense FFN, plus dispatch/combine
einsums and the router), so the number is comparable with the dense
BERT row. BENCH_EXPERTS / BENCH_CF / BENCH_BATCH / BENCH_SEQ override.

Run: python tools/bench_moe.py
"""

import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

H, FFN, LAYERS, HEADS, VOCAB = 768, 3072, 12, 12, 8192


def main():
    import paddle_tpu as pt
    from paddle_tpu.observability.device_peaks import device_peaks

    b = int(os.environ.get("BENCH_BATCH", 32))
    s = int(os.environ.get("BENCH_SEQ", 128))
    e = int(os.environ.get("BENCH_EXPERTS", 8))
    cf = float(os.environ.get("BENCH_CF", 1.25))
    steps = int(os.environ.get("BENCH_STEPS", 30))
    peak = device_peaks()["bf16_flops"]
    hd = H // HEADS
    cap = int(math.ceil(s * cf / e))

    main_p, startup = pt.Program(), pt.Program()
    with pt.unique_name_guard(), pt.program_guard(main_p, startup):
        toks = pt.layers.data("tokens", [s], dtype="int64")
        label = pt.layers.data("label", [1], dtype="int64")
        x = pt.layers.embedding(toks, size=[VOCAB, H],
                                param_attr=pt.ParamAttr(name="emb"))
        aux_total = None
        for i in range(LAYERS):
            h = pt.layers.layer_norm(x, begin_norm_axis=2)

            def proj(nm):
                t = pt.layers.fc(h, H, num_flatten_dims=2,
                                 param_attr=pt.ParamAttr(
                                     name=f"l{i}/{nm}.w"))
                return pt.layers.reshape(t, [0, s, HEADS, hd])
            q, k, v = proj("q"), proj("k"), proj("v")
            ctx = pt.layers.fused_attention(
                q, k, v, sm_scale=1.0 / math.sqrt(hd))
            ctx = pt.layers.reshape(ctx, [0, s, H])
            x = x + pt.layers.fc(ctx, H, num_flatten_dims=2,
                                 param_attr=pt.ParamAttr(
                                     name=f"l{i}/o.w"))
            h = pt.layers.layer_norm(x, begin_norm_axis=2)
            moe_out, aux = pt.nets.switch_moe_ffn(
                h, e, H, FFN, capacity_factor=cf,
                name_prefix=f"l{i}/moe")
            x = x + moe_out
            aux_total = aux if aux_total is None else aux_total + aux
        pooled = pt.layers.reduce_mean(x, dim=1)
        logits = pt.layers.fc(pooled, VOCAB)
        loss = pt.layers.mean(
            pt.layers.softmax_with_cross_entropy(logits, label)) + \
            pt.layers.scale(aux_total, scale=0.01)
        opt = pt.optimizer.Adam(1e-4)
        from paddle_tpu.contrib import mixed_precision
        if os.environ.get("BENCH_AMP", "1") == "1":
            opt = mixed_precision.decorate(opt)
        opt.minimize(loss)

    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    feed = {"tokens": jnp.asarray(rng.randint(0, VOCAB, (b, s)),
                                  jnp.int32),
            "label": jnp.asarray(rng.randint(0, VOCAB, (b, 1)),
                                 jnp.int32)}
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        l, = exe.run(main_p, feed=feed, fetch_list=[loss])
        assert np.isfinite(np.ravel(l)).all()
        t0 = time.perf_counter()
        last = None
        for _ in range(steps):
            last = exe.run(main_p, feed=feed, fetch_list=[loss],
                           return_numpy=False)[0]
        float(np.ravel(np.asarray(last))[0])
        dt = (time.perf_counter() - t0) / steps

    # fwd matmul flops (x3 for train): attention qkvo + scores/ctx,
    # router, dispatch/combine einsums, expert FFN at capacity
    attn = 8 * b * s * H * H + 4 * b * s * s * H
    router = 2 * b * s * H * e
    dispatch = 2 * 2 * b * s * e * cap * H
    experts = 2 * 2 * e * b * cap * H * FFN
    head = 2 * b * H * VOCAB
    fwd = LAYERS * (attn + router + dispatch + experts) + head
    mfu = 3.0 * fwd / dt / peak
    print(json.dumps({
        "metric": "switch_moe_bert_train_mfu",
        "value": round(mfu, 4),
        "unit": "MFU (E=%d cf=%.2f cap=%d b=%d s=%d, %.1f samples/s, "
                "%.1f ms/step)" % (e, cf, cap, b, s, b / dt, dt * 1e3),
        "vs_baseline": round(mfu / 0.45, 4),
    }))


if __name__ == "__main__":
    main()
