"""First dygraph step-time measurement (VERDICT r3 item 8): the same MLP
trained eagerly (tape + per-step jitted update) vs as a static Program, on
whatever device JAX selects (run without JAX_PLATFORMS=cpu for the TPU).

Run: python tools/bench_dygraph.py [steps]
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

import paddle_tpu as pt  # noqa: E402
from paddle_tpu import dygraph  # noqa: E402

B, D, H, C = 256, 1024, 1024, 64
STEPS = int(sys.argv[1]) if len(sys.argv) > 1 else 50


def bench_eager():
    rng = np.random.RandomState(0)
    xs = rng.rand(B, D).astype("float32")
    ys = rng.randint(0, C, (B, 1)).astype("int64")
    with dygraph.guard():
        l1 = dygraph.Linear(D, H, act="relu")
        l2 = dygraph.Linear(H, H, act="relu")
        l3 = dygraph.Linear(H, C)
        opt = pt.optimizer.SGD(0.01)

        def step():
            x = dygraph.to_variable(xs)
            y = dygraph.to_variable(ys)
            loss = dygraph.nn.reduce_mean(
                dygraph.nn.softmax_with_cross_entropy(l3(l2(l1(x))), y))
            loss.backward()
            opt.minimize(loss, parameter_list=(l1.parameters()
                                               + l2.parameters()
                                               + l3.parameters()))
            for lyr in (l1, l2, l3):
                lyr.clear_gradients()
            return loss

        step()  # warmup/compile
        t0 = time.perf_counter()
        for _ in range(STEPS):
            loss = step()
        _ = loss.numpy()  # sync
        return (time.perf_counter() - t0) / STEPS * 1e3


def bench_static():
    rng = np.random.RandomState(0)
    xs = rng.rand(B, D).astype("float32")
    ys = rng.randint(0, C, (B, 1)).astype("int64")
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = pt.layers.data("x", [D])
        y = pt.layers.data("y", [1], dtype="int64")
        h = pt.layers.fc(x, H, act="relu")
        h = pt.layers.fc(h, H, act="relu")
        logits = pt.layers.fc(h, C)
        loss = pt.layers.mean(
            pt.layers.softmax_with_cross_entropy(logits, y))
        pt.optimizer.SGD(0.01).minimize(loss)
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        feed = {"x": xs, "y": ys}
        exe.run(main, feed=feed, fetch_list=[loss])  # warmup
        t0 = time.perf_counter()
        for _ in range(STEPS):
            out = exe.run(main, feed=feed, fetch_list=[loss])
        _ = np.asarray(out[0])
        return (time.perf_counter() - t0) / STEPS * 1e3


def bench_encoder():
    """Model-scale pair (VERDICT r4 Weak #4: the MLP row measured
    dispatch noise): a hidden=768 4-layer transformer encoder, CHAINED
    steps with one sync at the end — dygraph dispatches each op eagerly
    but asynchronously, so per-step device time is what's measured, not
    a host round trip per op."""
    from paddle_tpu.models.transformer import (encoder_block_program,
                                               encoder_block_weights,
                                               make_dygraph_encoder)
    hdim, heads, ffn, layers_n, vocab, seq, b = 768, 12, 3072, 4, 4000, \
        128, 32
    w = encoder_block_weights(hdim, heads, ffn, layers_n, vocab)
    rng = np.random.RandomState(0)
    xs = rng.randint(0, vocab, (b, seq)).astype(np.int64)
    ys = rng.randint(0, vocab, (b, 1)).astype(np.int64)

    main, startup, loss = encoder_block_program(
        w, hdim, heads, ffn, layers_n, seq, vocab)
    with pt.program_guard(main, startup):
        pt.optimizer.SGD(0.01).minimize(loss)
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        feed = {"tokens": xs, "label": ys}
        exe.run(main, feed=feed, fetch_list=[loss])
        t0 = time.perf_counter()
        out = None
        for _ in range(STEPS):
            out = exe.run(main, feed=feed, fetch_list=[loss],
                          return_numpy=False)
        float(np.ravel(np.asarray(out[0]))[0])
        s_ms = (time.perf_counter() - t0) / STEPS * 1e3

    with dygraph.guard():
        layers_, forward = make_dygraph_encoder(
            w, hdim, heads, ffn, layers_n, vocab)
        opt = pt.optimizer.SGD(0.01)
        params = [p for lyr in layers_ for p in lyr.parameters()]

        def step():
            loss_vb = forward(dygraph.to_variable(xs),
                              dygraph.to_variable(ys))
            loss_vb.backward()
            opt.minimize(loss_vb, parameter_list=params)
            for lyr in layers_:
                lyr.clear_gradients()
            return loss_vb

        step()
        t0 = time.perf_counter()
        loss_vb = None
        for _ in range(STEPS):
            loss_vb = step()
        float(loss_vb.numpy())  # one sync for the whole chain
        e_ms = (time.perf_counter() - t0) / STEPS * 1e3
    return e_ms, s_ms, f"encoder h={hdim} L={layers_n} b={b} s={seq}"


def main():
    import jax
    dev = jax.devices()[0].platform
    if os.environ.get("BENCH_DYGRAPH_MODEL", "mlp") == "encoder":
        e, s, desc = bench_encoder()
        print(f"device={dev} {desc}, {STEPS} steps: dygraph {e:.2f} "
              f"ms/step, static {s:.2f} ms/step, eager overhead "
              f"{e / s:.2f}x")
        return
    e = bench_eager()
    s = bench_static()
    print(f"device={dev} MLP {D}x{H}x{H}x{C} b={B}, {STEPS} steps: "
          f"dygraph {e:.2f} ms/step, static {s:.2f} ms/step, "
          f"eager overhead {e / s:.2f}x")


if __name__ == "__main__":
    main()
