"""Pure-JAX ResNet-50 training-step roofline probe.

Measures what raw jax (no framework) achieves for the same model shape on
this chip — the ceiling our executor-lowered program should approach.
Flags: BATCH, STEPS, DTYPE (bf16|f32), FMT (NCHW|NHWC), BN (f32|bf16).
"""

import functools
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.observability.device_peaks import device_peaks

BATCH = int(os.environ.get("BATCH", 128))
STEPS = int(os.environ.get("STEPS", 20))
DTYPE = jnp.bfloat16 if os.environ.get("DTYPE", "bf16") == "bf16" \
    else jnp.float32
FMT = os.environ.get("FMT", "NHWC")
BN_DTYPE = jnp.float32 if os.environ.get("BN", "f32") == "f32" \
    else jnp.bfloat16

CFG = (3, 4, 6, 3)


def conv(x, w, stride):
    if FMT == "NHWC":
        dn = ("NHWC", "HWIO", "NHWC")
    else:
        dn = ("NCHW", "HWIO", "NCHW")
    kh = w.shape[0]
    # even kernels need asymmetric padding to preserve the grid size
    # (a symmetric kh//2 pad on a 4x4 kernel yields 113x113, not 112x112)
    pad = (((kh - 1) // 2, kh // 2),) * 2 if kh > 1 else ((0, 0), (0, 0))
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), pad, dimension_numbers=dn)


def bn(x, scale, bias):
    axis = (0, 1, 2) if FMT == "NHWC" else (0, 2, 3)
    xc = x.astype(BN_DTYPE)
    m = xc.mean(axis)
    v = ((xc - (m.reshape((1, 1, 1, -1) if FMT == "NHWC"
                          else (1, -1, 1, 1)))) ** 2).mean(axis)
    shape = (1, 1, 1, -1) if FMT == "NHWC" else (1, -1, 1, 1)
    y = (xc - m.reshape(shape)) * jax.lax.rsqrt(v + 1e-5).reshape(shape)
    return (y * scale.reshape(shape) + bias.reshape(shape)).astype(x.dtype)


def init_params(key):

    def mk_conv(cin, cout, k):
        nonlocal key
        key, sk = jax.random.split(key)
        w = jax.random.normal(sk, (k, k, cin, cout), jnp.float32) * 0.05
        return {"w": w, "scale": jnp.ones((cout,)),
                "bias": jnp.zeros((cout,))}

    layers, spec = [], []
    if os.environ.get("S2D", "0") == "1":
        assert FMT == "NHWC", "S2D=1 is implemented for FMT=NHWC only"
        # space-to-depth stem: 2x2 blocks folded into channels; the 7x7/s2
        # conv becomes a dense 4x4/s1 conv over [112,112,12] (C=3 convs are
        # padding-bound on the 128-lane MXU — the classic MLPerf trick)
        layers.append(mk_conv(12, 64, 4))
        spec.append(("conv_s2d", False, 1))
    else:
        layers.append(mk_conv(3, 64, 7))
        spec.append(("conv", False, 2))
    cin = 64
    for stage, blocks in enumerate(CFG):
        cout = 64 * (2 ** stage)
        for b in range(blocks):
            stride = 2 if (b == 0 and stage > 0) else 1
            blk = {
                "c1": mk_conv(cin, cout, 1),
                "c2": mk_conv(cout, cout, 3),
                "c3": mk_conv(cout, cout * 4, 1),
            }
            if cin != cout * 4 or stride != 1:
                blk["sc"] = mk_conv(cin, cout * 4, 1)
            layers.append(blk)
            spec.append(("block", "sc" in blk, stride))
            cin = cout * 4
    key, sk = jax.random.split(key)
    fc_w = jax.random.normal(sk, (2048, 1000), jnp.float32) * 0.01
    return {"layers": layers, "fc": fc_w}, tuple(spec)


def forward(params, spec, x):
    x = x.astype(DTYPE)
    for (kind, _, stride), p in zip(spec, params["layers"]):
        if kind == "conv_s2d":
            n, h, w_, c = x.shape
            x = x.reshape(n, h // 2, 2, w_ // 2, 2, c).transpose(
                0, 1, 3, 2, 4, 5).reshape(n, h // 2, w_ // 2, 4 * c)
            x = jax.nn.relu(bn(conv(x, p["w"].astype(DTYPE), stride),
                               p["scale"], p["bias"]))
            x = jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                "SAME")
        elif kind == "conv":
            x = jax.nn.relu(bn(conv(x, p["w"].astype(DTYPE), stride),
                               p["scale"], p["bias"]))
            x = jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max, (1, 3, 3, 1) if FMT == "NHWC"
                else (1, 1, 3, 3), (1, 2, 2, 1) if FMT == "NHWC"
                else (1, 1, 2, 2), "SAME")
        else:
            sc = x
            y = jax.nn.relu(bn(conv(x, p["c1"]["w"].astype(DTYPE), 1),
                               p["c1"]["scale"], p["c1"]["bias"]))
            y = jax.nn.relu(bn(conv(y, p["c2"]["w"].astype(DTYPE), stride),
                               p["c2"]["scale"], p["c2"]["bias"]))
            y = bn(conv(y, p["c3"]["w"].astype(DTYPE), 1),
                   p["c3"]["scale"], p["c3"]["bias"])
            if "sc" in p:
                sc = bn(conv(sc, p["sc"]["w"].astype(DTYPE), stride),
                        p["sc"]["scale"], p["sc"]["bias"])
            x = jax.nn.relu(sc + y)
    axis = (1, 2) if FMT == "NHWC" else (2, 3)
    x = x.mean(axis)
    return (x.astype(DTYPE) @ params["fc"].astype(DTYPE)).astype(
        jnp.float32)


def loss_fn(params, spec, x, labels):
    logits = forward(params, spec, x)
    lp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(lp, labels[:, None], 1).mean()


@functools.partial(jax.jit, static_argnums=(2,), donate_argnums=(0, 1))
def train_step(params, mom, spec, x, labels):
    loss, grads = jax.value_and_grad(loss_fn)(params, spec, x, labels)
    new_mom = jax.tree.map(lambda m, g: 0.9 * m + g, mom, grads)
    new_p = jax.tree.map(lambda p, m: p - 0.1 * m, params, new_mom)
    return new_p, new_mom, loss


def main():
    print("devices:", jax.devices())
    key = jax.random.PRNGKey(0)
    params, spec = init_params(key)
    mom = jax.tree.map(jnp.zeros_like, params)
    shape = (BATCH, 224, 224, 3) if FMT == "NHWC" else (BATCH, 3, 224, 224)
    x = jnp.asarray(np.random.RandomState(0).rand(*shape), jnp.float32)
    y = jnp.asarray(np.random.RandomState(1).randint(0, 1000, BATCH))

    params, mom, l = train_step(params, mom, spec, x, y)
    jax.block_until_ready(l)
    t0 = time.perf_counter()
    for _ in range(STEPS):
        params, mom, l = train_step(params, mom, spec, x, y)
    jax.block_until_ready(l)
    dt = (time.perf_counter() - t0) / STEPS
    flops = 3 * 2 * 4.089e9 * BATCH  # fwd ~4.089 GMAC/img -> x2 flops, x3 train
    PEAK = device_peaks()["bf16_flops"]
    print(f"fmt={FMT} dtype={DTYPE.__name__} bn={BN_DTYPE.__name__} "
          f"batch={BATCH}: {dt*1e3:.1f} ms/step, {BATCH/dt:.0f} img/s, "
          f"MFU={flops/dt/PEAK:.3f}, loss={float(l):.3f}")


if __name__ == "__main__":
    main()
