"""Pure-JAX BERT-base training-step roofline probe (bench_gpt_jax's
discipline on the bidirectional flagship): the IDENTICAL model to
models/bert.py — word+segment+position embeddings, post-LN encoder,
separate q/k/v, einsum attention with the additive key mask, tied MLM
head over all positions, rbg dropout, bf16 compute + f32 Adam — with
device-resident carried state and donated buffers. The ceiling the
framework's 57.3% MFU headline should approach.

Flags: BATCH, SEQ, STEPS, DROPOUT.
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

jax.config.update("jax_default_prng_impl", "rbg")

BATCH = int(os.environ.get("BATCH", 128))
SEQ = int(os.environ.get("SEQ", 128))
STEPS = int(os.environ.get("STEPS", 30))
DROPOUT = float(os.environ.get("DROPOUT", 0.1))

VOCAB, HIDDEN, LAYERS, HEADS, TYPES = 30522, 768, 12, 12, 2
FFN = 4 * HIDDEN
HD = HIDDEN // HEADS


def init_params(key):
    def dense(key, din, dout):
        k1, _ = jax.random.split(key)
        return {"w": jax.random.normal(k1, (din, dout), jnp.float32) * 0.02,
                "b": jnp.zeros((dout,), jnp.float32)}

    keys = iter(jax.random.split(key, 8 * LAYERS + 6))
    p = {
        "wte": jax.random.normal(next(keys), (VOCAB, HIDDEN),
                                 jnp.float32) * 0.02,
        "wpe": jax.random.normal(next(keys), (SEQ, HIDDEN),
                                 jnp.float32) * 0.02,
        "sent": jax.random.normal(next(keys), (TYPES, HIDDEN),
                                  jnp.float32) * 0.02,
        "emb_ln": {"g": jnp.ones((HIDDEN,)), "b": jnp.zeros((HIDDEN,))},
        "blocks": [],
    }
    for _ in range(LAYERS):
        p["blocks"].append({
            "ln1": {"g": jnp.ones((HIDDEN,)), "b": jnp.zeros((HIDDEN,))},
            "ln2": {"g": jnp.ones((HIDDEN,)), "b": jnp.zeros((HIDDEN,))},
            "q": dense(next(keys), HIDDEN, HIDDEN),
            "k": dense(next(keys), HIDDEN, HIDDEN),
            "v": dense(next(keys), HIDDEN, HIDDEN),
            "out": dense(next(keys), HIDDEN, HIDDEN),
            "ffn1": dense(next(keys), HIDDEN, FFN),
            "ffn2": dense(next(keys), FFN, HIDDEN),
        })
    return p


def ln(x, p):
    xf = x.astype(jnp.float32)
    m = xf.mean(-1, keepdims=True)
    v = ((xf - m) ** 2).mean(-1, keepdims=True)
    return ((xf - m) * jax.lax.rsqrt(v + 1e-5) * p["g"] + p["b"]) \
        .astype(x.dtype)


def dense(x, p):
    return x @ p["w"].astype(x.dtype) + p["b"].astype(x.dtype)


def drop(x, rate, key):
    if rate <= 0:
        return x
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0).astype(x.dtype)


def forward(params, src, sent, mask_bias, key):
    b, s = src.shape
    x = (params["wte"][src] + params["sent"][sent] + params["wpe"][:s])
    x = ln(x.astype(jnp.bfloat16), params["emb_ln"])
    keys = iter(jax.random.split(key, 1 + 2 * LAYERS))
    x = drop(x, DROPOUT, next(keys))
    for blk in params["blocks"]:
        q = dense(x, blk["q"]).reshape(b, s, HEADS, HD)
        k = dense(x, blk["k"]).reshape(b, s, HEADS, HD)
        v = dense(x, blk["v"]).reshape(b, s, HEADS, HD)
        scores = jnp.einsum("bqnd,bknd->bnqk", q, k,
                            preferred_element_type=jnp.float32)
        scores = scores / np.sqrt(HD) + mask_bias
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        ctx = jnp.einsum("bnqk,bknd->bqnd", probs, v).reshape(b, s, HIDDEN)
        x = ln(x + drop(dense(ctx, blk["out"]), DROPOUT, next(keys)),
               blk["ln1"])
        h = jax.nn.gelu(dense(x, blk["ffn1"]), approximate=True)
        x = ln(x + drop(dense(h, blk["ffn2"]), DROPOUT, next(keys)),
               blk["ln2"])
    return x @ params["wte"].T.astype(x.dtype)


def loss_fn(params, src, sent, mask_bias, labels, key):
    logits = forward(params, src, sent, mask_bias, key)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.take_along_axis(logp, labels[..., None], -1).mean()


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
def train_step(params, m, v, step, key, src, sent, mask_bias, labels):
    key, sub = jax.random.split(key)
    loss, grads = jax.value_and_grad(loss_fn)(params, src, sent,
                                              mask_bias, labels, sub)
    b1, b2, eps, lr = 0.9, 0.999, 1e-8, 1e-4
    new_m = jax.tree.map(lambda mm, g: b1 * mm + (1 - b1) * g, m, grads)
    new_v = jax.tree.map(lambda vv, g: b2 * vv + (1 - b2) * g * g, v,
                         grads)
    step = step + 1
    bc1 = 1 - b1 ** step
    bc2 = 1 - b2 ** step
    new_p = jax.tree.map(
        lambda p, mm, vv: p - lr * (mm / bc1) / (jnp.sqrt(vv / bc2) + eps),
        params, new_m, new_v)
    return new_p, new_m, new_v, step, key, loss


def flops_per_step(batch, seq):
    # same convention as models/bert.py flops_per_step
    per_layer = 24 * batch * seq * HIDDEN * HIDDEN \
        + 4 * batch * seq * seq * HIDDEN
    fwd = LAYERS * per_layer + 2 * batch * seq * HIDDEN * VOCAB
    return 3.0 * fwd


def main():
    print("devices:", jax.devices())
    params = init_params(jax.random.PRNGKey(0))
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    rng = np.random.RandomState(0)
    src = jnp.asarray(rng.randint(0, VOCAB, (BATCH, SEQ)), jnp.int32)
    sent = jnp.asarray(rng.randint(0, TYPES, (BATCH, SEQ)), jnp.int32)
    labels = jnp.asarray(rng.randint(0, VOCAB, (BATCH, SEQ)), jnp.int32)
    mask_bias = jnp.zeros((BATCH, 1, 1, SEQ), jnp.float32)  # all-keep
    key = jax.random.PRNGKey(1)
    step = jnp.float32(0)

    params, m, v, step, key, l = train_step(params, m, v, step, key, src,
                                            sent, mask_bias, labels)
    jax.block_until_ready(l)
    t0 = time.perf_counter()
    for _ in range(STEPS):
        params, m, v, step, key, l = train_step(params, m, v, step, key,
                                                src, sent, mask_bias,
                                                labels)
    l = float(l)  # hard D2H sync
    dt = (time.perf_counter() - t0) / STEPS
    fl = flops_per_step(BATCH, SEQ)
    PEAK = device_peaks()["bf16_flops"]
    print(f"batch={BATCH} seq={SEQ}: {dt*1e3:.1f} ms/step, "
          f"{BATCH/dt:.1f} samples/s, MFU={fl/dt/PEAK:.3f}, loss={l:.3f}")


if __name__ == "__main__":
    main()
