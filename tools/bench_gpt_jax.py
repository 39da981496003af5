"""Pure-JAX GPT-2-small training-step roofline probe (the bench_resnet_jax
discipline applied to the decoder-only flagship, VERDICT r4 item 1).

Measures what hand-written jax (no framework: no Program/Executor, no op
registry, donated buffers, chained steps) achieves for the IDENTICAL model
on this chip — the attainable ceiling the framework's GPT bench should
approach. Model matches paddle_tpu/models/gpt.py exactly: pre-LN blocks,
learned positions, separate q/k/v projections, tied wte head, residual +
embedding dropout (rbg PRNG, upscale_in_train), AMP-style bf16 compute
with f32 master params + f32 Adam, next-token CE over shifted slices.

Flags: BATCH, SEQ, STEPS, ATTN (einsum|flash — flash imports the same
Pallas kernel the framework dispatches to, so both columns of the
framework grid have a ceiling), DROPOUT (0.1).
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

BATCH = int(os.environ.get("BATCH", 32))
SEQ = int(os.environ.get("SEQ", 512))
STEPS = int(os.environ.get("STEPS", 30))
ATTN = os.environ.get("ATTN", "flash")
DROPOUT = float(os.environ.get("DROPOUT", 0.1))

VOCAB, HIDDEN, LAYERS, HEADS = 50257, 768, 12, 12
FFN = 4 * HIDDEN
HD = HIDDEN // HEADS


def init_params(key):
    def dense(key, din, dout):
        k1, _ = jax.random.split(key)
        return {"w": jax.random.normal(k1, (din, dout), jnp.float32) * 0.02,
                "b": jnp.zeros((dout,), jnp.float32)}

    keys = iter(jax.random.split(key, 8 * LAYERS + 4))
    p = {
        "wte": jax.random.normal(next(keys), (VOCAB, HIDDEN),
                                 jnp.float32) * 0.02,
        "wpe": jax.random.normal(next(keys), (SEQ, HIDDEN),
                                 jnp.float32) * 0.02,
        "lnf": {"g": jnp.ones((HIDDEN,)), "b": jnp.zeros((HIDDEN,))},
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
            "mlp1": dense(next(keys), HIDDEN, FFN),
            "mlp2": dense(next(keys), FFN, HIDDEN),
        })
    return p


def ln(x, p):
    xf = x.astype(jnp.float32)
    m = xf.mean(-1, keepdims=True)
    v = ((xf - m) ** 2).mean(-1, keepdims=True)
    return ((xf - m) * jax.lax.rsqrt(v + 1e-5) * p["g"] + p["b"]) \
        .astype(x.dtype)


FLAT = os.environ.get("FLAT", "0") == "1"


def dense(x, p):
    w, b = p["w"].astype(x.dtype), p["b"].astype(x.dtype)
    if FLAT and x.ndim == 3:  # mimic the framework mul op's 2D flatten
        bs, s, h = x.shape
        return (x.reshape(bs * s, h) @ w + b).reshape(bs, s, -1)
    return x @ w + b


def drop(x, rate, key):
    if rate <= 0:
        return x
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0).astype(x.dtype)


def causal_einsum_attention(q, k, v):
    # (b, s, n, d) in/out, masked-softmax reference — XLA's fusion path
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k)
    scores = scores.astype(jnp.float32) / np.sqrt(HD)
    sq = scores.shape[-1]
    mask = jnp.tril(jnp.ones((sq, sq), bool))
    scores = jnp.where(mask, scores, -1e9)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bnqk,bknd->bqnd", probs, v)


def attention(q, k, v):
    if ATTN == "flash":
        from paddle_tpu.ops.flash_attention import flash_attention
        return flash_attention(q, k, v, None, True, 1.0 / np.sqrt(HD),
                               jax.default_backend() != "tpu")
    return causal_einsum_attention(q, k, v)


def forward(params, tokens, key):
    b, s = tokens.shape
    x = params["wte"][tokens] + params["wpe"][:s]
    x = x.astype(jnp.bfloat16)
    keys = iter(jax.random.split(key, 1 + 2 * LAYERS))
    x = drop(x, DROPOUT, next(keys))
    for blk in params["blocks"]:
        h = ln(x, blk["ln1"])
        q = dense(h, blk["q"]).reshape(b, s, HEADS, HD)
        k = dense(h, blk["k"]).reshape(b, s, HEADS, HD)
        v = dense(h, blk["v"]).reshape(b, s, HEADS, HD)
        ctx = attention(q, k, v).reshape(b, s, HIDDEN)
        x = x + drop(dense(ctx, blk["out"]), DROPOUT, next(keys))
        h = ln(x, blk["ln2"])
        h = jax.nn.gelu(dense(h, blk["mlp1"]), approximate=True)
        x = x + drop(dense(h, blk["mlp2"]), DROPOUT, next(keys))
    x = ln(x, params["lnf"])
    return x @ params["wte"].T.astype(x.dtype)


def loss_fn(params, tokens, key):
    logits = forward(params, tokens, key)[:, :-1].astype(jnp.float32)
    labels = tokens[:, 1:]
    lp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(lp, labels[..., None], -1).mean()


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
def train_step(params, m, v, step, key, tokens):
    # step and key are device-resident carried state: a host-built scalar
    # per step would cost a H2D transfer that breaks the async chain
    key, sub = jax.random.split(key)
    loss, grads = jax.value_and_grad(loss_fn)(params, tokens, sub)
    b1, b2, eps, lr = 0.9, 0.999, 1e-8, 1e-4
    new_m = jax.tree.map(lambda mm, g: b1 * mm + (1 - b1) * g, m, grads)
    new_v = jax.tree.map(lambda vv, g: b2 * vv + (1 - b2) * g * g, v, grads)
    step = step + 1
    bc1 = 1 - b1 ** step
    bc2 = 1 - b2 ** step
    new_p = jax.tree.map(
        lambda p, mm, vv: p - lr * (mm / bc1) / (jnp.sqrt(vv / bc2) + eps),
        params, new_m, new_v)
    return new_p, new_m, new_v, step, key, loss


def flops_per_step(batch, seq):
    # identical formula to models/gpt.py flops_per_step
    per_tok = LAYERS * (4 * HIDDEN * HIDDEN + 2 * HIDDEN * FFN) * 2
    attn = LAYERS * 2 * 2 * HIDDEN * seq
    head = 2 * HIDDEN * VOCAB
    return 3.0 * batch * seq * (per_tok + attn + head)


def main():
    print("devices:", jax.devices(), "attn:", ATTN)
    params = init_params(jax.random.PRNGKey(0))
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    tokens = jnp.asarray(np.random.RandomState(0).randint(
        0, VOCAB, (BATCH, SEQ)), jnp.int32)
    key = jax.random.PRNGKey(1)

    step = jnp.float32(0)
    params, m, v, step, key, l = train_step(params, m, v, step, key, tokens)
    jax.block_until_ready(l)
    t0 = time.perf_counter()
    for i in range(STEPS):
        params, m, v, step, key, l = train_step(params, m, v, step, key,
                                                tokens)
    jax.block_until_ready(l)
    dt = (time.perf_counter() - t0) / STEPS

    prof = os.environ.get("PROFILE", "")
    if prof:  # 3 profiled steps for tools/profile_summary.py
        with jax.profiler.trace(prof):
            for i in range(3):
                params, m, v, step, key, l = train_step(
                    params, m, v, step, key, tokens)
            jax.block_until_ready(l)
    fl = flops_per_step(BATCH, SEQ)
    PEAK = device_peaks()["bf16_flops"]
    print(f"attn={ATTN} batch={BATCH} seq={SEQ}: {dt*1e3:.1f} ms/step, "
          f"{BATCH/dt:.1f} samples/s, MFU={fl/dt/PEAK:.3f}, loss={l:.3f}")


if __name__ == "__main__":
    main()
