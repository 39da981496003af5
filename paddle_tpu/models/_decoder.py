"""What the served decoder blocks share below their attention and their
feed-forward: the RMS norm, rotary positions (plain and YaRN), masked XLA
attention, the stage `embed`, the untied head and the activation type.

Imported by every served leaf but GPT's, by
models/_experts.py and by models/_grouped.py; imports no model and, at
module level, no jax (`import paddle_tpu` never loads this file).
"""

from __future__ import annotations

import math

__all__ = ["rms", "yarn_mscale", "rope_frequencies", "rope",
           "masked_attention", "embed", "head", "act_dtype"]


def rms(x, g, eps, centred=False):
    """RMS norm, statistics in float32, the result in x's type. `centred`
    (static): the weight is stored ZERO-CENTRED and the scale is `1 + g`
    (Qwen3-Next's family: a weight of zeros is the plain norm)."""
    import jax
    import jax.numpy as jnp
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    g = g.astype(jnp.float32)
    return (x32 * inv * (1.0 + g if centred else g)).astype(x.dtype)


def yarn_mscale(factor, mscale):
    """YaRN's attention-magnitude correction for a context stretched
    `factor` times."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_frequencies(d, theta, scaling=None):
    """(inv_freq (d/2,) float32, what cos and sin are scaled by) of a
    rotary width d. Plain RoPE: theta^(-2i/d) and 1. YaRN (`scaling`,
    the published dict): each frequency blended between itself
    (extrapolation) and itself over `factor` (interpolation) by a linear
    ramp over the dimensions between the one that turns `beta_fast`
    times in the original context and the one that turns `beta_slow`
    times; cos and sin scaled by mscale(factor, mscale) over
    mscale(factor, mscale_all_dim)."""
    import jax.numpy as jnp
    extra = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if scaling is None:
        return extra, 1.0
    factor = scaling["factor"]
    original = scaling["original_max_position_embeddings"]

    def turns_dim(turns):
        return (d * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(turns_dim(scaling["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    keep = 1.0 - ramp                       # 1: extrapolate, 0: interpolate
    inv = extra / factor * (1 - keep) + extra * keep
    return inv, (yarn_mscale(factor, scaling.get("mscale", 1))
                 / yarn_mscale(factor, scaling.get("mscale_all_dim", 0)))


def rope(x, pos, theta, scaling=None, interleaved=True):
    """Rotary position on the last axis of x at integer positions `pos`
    (broadcast against x's leading axes), in the PUBLISHED element
    order: the interleaved pairs (x0, x1), (x2, x3), ... are first
    permuted to halves (x0, x2, ..., x1, x3, ...), then `x cos +
    rotate_half(x) sin`, at `rope_frequencies(d, theta, scaling)`.
    `interleaved=False`: a model published with its pairs already in
    halves (Mellum, SDAR) is not permuted. Float32 inside, x's type
    out."""
    import jax.numpy as jnp
    d = x.shape[-1]
    x32 = x.astype(jnp.float32)
    if interleaved:
        x32 = jnp.concatenate([x32[..., 0::2], x32[..., 1::2]], -1)
    inv, mscale = rope_frequencies(d, theta, scaling)
    ang = pos.astype(jnp.float32)[..., None] * inv
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    rot = jnp.concatenate([-x32[..., d // 2:], x32[..., :d // 2]], -1)
    return (x32 * cos + rot * sin).astype(x.dtype)


def masked_attention(q, k, v, mask, scale, q_block=512):
    """softmax(q k^T scale) v under `mask` (Tq, Tk), float32 scores and
    statistics, by blocks of query rows so that the score matrix of a
    long prompt never exists whole. q (Tq, n, d), k (Tk, n, d), v (Tk,
    n, dv) -> (Tq, n, dv)."""
    import jax
    import jax.numpy as jnp

    def block(args):
        qb, mb = args
        s = jnp.einsum("qnd,knd->nqk", qb, k,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(mb[None], s, -1e30)
        p = jnp.exp(s - jnp.max(s, -1, keepdims=True))
        p = (p / p.sum(-1, keepdims=True)).astype(v.dtype)
        return jnp.einsum("nqk,knd->qnd", p, v)

    tq = q.shape[0]
    if tq <= q_block or tq % q_block:
        return block((q, mask))
    nb = tq // q_block
    out = jax.lax.map(block, (q.reshape(nb, q_block, *q.shape[1:]),
                              mask.reshape(nb, q_block, mask.shape[1])))
    return out.reshape(tq, *out.shape[2:])


def embed(params, tokens, dtype):
    """The stage `embed`: the table read and the cast."""
    import jax
    with jax.named_scope("embed"):
        return params["wte"][tokens].astype(dtype)


def head(cfg, params, x):
    """The stage `head`: the final RMS norm (`norm_f`, `cfg.rms_eps`) and
    the untied head (`head` (h, V)) over rows x (T, h); logits float32."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope("head"):
        y = rms(x, params["norm_f"], cfg.rms_eps)
        return jnp.dot(y, params["head"],
                       preferred_element_type=jnp.float32)


def act_dtype(params):
    """bfloat16 for a bfloat16 parameter tree, else float32."""
    import jax.numpy as jnp
    return jnp.bfloat16 if params["wte"].dtype == jnp.bfloat16 \
        else jnp.float32
