"""GPT-2 as published (Radford et al. 2019; the Hugging Face `gpt2` family's
config.json keys): pre-LN blocks, learned positions, gelu_new, a tied head.
Plain jax.numpy in float32 at the highest matmul precision: no cache, no
kernels, no batching tricks. It shares no code with paddle_tpu; only the
parameter tree's layout is the served one, so that the same weights can be
given to both:

  {"wte": (V, h), "wpe": (P, h), "lnf": {"g", "b"},
   "blocks": [{"ln1", "ln2": {"g", "b"},
               "q", "k", "v", "out", "mlp1", "mlp2": {"w": (in, out), "b"}}]}

Departure from the published layout: q, k and v are three (h, h) matrices and
not one (h, 3h) `c_attn`; the product is the same."""

import math

import jax
import jax.numpy as jnp


def _layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["g"] + p["b"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _linear(x, p):
    return x @ p["w"] + p["b"]


def as_float32(params):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)


def logits(params, tokens, n_head, eps=1e-5):
    """tokens (s,) -> logits (s, V) of one sequence."""
    params = as_float32(params)      # exact: bfloat16 widens without rounding
    s = tokens.shape[0]
    x = params["wte"][tokens] + params["wpe"][:s]
    causal = jnp.tril(jnp.ones((s, s), bool))
    for blk in params["blocks"]:
        y = _layer_norm(x, blk["ln1"], eps)
        q, k, v = (_linear(y, blk[n]).reshape(s, n_head, -1) for n in ("q", "k", "v"))
        scores = jnp.einsum("qnd,knd->nqk", q, k) / math.sqrt(q.shape[-1])
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        x = x + _linear(jnp.einsum("nqk,knd->qnd", probs, v).reshape(s, -1), blk["out"])
        y = _layer_norm(x, blk["ln2"], eps)
        x = x + _linear(_gelu_new(_linear(y, blk["mlp1"])), blk["mlp2"])
    return _layer_norm(x, params["lnf"], eps) @ params["wte"].T


def lm_loss(params, tokens, n_head, eps=1e-5):
    """Mean next-token cross-entropy of one sequence: positions 0..s-2
    predict tokens 1..s-1."""
    lg = logits(params, tokens, n_head, eps)[:-1]
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1).mean()


def sequence_logits(params, tokens, n_head, eps=1e-5):
    """float32, highest precision: on a TPU a float32 product otherwise runs in
    bfloat16 passes."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(logits, static_argnums=(2, 3))(
            params, jnp.asarray(tokens, jnp.int32), n_head, eps)


def batch_loss(params, batch, n_head, eps=1e-5):
    """The loss of a whole batch, computed sequence by sequence."""
    fn = jax.jit(lm_loss, static_argnums=(2, 3))
    with jax.default_matmul_precision("highest"):
        losses = [float(fn(params, jnp.asarray(row, jnp.int32), n_head, eps))
                  for row in batch]
    return sum(losses) / len(losses)
