"""Moonlight-16B-A3B as published: the DeepSeek-V3 block (`model_type:
deepseek_v3`) at the keys of https://huggingface.co/moonshotai/Moonlight-16B-A3B
`config.json`. Plain jax.numpy in float32 at the highest matmul precision: no
cache, no kernels, no batching, attention in the EXPANDED form only (keys and
values of every head written out), the experts in a Python loop. It shares no
code with paddle_tpu; only the parameter tree's layout is the served one, so
that the same weights can be given to both (`x @ W`, W is (in, out)):

  {"wte": (V, h), "head": (h, V), "norm_f": (h,),
   "layers": [{"norm1", "norm2": (h,), "wq": (h, n*(nope+rope)),
               "wkva": (h, rank+rope), "kv_norm": (rank,),
               "wkvb": (rank, n*(nope+v)), "wo": (n*v, h),
               and "gate", "up": (h, I), "down": (I, h)            (dense)
               or  "router": (h, E), "router_bias": (E,),
                   "w_gate", "w_up": (E, h, F), "w_down": (E, F, h),
                   "shared_gate", "shared_up": (h, Fs), "shared_down": (Fs, h)}]}

The block, with x^ = RMSNorm(x) (eps `rms_norm_eps`, no bias anywhere):
  q = x^ W_q -> n heads of [q_nope | q_rope];  [c_raw | k_rope_raw] = x^ W_kva;
  c = RMSNorm_kv(c_raw);  k_rope = RoPE(k_rope_raw), one a token for all heads;
  q_rope = RoPE(q_rope);  [k_nope | v] of each head = c W_kvb;
  scores (q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope), causal softmax,
  o = sum p v, out = concat(o) W_o;  h = x + out;  y = h + FFN(RMSNorm(h)).
  FFN of the first `first_k_dense_replace` layers: W_down(silu(W_gate x^) * W_up x^).
  FFN of the others: s = sigmoid(x^ W_g) over the E experts; the k largest of
  s + e_score_correction_bias are picked (`n_group` = `topk_group` = 1: no
  group stage); their weights are s (WITHOUT the bias) at the picks over their
  sum + 1e-20, times `routed_scaling_factor`; sum_e w_e Expert_e(x^) +
  Shared(x^), every expert a SwiGLU of width `moe_intermediate_size`, the shared
  one a SwiGLU of `n_shared_experts` times that.
  Final RMSNorm, logits = y W_head (untied).
RoPE is theta `rope_theta` in the published element order: the interleaved
pairs of the last axis are permuted to halves, then x cos + rotate_half(x) sin.

Departures from the published code, none of which changes a value: no YaRN
(`rope_scaling` is absent from this config, so mscale is 1); `q_lora_rank` is
null, so the query is one projection; each expert is applied to EVERY token
and weighted by its routing weight, which is zero where it was not picked
(the published code gathers the routed tokens: the same sum); a sequence is
computed layer by layer with ONE layer's weights widened to float32 at a time
and one expert at a time, so that it fits beside the served weights on a chip.
"""

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x (..., d) at integer positions pos (broadcastable to x's leading axes)."""
    d = x.shape[-1]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)        # pairs -> halves
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    freqs = pos.astype(F32)[..., None] * inv_freq
    emb = jnp.concatenate([freqs, freqs], -1)
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(emb) + rotated * jnp.sin(emb)


def _attention(x, lp, cfg):
    """x (T, h) float32 -> x + Attn(RMSNorm(x)), head by head."""
    T = x.shape[0]
    n, nope, rope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, vd = cfg["kv_lora_rank"], cfg["v_head_dim"]
    lp = {k: jnp.asarray(v, F32) for k, v in lp.items()}
    pos = jnp.arange(T)
    xh = _rms_norm(x, lp["norm1"], cfg["rms_norm_eps"])
    q = (xh @ lp["wq"]).reshape(T, n, nope + rope)
    kva = xh @ lp["wkva"]
    c = _rms_norm(kva[:, :rank], lp["kv_norm"], cfg["rms_norm_eps"])
    k_rope = _rope(kva[:, rank:], pos, cfg["rope_theta"])
    kv = (c @ lp["wkvb"]).reshape(T, n, nope + vd)
    causal = pos[None, :] <= pos[:, None]
    outs = []
    for h in range(n):
        q_nope, q_rope = q[:, h, :nope], _rope(q[:, h, nope:], pos, cfg["rope_theta"])
        k_nope, v = kv[:, h, :nope], kv[:, h, nope:]
        scores = (q_nope @ k_nope.T + q_rope @ k_rope.T) / math.sqrt(nope + rope)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        outs.append(probs @ v)
    return x + jnp.concatenate(outs, -1) @ lp["wo"]


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _dense_ffn(x, lp, cfg):
    lp = {k: jnp.asarray(v, F32) for k, v in lp.items()}
    xh = _rms_norm(x, lp["norm2"], cfg["rms_norm_eps"])
    return x + _swiglu(xh, lp["gate"], lp["up"], lp["down"])


def router(xh, w_router, bias, cfg):
    """xh (T, h) float32, normed -> (picks (T, k), weights (T, k), dense (T, E)
    of the weights at their experts and zero elsewhere)."""
    scores = jax.nn.sigmoid(xh @ jnp.asarray(w_router, F32))
    k = cfg["num_experts_per_tok"]
    _, picks = jax.lax.top_k(scores + jnp.asarray(bias, F32), k)
    weights = jnp.take_along_axis(scores, picks, -1)
    if cfg["norm_topk_prob"]:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    weights = weights * cfg["routed_scaling_factor"]
    dense = jnp.zeros_like(scores).at[jnp.arange(xh.shape[0])[:, None], picks].set(weights)
    return picks, weights, dense


def pick_gap(xh, w_router, bias, cfg):
    """(T,): how far the last expert picked is ahead of the first one left out,
    in the biased score the picks are made by. The picks are discontinuous in
    it: a system that computes in a lower precision picks another expert where
    this is within its rounding, and its logits at that position are then
    another function's, however right it is."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(xh @ jnp.asarray(w_router, F32)) + jnp.asarray(bias, F32)
    best, _ = jax.lax.top_k(scores, k + 1)
    return best[:, k - 1] - best[:, k]


def _moe_head(x, lp, cfg):
    """The norm, the router and the shared expert: (xh, dense weights, shared,
    the picks' gap)."""
    lp = {k: jnp.asarray(v, F32) for k, v in lp.items()}
    xh = _rms_norm(x, lp["norm2"], cfg["rms_norm_eps"])
    _, _, dense = router(xh, lp["router"], lp["router_bias"], cfg)
    return (xh, dense, _swiglu(xh, lp["shared_gate"], lp["shared_up"], lp["shared_down"]),
            pick_gap(xh, lp["router"], lp["router_bias"], cfg))


def _expert(acc, xh, w_col, gate, up, down):
    return acc + w_col[:, None] * _swiglu(xh, jnp.asarray(gate, F32), jnp.asarray(up, F32),
                                          jnp.asarray(down, F32))


def _logits(x, norm_f, head, eps):
    return _rms_norm(x, jnp.asarray(norm_f, F32), eps) @ jnp.asarray(head, F32)


_ATTN = ("norm1", "wq", "wkva", "kv_norm", "wkvb", "wo")
_MOE_HEAD = ("norm2", "router", "router_bias", "shared_gate", "shared_up", "shared_down")


def _static(cfg):
    """The config's numbers the jitted pieces close over, hashable."""
    keys = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "kv_lora_rank",
            "v_head_dim", "rms_norm_eps", "rope_theta", "num_experts_per_tok",
            "norm_topk_prob", "routed_scaling_factor")
    return tuple((k, cfg[k]) for k in keys)


_PIECES = {}


def _pieces(cfg):
    key = _static(cfg)
    if key not in _PIECES:
        c = dict(key)
        _PIECES[key] = {
            "attention": jax.jit(lambda x, lp: _attention(x, lp, c)),
            "dense_ffn": jax.jit(lambda x, lp: _dense_ffn(x, lp, c)),
            "moe_head": jax.jit(lambda x, lp: _moe_head(x, lp, c)),
            "expert": jax.jit(_expert, donate_argnums=(0,)),
            "logits": jax.jit(lambda x, g, w: _logits(x, g, w, c["rms_norm_eps"])),
        }
    return _PIECES[key]


def sequence_logits(params, cfg, tokens, rows=None, gaps=False):
    """tokens (T,) -> logits (len(rows), V) float32 of one sequence at the
    positions `rows` (all of them when None). `cfg` is the configuration file's
    dict (the published keys). Layer by layer, expert by expert. With `gaps`,
    also each of those positions' smallest `pick_gap` over the expert layers."""
    fn = _pieces(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    least_gap = jnp.full(tokens.shape, jnp.inf, F32)
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["wte"][tokens], F32)
        for lp in params["layers"]:
            x = fn["attention"](x, {k: lp[k] for k in _ATTN})
            if "router" not in lp:
                x = fn["dense_ffn"](x, {k: lp[k] for k in ("norm2", "gate", "up", "down")})
                continue
            xh, dense, acc, gap = fn["moe_head"](x, {k: lp[k] for k in _MOE_HEAD})
            least_gap = jnp.minimum(least_gap, gap)
            for e in range(lp["w_gate"].shape[0]):
                acc = fn["expert"](acc, xh, dense[:, e], lp["w_gate"][e], lp["w_up"][e],
                                   lp["w_down"][e])
            x = x + acc
        if rows is not None:
            x, least_gap = x[jnp.asarray(rows)], least_gap[jnp.asarray(rows)]
        logits = fn["logits"](x, params["norm_f"], params["head"])
        return (logits, least_gap) if gaps else logits
