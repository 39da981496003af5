"""Xing4.0-29B-A4B (`model_type: xing4_0`) at the keys of
https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B `config.json`: DeepSeek-V3's
block (latent attention, routed + shared experts) with the query through a
low-rank pair, YaRN positions and the residual path replaced by manifold-
constrained hyper-connections over `hc_mult` streams. Plain jax.numpy in float32
at the highest matmul precision: no cache, no kernels, no batching, attention in
the EXPANDED form only (each head's keys and values written out), the experts in
a Python loop. It shares no code with paddle_tpu or with moonlight_ref.py; only
the parameter tree's layout is the served one, so that the same weights can be
given to both (`x @ W`, W is (in, out); n = `hc_mult`, C = `hidden_size`):

  {"wte": (V, C), "head": (C, V), "norm_f": (C,),
   "layers": [{"norm1", "norm2": (C,), "wqa": (C, q_rank), "q_norm": (q_rank,),
               "wqb": (q_rank, heads*(nope+rope)), "wkva": (C, rank+rope),
               "kv_norm": (rank,), "wkvb": (rank, heads*(nope+v)), "wo": (heads*v, C),
               "hc_attn", "hc_ffn": {"hc_norm": (n*C,), "phi": (n*C, 2n + n*n),
                                     "b_pre", "b_post": (n,), "b_res": (n, n),
                                     "a_pre", "a_post", "a_res": ()},
               and "gate", "up": (C, I), "down": (I, C)            (dense)
               or  "router": (C, E), "router_bias": (E,),
                   "w_gate", "w_up": (E, C, F), "w_down": (E, F, C),
                   "shared_gate", "shared_up": (C, Fs), "shared_down": (Fs, C)}]}

PROVENANCE. Neither this file's writer (PR 31) nor the issue's had a network, the
mHC paper ("mHC: Manifold-Constrained Hyper-Connections", recalled as
arXiv:2512.24880), the Hyper-Connections paper (recalled as arXiv:2409.19606),
DeepSeek-V3's modelling code or Xing's own: the catalog's `config` (the model's
published keys) is the ONLY source that was read. Every line below marked
[recalled] is written from recollection of those sources and was checked against
nothing but the config's keys, which name the quantities (`hc_mult` 4,
`hc_sinkhorn_iters` 20, `hc_eps`, `mhc_h_res_clamp_min/max` -30/30, `q_lora_rank`,
`rope_scaling`); [config] marks what a key fixes. Nothing here is cited.

The streams [recalled: Hyper-Connections' entry and exit]: X_0 (n, C) is the
token's embedding repeated n times; after the last layer x = the sum of X's n
rows, then the final RMSNorm and the untied head.
One sublayer F (attention; the dense or expert feed-forward), each with a mixer
of its own [recalled: one mixer a sublayer, its own phi, b, a and norm]:
  z = RMSNorm_{nC}(vec X; hc_norm, hc_eps) . phi              [recalled: the norm over
      all n*C values before phi; `hc_eps` as its epsilon is assumed]
  z_pre (n), z_post (n), z_res (n, n) = the split of z's 2n + n^2 values
  H_pre  = sigmoid(a_pre z_pre + b_pre)                         [recalled]
  H_post = 2 sigmoid(a_post z_post + b_post)                    [recalled]
  M_0 = exp(clamp(a_res z_res + b_res, min, max))               [config: the clamp's
      limits; recalled: that it sits before the exp]
  `hc_sinkhorn_iters` [config] times: each COLUMN over (its sum + hc_eps), then each
      ROW over (its sum + hc_eps)  [recalled: Sinkhorn-Knopp, T_r(T_c(M)); the order
      and the epsilon in the divisions are assumed];  H_res = the result
  u = H_pre X (C,);  y = F(RMSNorm_C(u; norm1 | norm2));
  X' = H_res X + outer(H_post, y)                               [recalled]
Attention [config + recalled: DeepSeek-V2/V3's low-rank query]: c_q = RMSNorm(h W_qa;
q_norm), q = c_q W_qb -> heads of [q_nope | q_rope]; [c_raw | k_rope_raw] = h W_kva;
c = RMSNorm_kv(c_raw); k_rope = RoPE(k_rope_raw), one a token for all heads; [k_nope |
v] of each head = c W_kvb; scores (q_nope . k_nope + q_rope . k_rope) x scale, causal
softmax, o = sum p v, out = concat(o) W_o.
YaRN [recalled: DeepSeek-V3's `DeepseekV3YarnRotaryEmbedding`; config: its numbers]:
with d the rotary width, extra_i = theta^(-2i/d), inter_i = extra_i / factor,
  dim(r) = d ln(original / (2 pi r)) / (2 ln theta); low = floor(dim(beta_fast)),
  high = ceil(dim(beta_slow)), clipped to [0, d - 1];
  ramp_i = clip((i - low) / (high - low), 0, 1) over i < d/2;
  inv_freq_i = inter_i ramp_i + extra_i (1 - ramp_i);
  cos and sin times mscale(factor, mscale) / mscale(factor, mscale_all_dim), where
  mscale(s, m) = 0.1 m ln s + 1 for s > 1; scale = (nope + rope)^-1/2 x mscale(factor,
  mscale_all_dim)^2.
RoPE in the published element order of DeepSeek-V3 [recalled]: the interleaved pairs
of the last axis are permuted to halves, then x cos + rotate_half(x) sin.
Experts [config: sigmoid, noaux_tc, n_group 1, norm_topk_prob, the scaling factor;
recalled: DeepSeek-V3's gate]: s = sigmoid(x^ W_g); the k largest of s +
e_score_correction_bias are picked; their weights are s (WITHOUT the bias) at the
picks over their sum + 1e-20, times `routed_scaling_factor`; sum_e w_e Expert_e(x^)
+ Shared(x^), SwiGLUs all.

Departures, none of which changes a value: the multi-token-prediction module is
absent (`num_nextn_predict_layers` 0 in the cut configuration; how it joins n
streams is not public); each expert is applied to EVERY token and weighted by its
routing weight, zero where it was not picked (the published code gathers the routed
tokens: the same sum); a sequence is computed sublayer by sublayer with
its streams held in blocks of 2048 tokens, a block, a head and an expert at a time
and their weights widened to float32 where they are used (a head's keys and values
are written out over the whole sequence, once a head), so that 16k rows fit in the 2 GB
the served weights, the arena and the engine's programs leave on a chip.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
BLOCK = 2048


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rotary_frequencies(d, theta, scaling):
    """(inv_freq (d/2,), the factor on cos and sin)."""
    extra = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    if not scaling:
        return extra, 1.0
    factor, original = scaling["factor"], scaling["original_max_position_embeddings"]

    def correction_dim(rotations):
        return d * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=F32) - low) / (high - low), 0.0, 1.0)
    inv_freq = (extra / factor) * ramp + extra * (1.0 - ramp)
    return inv_freq, (yarn_mscale(factor, scaling.get("mscale", 1))
                      / yarn_mscale(factor, scaling.get("mscale_all_dim", 0)))


def softmax_scale(cfg):
    scale = 1.0 / math.sqrt(cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
    scaling = cfg.get("rope_scaling")
    if scaling and scaling.get("mscale_all_dim", 0):
        scale *= yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    return scale


def _rope(x, pos, cfg):
    """x (T, d) at integer positions pos (T,)."""
    d = x.shape[-1]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)        # pairs -> halves
    inv_freq, factor = rotary_frequencies(d, cfg["rope_theta"], cfg.get("rope_scaling"))
    freqs = pos.astype(F32)[:, None] * inv_freq
    emb = jnp.concatenate([freqs, freqs], -1)
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * (jnp.cos(emb) * factor) + rotated * (jnp.sin(emb) * factor)


def mixer_coefficients(X, hp, cfg):
    """X (T, n, C) float32 -> H_pre (T, n), H_post (T, n), H_res (T, n, n)."""
    T, n, C = X.shape
    hp = {k: jnp.asarray(v, F32) for k, v in hp.items()}
    eps = cfg["hc_eps"]
    z = _rms_norm(X.reshape(T, n * C), hp["hc_norm"], eps) @ hp["phi"]
    z_pre, z_post, z_res = z[:, :n], z[:, n:2 * n], z[:, 2 * n:].reshape(T, n, n)
    h_pre = jax.nn.sigmoid(hp["a_pre"] * z_pre + hp["b_pre"])
    h_post = 2.0 * jax.nn.sigmoid(hp["a_post"] * z_post + hp["b_post"])
    m = jnp.exp(jnp.clip(hp["a_res"] * z_res + hp["b_res"],
                         cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]))
    for _ in range(cfg["hc_sinkhorn_iters"]):
        m = m / (m.sum(-2, keepdims=True) + eps)                 # each column by its sum
        m = m / (m.sum(-1, keepdims=True) + eps)                 # each row by its sum
    return h_pre, h_post, m


def _mix_in(X, hp, cfg):
    """The sublayer's input and what its output is mixed back with."""
    h_pre, h_post, h_res = mixer_coefficients(X, hp, cfg)
    return jnp.einsum("tn,tnc->tc", h_pre, X), h_post, h_res


def _mix_out(X, h_post, h_res, y):
    return jnp.einsum("tji,tic->tjc", h_res, X) + h_post[:, :, None] * y[:, None, :]


def _latents(u, start, lp, cfg):
    """A block of tokens u (B, C) at positions start..: the query's normed latent
    c_q (B, q_rank), the normed latents c (B, rank) and the rotated shared key
    k_rope (B, rope)."""
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    lp = {k: jnp.asarray(v, F32) for k, v in lp.items()}
    h = _rms_norm(u, lp["norm1"], eps)
    kva = h @ lp["wkva"]
    pos = start + jnp.arange(u.shape[0])
    return (_rms_norm(h @ lp["wqa"], lp["q_norm"], eps),
            _rms_norm(kva[:, :rank], lp["kv_norm"], eps), _rope(kva[:, rank:], pos, cfg))


def _head_keys(c, k_rope, w_kvb, cfg):
    """One head's keys (T, nope + rope) and values (T, v), written out from the
    latents of the whole sequence through its slice w_kvb (rank, nope + v)."""
    nope = cfg["qk_nope_head_dim"]
    kv = c @ jnp.asarray(w_kvb, F32)
    return jnp.concatenate([kv[:, :nope], k_rope], -1), kv[:, nope:]


def _head_block(y, c_q, start, w_qb, k, v, w_o, cfg):
    """One head over one block of query rows: y (B, C) + softmax(q k^T scale) v
    W_o, q (B, nope + rope) from the block's c_q through the head's slice w_qb
    (q_rank, nope + rope), causal by position, w_o (v, C) the head's rows of W_o."""
    nope = cfg["qk_nope_head_dim"]
    rows = start + jnp.arange(c_q.shape[0])
    q = c_q @ jnp.asarray(w_qb, F32)
    q = jnp.concatenate([q[:, :nope], _rope(q[:, nope:], rows, cfg)], -1)
    scores = (q @ k.T) * softmax_scale(cfg)
    causal = jnp.arange(k.shape[0])[None, :] <= rows[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return y + (probs @ v) @ jnp.asarray(w_o, F32)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _dense_ffn(u, lp, cfg):
    lp = {k: jnp.asarray(v, F32) for k, v in lp.items()}
    return _swiglu(_rms_norm(u, lp["norm2"], cfg["rms_norm_eps"]), lp["gate"], lp["up"], lp["down"])


def router(xh, w_router, bias, cfg):
    """xh (T, C) float32, normed -> (picks (T, k), weights (T, k), dense (T, E)
    of the weights at their experts and zero elsewhere)."""
    scores = jax.nn.sigmoid(xh @ jnp.asarray(w_router, F32))
    _, picks = jax.lax.top_k(scores + jnp.asarray(bias, F32), cfg["num_experts_per_tok"])
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


def _moe_head(u, lp, cfg):
    """The norm, the router and the shared expert: (xh, dense weights, shared,
    the picks' gap)."""
    lp = {k: jnp.asarray(v, F32) for k, v in lp.items()}
    xh = _rms_norm(u, lp["norm2"], cfg["rms_norm_eps"])
    _, _, dense = router(xh, lp["router"], lp["router_bias"], cfg)
    return (xh, dense, _swiglu(xh, lp["shared_gate"], lp["shared_up"], lp["shared_down"]),
            pick_gap(xh, lp["router"], lp["router_bias"], cfg))


def _expert(acc, xh, w_col, gate, up, down):
    return acc + w_col[:, None] * _swiglu(xh, jnp.asarray(gate, F32), jnp.asarray(up, F32),
                                          jnp.asarray(down, F32))


def _logits(X, norm_f, head, eps):
    return _rms_norm(X.sum(1), jnp.asarray(norm_f, F32), eps) @ jnp.asarray(head, F32)


_LATENTS = ("norm1", "wqa", "q_norm", "wkva", "kv_norm")
_MOE_HEAD = ("norm2", "router", "router_bias", "shared_gate", "shared_up", "shared_down")


def _freeze(value):
    return tuple(sorted((k, _freeze(v)) for k, v in value.items())) \
        if isinstance(value, dict) else value


def _thaw(value):
    return {k: _thaw(v) for k, v in value} if isinstance(value, tuple) else value


def _static(cfg):
    """The config's numbers the jitted pieces close over, hashable."""
    keys = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "kv_lora_rank",
            "v_head_dim", "rms_norm_eps", "rope_theta", "rope_scaling", "num_experts_per_tok",
            "norm_topk_prob", "routed_scaling_factor", "hc_eps", "hc_sinkhorn_iters",
            "mhc_h_res_clamp_min", "mhc_h_res_clamp_max")
    return tuple((k, _freeze(cfg[k])) for k in keys)


_PIECES = {}


def _pieces(cfg):
    key = _static(cfg)
    if key not in _PIECES:
        c = {k: _thaw(v) for k, v in key}
        _PIECES[key] = {
            "mix_in": jax.jit(lambda X, hp: _mix_in(X, hp, c)),
            "mix_out": jax.jit(_mix_out, donate_argnums=(0,)),
            "latents": jax.jit(lambda u, start, lp: _latents(u, start, lp, c)),
            "head_keys": jax.jit(lambda cc, kr, w: _head_keys(cc, kr, w, c)),
            "head_block": jax.jit(lambda y, cq, start, wqb, k, v, wo:
                                  _head_block(y, cq, start, wqb, k, v, wo, c),
                                  donate_argnums=(0,)),
            "dense_ffn": jax.jit(lambda u, lp: _dense_ffn(u, lp, c)),
            "moe_head": jax.jit(lambda u, lp: _moe_head(u, lp, c)),
            "expert": jax.jit(_expert, donate_argnums=(0,)),
            "logits": jax.jit(lambda X, g, w: _logits(X, g, w, c["rms_norm_eps"])),
        }
    return _PIECES[key]


def _attention(fn, us, starts, lp, cfg):
    """F of the first sublayer over a sequence in blocks us [(B, C)] that start at
    positions `starts`: [(B, C)]. Keys and values head by head over the whole
    sequence, queries block by block."""
    n = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    kv, vd = cfg["qk_nope_head_dim"] + cfg["v_head_dim"], cfg["v_head_dim"]
    sub = {k: lp[k] for k in _LATENTS}
    c_q, c, k_rope = zip(*(fn["latents"](u, start, sub) for u, start in zip(us, starts)))
    c, k_rope = jnp.concatenate(c), jnp.concatenate(k_rope)
    ys = [jnp.zeros_like(u) for u in us]
    for h in range(n):
        k, v = fn["head_keys"](c, k_rope, lp["wkvb"][:, h * kv:(h + 1) * kv])
        w_qb, w_o = lp["wqb"][:, h * qk:(h + 1) * qk], lp["wo"][h * vd:(h + 1) * vd]
        ys = [fn["head_block"](y, cq, start, w_qb, k, v, w_o)
              for y, cq, start in zip(ys, c_q, starts)]
    return ys


def _feed_forward(fn, u, lp):
    """F of the second sublayer on one block: (y, the picks' gap or None)."""
    if "router" not in lp:
        return fn["dense_ffn"](u, {k: lp[k] for k in ("norm2", "gate", "up", "down")}), None
    xh, dense, acc, gap = fn["moe_head"](u, {k: lp[k] for k in _MOE_HEAD})
    for e in range(lp["w_gate"].shape[0]):
        acc = fn["expert"](acc, xh, dense[:, e], lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e])
    return acc, gap


def sequence_logits(params, cfg, tokens, rows=None, gaps=False):
    """tokens (T,) -> logits (len(rows), V) float32 of one sequence at the
    positions `rows` (all of them when None, in order). `cfg` is the configuration
    file's dict (the published keys). The streams are held as blocks of BLOCK
    tokens (one block where T is no multiple of it); sublayer by sublayer, block by
    block, head by head, expert by expert, so that what is alive beside the streams
    is a block's worth. With `gaps`, also each of those positions' smallest
    `pick_gap` over the expert layers."""
    fn = _pieces(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    T = tokens.shape[0]
    size = BLOCK if T % BLOCK == 0 else T
    starts = list(range(0, T, size))
    with jax.default_matmul_precision("highest"):
        X = [jnp.repeat(jnp.asarray(params["wte"][tokens[s:s + size]], F32)[:, None, :],
                        cfg["hc_mult"], 1) for s in starts]
        least_gap = [jnp.full((size,), jnp.inf, F32) for _ in starts]
        for lp in params["layers"]:
            us, h_post, h_res = zip(*(fn["mix_in"](Xb, lp["hc_attn"]) for Xb in X))
            ys = _attention(fn, us, starts, lp, cfg)
            X = [fn["mix_out"](*parts) for parts in zip(X, h_post, h_res, ys)]
            del us, ys
            for b, Xb in enumerate(X):
                u, h_post, h_res = fn["mix_in"](Xb, lp["hc_ffn"])
                y, gap = _feed_forward(fn, u, lp)
                if gap is not None:
                    least_gap[b] = jnp.minimum(least_gap[b], gap)
                X[b] = fn["mix_out"](Xb, h_post, h_res, y)
        if rows is None:
            X, least_gap = jnp.concatenate(X), jnp.concatenate(least_gap)
        else:
            rows = np.asarray(rows)
            X = jnp.stack([X[r // size][r % size] for r in rows.tolist()])
            least_gap = jnp.concatenate(least_gap)[rows]
        logits = fn["logits"](X, params["norm_f"], params["head"])
        return (logits, least_gap) if gaps else logits
