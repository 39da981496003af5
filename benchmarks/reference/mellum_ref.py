"""Mellum2-12B-A2.5B-Instruct as its published config.json describes it
(`model_type: mellum`, https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct).
Plain jax.numpy in float32 at the highest matmul precision: no cache, no kernel, no
batching, the masks built from `layer_types` and `sliding_window`, the experts in a
Python loop. It shares no code with paddle_tpu and imports nothing from it; only the
parameter tree's layout is the served one, so that the same weights can be given to
both (`x @ W`, W is (in, out)):

  {"wte": (V, h), "head": (h, V), "norm_f": (h,),
   "layers": [{"norm1", "norm2": (h,), "wq": (h, n*d), "wk", "wv": (h, n_kv*d),
               "wo": (n*d, h), "router": (h, E), "w_gate", "w_up": (E, h, F),
               "w_down": (E, F, h)}]}

The layer, with x^ = RMSNorm(x) (eps `rms_norm_eps`, no bias anywhere). A line marked
[config] is settled by a key of the config; one marked [assumed] is not, and is
listed under `assumed` in benchmarks/configs/mellum2-12b-a2.5b.json.
  q = x^ W_q: `num_attention_heads` heads of `head_dim`; k = x^ W_k, v = x^ W_v:
  `num_key_value_heads` heads; query head i reads KV head i // (n / n_kv) [config].
  Rotary on all `head_dim` values of q and k, rotate_half with pairs in halves
  [assumed: the Hugging Face layout], theta `rope_parameters[kind].rope_theta`:
    layer_types[l] == "sliding_attention": plain frequencies [config: rope_type
    default]; position i attends j with i - sliding_window < j <= i [config: the
    number; assumed: that the window counts position i itself];
    layer_types[l] == "full_attention": YaRN [config: factor, original positions,
    beta_fast, beta_slow, attention_factor; assumed: the blend as transformers
    computes it]: extra_i = theta^(-2i/d), inter_i = extra_i / factor,
    ramp_i = clip((i - low) / (high - low), 0, 1), inv_freq_i = inter_i ramp_i +
    extra_i (1 - ramp_i), cos and sin times attention_factor; causal over everything.
  Scores q . k / sqrt(head_dim), softmax, o = sum p v, x = x + concat(o) W_o.
  No per-head q/k norm [assumed: no key declares one].
  Every layer is sparse [config: mlp_layer_types; intermediate_size is used by no
  layer]: p = softmax(x^ W_r) over `num_experts` in float32, the
  `num_experts_per_tok` largest, their probabilities over their sum [config:
  norm_topk_prob]; x = x + sum_k w_k Expert_k(x^), an expert a SwiGLU of width
  `moe_intermediate_size`, silu. No shared expert, bias or factor [config: no key].
  Final RMSNorm, logits = y W_head (untied) [config]. No MTP head [assumed].

Departures, none of which changes a value: each expert is applied to EVERY token and
weighted by its routing weight, zero where it was not picked (the same sum); a
sequence is held in blocks of BLOCK tokens and computed a layer, a block, a head and
an expert at a time, their weights widened to float32 where they are used (a KV
head's keys and values are written out over the whole sequence, once a head), so
that 16k rows fit beside the served weights on a chip."""

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
BLOCK = 2048


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rotary_frequencies(d, rp):
    """(inv_freq (d/2,), the factor on cos and sin) of one kind of layer's
    `rope_parameters` entry."""
    theta = rp["rope_theta"]
    extra = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    if rp["rope_type"] == "default":
        return extra, 1.0
    if rp["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rp['rope_type']!r}")
    factor, original = rp["factor"], rp["original_max_position_embeddings"]

    def correction_dim(rotations):
        return d * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(rp["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rp["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=F32) - low) / (high - low), 0.0, 1.0)
    return (extra / factor) * ramp + extra * (1.0 - ramp), rp["attention_factor"]


def _rope(x, pos, rp):
    """x (T, heads, d) at integer positions pos (T,)."""
    d = x.shape[-1]
    inv_freq, factor = rotary_frequencies(d, rp)
    freqs = pos.astype(F32)[:, None, None] * inv_freq
    emb = jnp.concatenate([freqs, freqs], -1)
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * (jnp.cos(emb) * factor) + rotated * (jnp.sin(emb) * factor)


def _qkv(x, start, lp, c, kind):
    """One block's rotated queries (B, n, d) and keys, and values (B, n_kv, d)."""
    lp = {k: jnp.asarray(v, F32) for k, v in lp.items()}
    B, d = x.shape[0], c["head_dim"]
    rp = dict(c["rope_parameters"])[kind]
    pos = start + jnp.arange(B)
    xh = _rms_norm(x, lp["norm1"], c["rms_norm_eps"])
    q = _rope((xh @ lp["wq"]).reshape(B, -1, d), pos, dict(rp))
    k = _rope((xh @ lp["wk"]).reshape(B, -1, d), pos, dict(rp))
    return q, k, (xh @ lp["wv"]).reshape(B, -1, d)


def _head_block(y, q, start, k, v, w_o, window):
    """y (B, h) + one query head of one block against its KV head's keys and values
    over the whole sequence (T, d), through its rows of W_o. `window` is None (causal
    over everything) or the number of positions attended, position i itself counted."""
    i = start + jnp.arange(q.shape[0])[:, None]
    j = jnp.arange(k.shape[0])[None, :]
    mask = j <= i
    if window is not None:
        mask = mask & (i - j < window)
    scores = (q @ k.T) / math.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return y + (probs @ v) @ jnp.asarray(w_o, F32)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def router(xh, w_router, c):
    """xh (T, h) float32, normed -> (picks (T, k), weights (T, k), dense (T, E) of the
    weights at their experts and zero elsewhere)."""
    probs = jax.nn.softmax(xh @ jnp.asarray(w_router, F32), axis=-1)
    weights, picks = jax.lax.top_k(probs, c["num_experts_per_tok"])
    if c["norm_topk_prob"]:
        weights = weights / weights.sum(-1, keepdims=True)
    dense = jnp.zeros_like(probs).at[jnp.arange(xh.shape[0])[:, None], picks].set(weights)
    return picks, weights, dense


def pick_gap(xh, w_router, c):
    """(T,): how far the last expert picked is ahead of the first one left out, in the
    router's LOGIT (the softmax keeps their order). The picks are discontinuous in it:
    a system that computes in a lower precision picks another expert where this is
    within its rounding, and its logits at that position are then another function's."""
    k = c["num_experts_per_tok"]
    best, _ = jax.lax.top_k(xh @ jnp.asarray(w_router, F32), k + 1)
    return best[:, k - 1] - best[:, k]


def _moe_head(x, lp, c):
    lp = {k: jnp.asarray(v, F32) for k, v in lp.items()}
    xh = _rms_norm(x, lp["norm2"], c["rms_norm_eps"])
    _, _, dense = router(xh, lp["router"], c)
    return xh, dense, jnp.zeros_like(x), pick_gap(xh, lp["router"], c)


def _expert(acc, xh, w_col, gate, up, down):
    return acc + w_col[:, None] * _swiglu(xh, jnp.asarray(gate, F32), jnp.asarray(up, F32),
                                          jnp.asarray(down, F32))


def _logits(x, norm_f, head, eps):
    return _rms_norm(x, jnp.asarray(norm_f, F32), eps) @ jnp.asarray(head, F32)


_ATTN = ("norm1", "wq", "wk", "wv")


def _static(cfg):
    """The config's numbers the jitted pieces close over, hashable."""
    keys = ("head_dim", "rms_norm_eps", "num_experts_per_tok", "norm_topk_prob")
    rope = tuple((kind, tuple(sorted(rp.items())))
                 for kind, rp in sorted(cfg["rope_parameters"].items()))
    return tuple((k, cfg[k]) for k in keys) + (("rope_parameters", rope),)


_PIECES = {}


def _pieces(cfg):
    key = _static(cfg)
    if key not in _PIECES:
        c = dict(key)
        _PIECES[key] = {
            "qkv": jax.jit(lambda x, start, lp, kind: _qkv(x, start, lp, c, kind),
                           static_argnums=(3,)),
            "head_block": jax.jit(_head_block, static_argnums=(6,), donate_argnums=(0,)),
            "moe_head": jax.jit(lambda x, lp: _moe_head(x, lp, c)),
            "expert": jax.jit(_expert, donate_argnums=(0,)),
            "logits": jax.jit(lambda x, g, w: _logits(x, g, w, c["rms_norm_eps"])),
        }
    return _PIECES[key]


def sequence_logits(params, cfg, tokens, rows=None, gaps=False):
    """tokens (T,) -> logits (len(rows), V) float32 of one sequence at the positions
    `rows` (all of them when None, in order). `cfg` is the configuration file's dict
    (the published keys). The residual is held as blocks of BLOCK tokens (one block
    where T is no multiple of it). With `gaps`, also each of those positions' smallest
    `pick_gap` over the layers."""
    fn = _pieces(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    T = tokens.shape[0]
    size = BLOCK if T % BLOCK == 0 else T
    starts = list(range(0, T, size))
    d = cfg["head_dim"]
    group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    with jax.default_matmul_precision("highest"):
        X = [jnp.asarray(params["wte"][tokens[s:s + size]], F32) for s in starts]
        least_gap = [jnp.full((size,), jnp.inf, F32) for _ in starts]
        for lp, kind in zip(params["layers"], cfg["layer_types"]):
            window = cfg["sliding_window"] if kind == "sliding_attention" else None
            sub = {k: lp[k] for k in _ATTN}
            q, k, v = zip(*(fn["qkv"](x, s, sub, kind) for x, s in zip(X, starts)))
            k, v = jnp.concatenate(k), jnp.concatenate(v)
            for h in range(cfg["num_attention_heads"]):
                kh, vh = k[:, h // group], v[:, h // group]
                X = [fn["head_block"](x, qb[:, h], s, kh, vh, lp["wo"][h * d:(h + 1) * d], window)
                     for x, qb, s in zip(X, q, starts)]
            del q, k, v
            for b, x in enumerate(X):
                xh, dense, acc, gap = fn["moe_head"](x, {k: lp[k] for k in ("norm2", "router")})
                least_gap[b] = jnp.minimum(least_gap[b], gap)
                for e in range(lp["w_gate"].shape[0]):
                    acc = fn["expert"](acc, xh, dense[:, e], lp["w_gate"][e], lp["w_up"][e],
                                       lp["w_down"][e])
                X[b] = x + acc
        x, least_gap = jnp.concatenate(X), jnp.concatenate(least_gap)
        if rows is not None:
            x, least_gap = x[jnp.asarray(rows)], least_gap[jnp.asarray(rows)]
        logits = fn["logits"](x, params["norm_f"], params["head"])
        return (logits, least_gap) if gaps else logits
