"""command-a-plus-05-2026's language model as its published config.json describes it
(`model_type: cohere2_moe`, https://huggingface.co/CohereLabs/command-a-plus-05-2026).
Plain jax.numpy in float32 at the highest matmul precision: no cache, no kernel, no
batching, the masks built from `layer_types` and `sliding_window`, the experts in a
Python loop. It shares no code with paddle_tpu and imports nothing from it; only the
parameter tree's layout is the served one, so that the same weights can be given to
both (`x @ W`, W is (in, out)):

  {"wte": (V, h), "norm_f": (h,),
   "layers": [{"norm": (h,), "wq": (h, n*d), "wk", "wv": (h, n_kv*d), "wo": (n*d, h),
               "router": (h, E), "w_gate", "w_up": (held, h, F), "w_down": (held, F, h),
               "shared_gate", "shared_up": (h, S*F), "shared_down": (S*F, h)}]}

The layer [config: use_parallel_block]: u = LN(x); x' = x + Attn(u) + FFN(u). A line
marked [config] is settled by a key of the config; one marked [assumed] is not, and is
listed under `assumed` in benchmarks/configs/command-a-plus-05-2026.json.
  LN(x) = (x - mean) / sqrt(var + layer_norm_eps) * g [config: layer_norm_eps;
  rms_norm_eps is null; assumed: no bias, Cohere's LayerNorm].
  q = u W_q: `num_attention_heads` heads of `head_dim`; k = u W_k, v = u W_v:
  `num_key_value_heads` heads; query head i reads KV head i // (n / n_kv); no bias
  [config: attention_bias], no q/k norm [config: use_qk_norm].
    layer_types[l] == "sliding_attention": rotary on all `head_dim` values of q and k
    [config: rotary_pct 1], INTERLEAVED pairs (x0, x1), (x2, x3), ... [config:
    position_embedding_type rope_gptj], theta `rope_theta`; position i attends j with
    i - sliding_window < j <= i [config: the number; assumed: that it counts i itself];
    layer_types[l] == "full_attention": NO positions (q and k as projected), causal
    over everything [assumed from the family's rule and the catalog's "global NoPE"].
  Scores q . k / sqrt(head_dim), softmax, o = sum p v, Attn(u) = concat(o) W_o.
  FFN(u) = sum_i w_i E_i(u) + (1 / S) sum_j S_j(u) [config: num_shared_experts S,
  shared_expert_combination_strategy average; assumed: that the average is of the S
  outputs and is ADDED to the routed sum]. s = sigmoid(u W_r) over `num_experts`
  published experts in float32 [config: expert_selection_fn], the `num_experts_per_tok`
  largest, w_i = s_i / sum of the picked s [config: norm_topk_prob]; no correction bias,
  no scaling factor [config: no key]. An expert and a shared expert are SwiGLUs of width
  `intermediate_size`, silu [config: hidden_act, use_gated_activation; assumed: the
  width's reading].
  Final LN, logits = y W_e^T * logit_scale over the embedding [config:
  tie_word_embeddings, logit_scale].

THE HELD RANGE. `held = (first, count)`: the routed experts whose weights the tree
holds (ids first .. first + count - 1; `w_gate[j]` is expert first + j). The router
scores all the published experts; a pick outside the range adds nothing, its weight
still divides the sum. All of them ((0, E) with a tree of E experts) is the uncut
layer. The vocabulary is the tree's rows: a slice of the published one is a smaller
vocabulary.

Departures, none of which changes a value: each held expert is applied to EVERY token
and weighted by its routing weight, zero where it was not picked (the same sum); a
sequence is held in blocks of BLOCK tokens and computed a layer, a block, a head and an
expert at a time, their weights widened to float32 where they are used, so that 10k
rows fit beside the served weights on a chip.

WRONG references (`wrong=`), for showing that the cell's verdict tells them from the
served tokens; none is ever the reference of a run's `correct`:
  "float8": every matrix rounded to float8_e4m3 (the precision below bfloat16);
  "window_plus_block": the window one block of 128 wider (i - 4224 < j);
  "rotary_on_full": the full layers rotated like the sliding ones;
  "shared_summed": the shared experts summed, not averaged;
  "held_shifted": the held range one expert on (first + 1), the weights as they are."""

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
BLOCK = 2048
WRONG = ("float8", "window_plus_block", "rotary_on_full", "shared_summed", "held_shifted")


def layer_norm(x, g, eps):
    c = x - jnp.mean(x, -1, keepdims=True)
    return c * jax.lax.rsqrt(jnp.mean(c * c, -1, keepdims=True) + eps) * g


def _rope_interleaved(x, pos, theta):
    """x (T, heads, d) at integer positions pos (T,): the pair (x[2i], x[2i+1]) turned
    by pos * theta^(-2i/d)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(x.shape)


def _wide(w, float8):
    """A weight in float32, through float8_e4m3 first for the wrong reference."""
    w = jnp.asarray(w)
    if float8:
        w = w.astype(jnp.float8_e4m3fn)
    return w.astype(F32)


def _qkv(x, start, lp, c, rotate, float8):
    """One block's normed input u (B, h), queries (B, n, d), keys and values
    (B, n_kv, d)."""
    B, d = x.shape[0], c["head_dim"]
    u = layer_norm(x, jnp.asarray(lp["norm"], F32), c["layer_norm_eps"])
    q, k, v = ((u @ _wide(lp[name], float8)).reshape(B, -1, d) for name in ("wq", "wk", "wv"))
    if rotate:
        pos = start + jnp.arange(B)
        q, k = (_rope_interleaved(t, pos, c["rope_theta"]) for t in (q, k))
    return u, q, k, v


def _head_block(y, q, start, k, v, w_o, window, float8):
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
    return y + (probs @ v) @ _wide(w_o, float8)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def router(u, w_router, c):
    """u (T, h) float32, normed -> (picks (T, k), weights (T, k), dense (T, E) of the
    weights at their experts and zero elsewhere), over ALL the published experts."""
    scores = jax.nn.sigmoid(u @ w_router)
    weights, picks = jax.lax.top_k(scores, c["num_experts_per_tok"])
    if c["norm_topk_prob"]:
        weights = weights / weights.sum(-1, keepdims=True)
    dense = jnp.zeros_like(scores).at[jnp.arange(u.shape[0])[:, None], picks].set(weights)
    return picks, weights, dense


def pick_gap(u, w_router, c):
    """(T,): how far the last expert picked is ahead of the first one left out, in the
    router's LOGIT (the sigmoid keeps their order). The picks are discontinuous in it:
    a system that computes in a lower precision picks another expert where this is
    within its rounding, and its logits at that position are then another function's."""
    k = c["num_experts_per_tok"]
    best, _ = jax.lax.top_k(u @ w_router, k + 1)
    return best[:, k - 1] - best[:, k]


def _route(u, w_router, c, float8):
    w_router = _wide(w_router, float8)
    return router(u, w_router, c)[2], pick_gap(u, w_router, c)


def _expert(acc, u, scale, gate, up, down, float8):
    """acc + scale * SwiGLU(u): `scale` (T,) a routed expert's column of weights, or a
    scalar (a shared expert's 1 / S)."""
    y = _swiglu(u, _wide(gate, float8), _wide(up, float8), _wide(down, float8))
    return acc + (scale[:, None] if jnp.ndim(scale) else scale) * y


def ffn(u, lp, cfg, held=None, wrong=None):
    """FFN(u) of one layer in two parts, (routed (T, h), shared (T, h)): what the experts
    `held` = (first, count) add for the tokens that picked them, and the shared experts'
    average, which every chip of a deployment computes alike. Also the picks' smallest
    gap (T,). u (T, h) float32, normed."""
    fn = _pieces(cfg)
    float8 = wrong == "float8"
    first, count = _held(cfg, lp, held, wrong)
    dense, gap = fn["route"](u, lp["router"], float8)
    routed = jnp.zeros_like(u)
    for j in range(count):
        routed = fn["expert"](routed, u, dense[:, first + j], lp["w_gate"][j], lp["w_up"][j],
                              lp["w_down"][j], float8)
    S = cfg["num_shared_experts"]
    F = lp["shared_gate"].shape[1] // S
    shared = jnp.zeros_like(u)
    for j in range(S):
        at = slice(j * F, (j + 1) * F)
        shared = fn["expert"](shared, u, 1.0 if wrong == "shared_summed" else 1.0 / S,
                              lp["shared_gate"][:, at], lp["shared_up"][:, at],
                              lp["shared_down"][at], float8)
    return routed, shared, gap


def _held(cfg, lp, held, wrong):
    """(first, count) of the experts the tree holds: the argument, else the
    configuration file's (`experts_held_first`, default 0, and the tree's count)."""
    first, count = held if held is not None else \
        (cfg.get("experts_held_first", 0), lp["w_gate"].shape[0])
    if count != lp["w_gate"].shape[0]:
        raise ValueError(f"held {count} experts, the tree has {lp['w_gate'].shape[0]}")
    return (first + 1 if wrong == "held_shifted" else first), count


def _logits(x, norm_f, wte, eps, scale, float8):
    return layer_norm(x, jnp.asarray(norm_f, F32), eps) @ _wide(wte, float8).T * scale


def _static(cfg):
    """The config's numbers the jitted pieces close over, hashable."""
    keys = ("head_dim", "layer_norm_eps", "num_experts_per_tok", "norm_topk_prob",
            "rope_theta", "logit_scale")
    return tuple((k, cfg[k]) for k in keys)


_PIECES = {}


def _pieces(cfg):
    key = _static(cfg)
    if key not in _PIECES:
        c = dict(key)
        _PIECES[key] = {
            "qkv": jax.jit(lambda x, start, lp, rotate, float8: _qkv(x, start, lp, c, rotate,
                                                                     float8),
                           static_argnums=(3, 4)),
            "head_block": jax.jit(_head_block, static_argnums=(6, 7), donate_argnums=(0,)),
            "route": jax.jit(lambda u, w, float8: _route(u, w, c, float8), static_argnums=(2,)),
            "expert": jax.jit(_expert, static_argnums=(6,), donate_argnums=(0,)),
            "logits": jax.jit(lambda x, g, w, float8: _logits(
                x, g, w, c["layer_norm_eps"], c["logit_scale"], float8), static_argnums=(3,)),
        }
    return _PIECES[key]


_ATTN = ("norm", "wq", "wk", "wv")


def sequence_logits(params, cfg, tokens, rows=None, gaps=False, held=None, wrong=None):
    """tokens (T,) -> logits (len(rows), V) float32 of one sequence at the positions
    `rows` (all of them when None, in order). `cfg` is the configuration file's dict
    (the published keys). `held`: the module's docstring. The residual is held as blocks
    of BLOCK tokens (one block where T is no multiple of it). With `gaps`, also each of
    those positions' smallest `pick_gap` over the layers. `wrong`: None, or one of WRONG."""
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"wrong is None or one of {WRONG}, not {wrong!r}")
    fn = _pieces(cfg)
    float8 = wrong == "float8"
    tokens = jnp.asarray(tokens, jnp.int32)
    T = tokens.shape[0]
    size = BLOCK if T % BLOCK == 0 else T
    starts = list(range(0, T, size))
    d = cfg["head_dim"]
    group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    window = cfg["sliding_window"] + (128 if wrong == "window_plus_block" else 0)
    with jax.default_matmul_precision("highest"):
        X = [_wide(params["wte"][tokens[s:s + size]], float8) for s in starts]
        least_gap = [jnp.full((size,), jnp.inf, F32) for _ in starts]
        for lp, kind in zip(params["layers"], cfg["layer_types"]):
            sliding = kind == "sliding_attention"
            sub = {k: lp[k] for k in _ATTN}
            U, q, k, v = zip(*(fn["qkv"](x, s, sub, sliding or wrong == "rotary_on_full", float8)
                               for x, s in zip(X, starts)))
            k, v = jnp.concatenate(k), jnp.concatenate(v)
            for h in range(cfg["num_attention_heads"]):
                kh, vh = k[:, h // group], v[:, h // group]
                X = [fn["head_block"](x, qb[:, h], s, kh, vh, lp["wo"][h * d:(h + 1) * d],
                                      window if sliding else None, float8)
                     for x, qb, s in zip(X, q, starts)]
            del q, k, v
            for b, u in enumerate(U):
                routed, shared, gap = ffn(u, lp, cfg, held, wrong)
                least_gap[b] = jnp.minimum(least_gap[b], gap)
                X[b] = X[b] + routed + shared
            del U
        x, least_gap = jnp.concatenate(X), jnp.concatenate(least_gap)
        if rows is not None:
            x, least_gap = x[jnp.asarray(rows)], least_gap[jnp.asarray(rows)]
        logits = fn["logits"](x, params["norm_f"], params["wte"], float8)
        return (logits, least_gap) if gaps else logits
