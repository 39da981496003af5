"""LongCat-Flash-Omni's LANGUAGE MODEL as its published config.json describes it
(https://huggingface.co/meituan-longcat/LongCat-Flash-Omni/blob/main/config.json; the audio
and vision encoders and the codec decoder beside it are not here). Plain jax.numpy in
float32 at the highest matmul precision: no cache, no kernel, no batching, attention in the
EXPANDED form a head at a time, the experts in a Python loop. It shares no code with
paddle_tpu and imports nothing from it; only the parameter tree's layout is the served one,
so that the same weights can be given to both (`x @ W`, W is (in, out)):

  {"wte": (V, h), "head": (h, V), "norm_f": (h,),
   "layers": [{"attn": 2 x {"norm1": (h,), "wqa": (h, r), "q_norm": (r,),
                            "wqb": (r, n*(nope+rope)), "wkva": (h, rank+rope),
                            "kv_norm": (rank,), "wkvb": (rank, n*(nope+v)), "wo": (n*v, h)},
               "ffn": 2 x {"norm2": (h,), "gate", "up": (h, I), "down": (I, h)},
               "moe": {"router": (h, E+Z), "router_bias": (E+Z,),
                       "w_gate", "w_up": (held, h, F), "w_down": (held, F, h)}}]}

One layer l on the stream x (every norm an RMSNorm, eps `rms_norm_eps`, no bias) [a line
marked assumed is recalled from the published modelling code and is not a key of the
catalog's config: benchmarks/configs/longcat-flash-omni.json `assumed`]:
  a0 = x + MLA[l,0](norm1[l,0](x));  u0 = norm2[l,0](a0);  s = MoE[l](u0);
  b0 = a0 + FFN[l,0](u0);  a1 = b0 + MLA[l,1](norm1[l,1](b0));
  b1 = a1 + FFN[l,1](norm2[l,1](a1));  x' = b1 + s      [assumed: the shortcut's two ends]
MLA(u): q = RMSNorm_q(u W_qa) W_qb as n heads of [q_nope | q_rope], times sqrt(hidden /
  q_lora_rank), both parts, BEFORE rotation [config: mla_scale_q_lora; assumed: its place];
  [c_raw | k_rope_raw] = u W_kva; c = RMSNorm_kv(c_raw) * sqrt(hidden / kv_lora_rank) [config:
  mla_scale_kv_lora; assumed: its place]; k_rope is NOT scaled; q_rope and k_rope rotated,
  INTERLEAVED pairs (x0, x1), (x2, x3), ..., theta `rope_theta`, no scaling of positions
  [assumed: the pairing]; [k_nope | v] of each head = c W_kvb; scores (q_nope . k_nope +
  q_rope . k_rope) * (nope + rope)^-0.5 [assumed], causal softmax, o = sum p v, concat(o) W_o.
FFN(u) = W_down(silu(W_gate u) * W_up u), `ffn_hidden_size` wide.
MoE(u): p = softmax(u W_r) over `n_routed_experts` + `zero_expert_num` outputs, float32; the
  `moe_topk` largest of p + e_score_correction_bias are picked [assumed: the bias RANKS and
  does not weigh]; w_j = `routed_scaling_factor` * p_j, NOT renormalised [assumed];
  s = sum_{j picked, j < E} w_j E_j(u) + (sum_{j picked, j >= E} w_j) u [config:
  zero_expert_type identity]; E_j a SwiGLU `expert_ffn_hidden_size` wide. No shared expert.
Final RMSNorm, logits = y W_head [assumed: untied].

THE HELD RANGE. `held = (first, count)`: the routed experts whose weights the tree holds
(ids first .. first + count - 1; `w_gate[j]` is expert first + j). The router scores all
the published outputs; a real pick outside the range adds nothing here, an identity pick
adds its term on every chip alike. `published_experts` (the configuration file's
`published.n_routed_experts`) is E; all of them ((0, E) with a tree of E experts) is the
uncut layer. The vocabulary is the tree's rows.

Departures, none of which changes a value: each held expert is applied to EVERY token and
weighted by its routing weight, zero where it was not picked; a sequence is held in blocks
of BLOCK tokens and computed a layer, a block, a head, a slice of the dense width and an
expert at a time, their weights widened to float32 where they are used, so that 6k rows fit
beside the served weights on a chip.

WRONG programs (`wrong=`), for showing that the cell's verdict tells them from what was
served; none is ever the reference of a run's `correct`:
  "float8": every matrix rounded to float8_e4m3 (the precision below the stated bfloat16);
  "products_bf16": every product's RESULT, every partial sum of the expert layer and the
      identity term rounded to bfloat16 for real (`jax.lax.reduce_precision`: inside one
      compiled program the TPU's compiler drops an `astype` pair);
  "no_identity": the identity experts' term dropped;
  "bias_weighs": the weights taken from p + bias;
  "renormalised": the picks' weights divided by their sum;
  "shortcut_from_second": s = MoE(norm2[l,1](a1)), the expert layer on the second half;
  "shortcut_early": s added to b0, before the second attention;
  "no_mla_scale": neither q nor c scaled;
  "held_shifted": the held range one expert on (first + 1), the weights as they are."""

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
BLOCK = 1024
DENSE_SLICES = 4
WRONG = ("float8", "products_bf16", "no_identity", "bias_weighs", "renormalised",
         "shortcut_from_second", "shortcut_early", "no_mla_scale", "held_shifted")


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def as_bfloat16(x):
    """float32 x rounded to bfloat16's eight bits of significand, still float32."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _mm(a, b, low):
    """a @ b; its result rounded to bfloat16 for the wrong program `products_bf16`."""
    y = a @ b
    return as_bfloat16(y) if low else y


def _held_weight(w, float8):
    """A matrix as the pieces take it: itself, or (the wrong program `float8`) a REAL
    float8_e4m3 array, made here, outside every compiled piece, so that no compiler can
    drop the rounding."""
    w = jnp.asarray(w)
    return w.astype(jnp.float8_e4m3fn) if float8 and w.ndim >= 2 else w


def _f32(tree):
    return jax.tree_util.tree_map(lambda w: w.astype(F32), tree)


def _rope_interleaved(x, pos, theta):
    """x (T, ..., d) at integer positions pos (T,): the pair (x[2i], x[2i+1]) turned by
    pos * theta^(-2i/d)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32).reshape((-1,) + (1,) * (x.ndim - 1)) * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(x.shape)


def _latent(x, start, ap, c, scaled, low):
    """One block's rows x (B, h) at positions start..: the queries (B, n, nope + rope),
    scaled and rotated, and the block's cache rows: c (B, rank) normed and scaled, k_rope
    (B, rope) rotated."""
    ap = _f32(ap)
    B, n, nope, rank = x.shape[0], c["heads"], c["nope"], c["rank"]
    pos = start + jnp.arange(B)
    u = rms_norm(x, ap["norm1"], c["eps"])
    q = _mm(rms_norm(_mm(u, ap["wqa"], low), ap["q_norm"], c["eps"]), ap["wqb"], low)
    q = q.reshape(B, n, nope + c["rope"])
    if scaled and c["scale_q"]:
        q = q * math.sqrt(c["hidden"] / c["q_rank"])
    q = jnp.concatenate([q[..., :nope], _rope_interleaved(q[..., nope:], pos, c["theta"])], -1)
    kva = _mm(u, ap["wkva"], low)
    lat = rms_norm(kva[:, :rank], ap["kv_norm"], c["eps"])
    if scaled and c["scale_kv"]:
        lat = lat * math.sqrt(c["hidden"] / rank)
    return q, lat, _rope_interleaved(kva[:, rank:], pos, c["theta"])


def _head_block(y, q, start, lat, k_rope, w_kvb, w_o, c, low):
    """y (B, h) + one head of one block's queries q (B, nope + rope) against the whole
    sequence's cache rows (lat (T, rank), k_rope (T, rope)), its keys and values expanded
    through its columns of W_kvb (rank, nope + v), through its rows of W_o (v, h)."""
    nope = c["nope"]
    w_kvb, w_o = w_kvb.astype(F32), w_o.astype(F32)
    kv = _mm(lat, w_kvb, low)
    i = start + jnp.arange(q.shape[0])[:, None]
    j = jnp.arange(lat.shape[0])[None, :]
    scores = (q[:, :nope] @ kv[:, :nope].T + q[:, nope:] @ k_rope.T) \
        / math.sqrt(nope + c["rope"])
    probs = jax.nn.softmax(jnp.where(j <= i, scores, -jnp.inf), axis=-1)
    return y + _mm(_mm(probs, kv[:, nope:], low), w_o, low)


def _dense_slice(acc, u, gate, up, down, low):
    """acc + one slice of the dense width of SwiGLU(u)."""
    hidden = jax.nn.silu(_mm(u, gate.astype(F32), low)) * _mm(u, up.astype(F32), low)
    return acc + _mm(hidden, down.astype(F32), low)


def router(u, w_router, bias, c, wrong=None):
    """u (T, h) float32, normed -> (picks (T, k), weights (T, k), dense (T, E + Z) of the
    weights at their outputs and zero elsewhere, the picks' gap (T,): how far the last
    output picked is ahead of the first one left out, in the score the picks are ranked
    by, as a share of that score), over ALL the published outputs."""
    p = jax.nn.softmax(u @ w_router.astype(F32), -1)
    ranked = p + bias.astype(F32)
    best, picks = jax.lax.top_k(ranked, c["topk"] + 1)
    gap = (best[:, -2] - best[:, -1]) / best[:, -2]
    picks = picks[:, :-1]
    weights = jnp.take_along_axis(ranked if wrong == "bias_weighs" else p, picks, -1)
    if wrong == "renormalised":
        weights = weights / weights.sum(-1, keepdims=True)
    weights = weights * c["factor"]
    dense = jnp.zeros_like(p).at[jnp.arange(u.shape[0])[:, None], picks].set(weights)
    return picks, weights, dense, gap


def _route(u, w_router, bias, c, wrong):
    _, _, dense, gap = router(u, w_router, bias, c, wrong)
    E = c["experts"]
    real = jnp.sum(dense[:, :E] > 0, -1)
    identity = jnp.sum(dense[:, E:], -1)
    if wrong == "products_bf16":
        dense, identity = as_bfloat16(dense), as_bfloat16(identity)
    return dense, identity, gap, real


def _expert(acc, u, col, gate, up, down, low):
    """acc + col * SwiGLU(u): `col` (T,) a routed expert's column of weights."""
    hidden = jax.nn.silu(_mm(u, gate.astype(F32), low)) * _mm(u, up.astype(F32), low)
    y = acc + col[:, None] * _mm(hidden, down.astype(F32), low)
    return as_bfloat16(y) if low else y


def _identity_term(u, weight, low):
    y = weight[:, None] * u
    return as_bfloat16(y) if low else y


def _norm(x, g, eps):
    return rms_norm(x, g.astype(F32), eps)


def _logits(x, norm_f, head, eps, low):
    return _mm(rms_norm(x, norm_f.astype(F32), eps), head.astype(F32), low)


def _static(cfg):
    """What the mathematics reads of the configuration file, hashable."""
    return (("hidden", cfg["hidden_size"]), ("heads", cfg["num_attention_heads"]),
            ("q_rank", cfg["q_lora_rank"]), ("rank", cfg["kv_lora_rank"]),
            ("nope", cfg["qk_nope_head_dim"]), ("rope", cfg["qk_rope_head_dim"]),
            ("v", cfg["v_head_dim"]), ("eps", cfg["rms_norm_eps"]),
            ("theta", float(cfg["rope_theta"])), ("scale_q", bool(cfg["mla_scale_q_lora"])),
            ("scale_kv", bool(cfg["mla_scale_kv_lora"])), ("topk", cfg["moe_topk"]),
            ("factor", float(cfg["routed_scaling_factor"])),
            ("experts", cfg["published"]["n_routed_experts"]),
            ("zero", cfg["zero_expert_num"]))


_PIECES = {}


def _pieces(cfg):
    key = _static(cfg)
    if key not in _PIECES:
        c = dict(key)
        _PIECES[key] = {
            "latent": jax.jit(lambda x, start, ap, scaled, low: _latent(x, start, ap, c, scaled,
                                                                        low),
                              static_argnums=(3, 4)),
            "head_block": jax.jit(
                lambda y, q, start, lat, k_rope, w_kvb, w_o, low: _head_block(
                    y, q, start, lat, k_rope, w_kvb, w_o, c, low),
                static_argnums=(7,), donate_argnums=(0,)),
            "norm": jax.jit(lambda x, g: _norm(x, g, c["eps"])),
            "dense_slice": jax.jit(_dense_slice, static_argnums=(5,), donate_argnums=(0,)),
            "route": jax.jit(lambda u, w, b, wrong: _route(u, w, b, c, wrong),
                             static_argnums=(3,)),
            "expert": jax.jit(_expert, static_argnums=(6,), donate_argnums=(0,)),
            "identity": jax.jit(_identity_term, static_argnums=(2,)),
            "logits": jax.jit(lambda x, g, w, low: _logits(x, g, w, c["eps"], low),
                              static_argnums=(3,)),
        }
    return _PIECES[key]


def _held(cfg, mp, held, wrong):
    """(first, count) of the experts the tree holds: the argument, else the configuration
    file's (`experts_held_first`, default 0, and the tree's count)."""
    first, count = held if held is not None else \
        (cfg.get("experts_held_first", 0), mp["w_gate"].shape[0])
    if count != mp["w_gate"].shape[0]:
        raise ValueError(f"held {count} experts, the tree has {mp['w_gate'].shape[0]}")
    return (first + 1 if wrong == "held_shifted" else first), count


def shortcut(u, mp, cfg, held=None, wrong=None):
    """MoE(u) of one layer in its two parts, (routed (T, h), identity (T, h)): what the
    experts `held` = (first, count) add for the tokens that picked them, and the identity
    experts' term, which every chip of a deployment adds alike (zeros under the wrong
    program `no_identity`). Also the picks' gap (T,) and how many of each token's picks
    were real experts (T,). u (T, h) float32, normed; `mp` the layer's "moe" parameters."""
    fn = _pieces(cfg)
    float8, low = wrong == "float8", wrong == "products_bf16"
    first, count = _held(cfg, mp, held, wrong)
    dense, identity, gap, real = fn["route"](u, _held_weight(mp["router"], float8),
                                             mp["router_bias"], wrong)
    routed = jnp.zeros_like(u)
    for j in range(count):
        if first + j >= dense.shape[1]:
            break
        routed = fn["expert"](routed, u, dense[:, first + j],
                              *(_held_weight(mp[name][j], float8)
                                for name in ("w_gate", "w_up", "w_down")), low)
    term = jnp.zeros_like(u) if wrong == "no_identity" else fn["identity"](u, identity, low)
    return routed, term, gap, real


def _attention(X, starts, ap, cfg, wrong):
    """Every block of X plus its latent attention sublayer: a block's queries against the
    whole sequence's cache rows, head by head."""
    fn = _pieces(cfg)
    float8, low = wrong == "float8", wrong == "products_bf16"
    n, nope, v = cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    ap = {k: _held_weight(w, float8) for k, w in ap.items()}
    sub = {k: ap[k] for k in ("norm1", "wqa", "q_norm", "wqb", "wkva", "kv_norm")}
    q, lat, k_rope = zip(*(fn["latent"](x, s, sub, wrong != "no_mla_scale", low)
                           for x, s in zip(X, starts)))
    lat, k_rope = jnp.concatenate(lat), jnp.concatenate(k_rope)
    w_kvb = ap["wkvb"].reshape(ap["wkvb"].shape[0], n, nope + v)
    for h in range(n):
        X = [fn["head_block"](x, qb[:, h], s, lat, k_rope, w_kvb[:, h],
                              ap["wo"][h * v:(h + 1) * v], low)
             for x, qb, s in zip(X, q, starts)]
    return X


def _dense(a, u, fp, cfg, wrong):
    """a + FFN(u), the dense width a slice at a time."""
    fn = _pieces(cfg)
    float8, low = wrong == "float8", wrong == "products_bf16"
    I = fp["gate"].shape[1]
    n = DENSE_SLICES if I % DENSE_SLICES == 0 else 1
    for i in range(n):
        at = slice(i * I // n, (i + 1) * I // n)
        a = fn["dense_slice"](a, u, _held_weight(fp["gate"][:, at], float8),
                              _held_weight(fp["up"][:, at], float8),
                              _held_weight(fp["down"][at], float8), low)
    return a


def sequence_logits(params, cfg, tokens, rows=None, gaps=False, held=None, wrong=None,
                    tap=False):
    """tokens (T,) -> logits (len(rows), V) float32 of one sequence at the positions `rows`
    (all of them when None, in order). `cfg` is the configuration file's dict (the
    published keys). `held`: the module's docstring. The residual is held as blocks of
    BLOCK tokens (one block where T is no multiple of it). With `gaps`, also each of those
    positions' smallest pick gap over the layers. `wrong`: None, or one of WRONG. With
    `tap`, also {"u0": layer 0's normed input of the expert layer, "s": its output, "real":
    (layers, len(rows)) how many of each position's picks were real experts}, at `rows`."""
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"wrong is None or one of {WRONG}, not {wrong!r}")
    fn = _pieces(cfg)
    float8, low = wrong == "float8", wrong == "products_bf16"
    tokens = jnp.asarray(tokens, jnp.int32)
    T = tokens.shape[0]
    size = BLOCK if T % BLOCK == 0 else T
    starts = list(range(0, T, size))
    taps = {"real": []}
    with jax.default_matmul_precision("highest"):
        wte = _held_weight(params["wte"], float8)
        X = [wte[tokens[s:s + size]].astype(F32) for s in starts]
        least_gap = [jnp.full((size,), jnp.inf, F32) for _ in starts]
        for li, lp in enumerate(params["layers"]):
            A0 = _attention(X, starts, lp["attn"][0], cfg, wrong)
            del X
            U0 = [fn["norm"](a, lp["ffn"][0]["norm2"]) for a in A0]
            B0 = [_dense(a, u, lp["ffn"][0], cfg, wrong) for a, u in zip(A0, U0)]
            del A0
            if wrong != "shortcut_from_second":
                S = [shortcut(u, lp["moe"], cfg, held, wrong) for u in U0]
            if wrong == "shortcut_early":
                B0 = [b + routed + term for b, (routed, term, _, _) in zip(B0, S)]
            A1 = _attention(B0, starts, lp["attn"][1], cfg, wrong)
            del B0
            U1 = [fn["norm"](a, lp["ffn"][1]["norm2"]) for a in A1]
            if wrong == "shortcut_from_second":
                S = [shortcut(u, lp["moe"], cfg, held, wrong) for u in U1]
            X = [_dense(a, u, lp["ffn"][1], cfg, wrong) for a, u in zip(A1, U1)]
            del A1, U1
            if wrong != "shortcut_early":
                X = [x + routed + term for x, (routed, term, _, _) in zip(X, S)]
            least_gap = [jnp.minimum(g, gap) for g, (_, _, gap, _) in zip(least_gap, S)]
            if tap:
                taps["real"].append(jnp.concatenate([real for _, _, _, real in S]))
                if li == 0:
                    taps["u0"] = jnp.concatenate(U0)
                    taps["s"] = jnp.concatenate([routed + term for routed, term, _, _ in S])
            del U0, S
        x, least_gap = jnp.concatenate(X), jnp.concatenate(least_gap)
        if tap:
            taps["real"] = jnp.stack(taps["real"])
        if rows is not None:
            at = jnp.asarray(rows)
            x, least_gap = x[at], least_gap[at]
            if tap:
                taps = {"u0": taps["u0"][at], "s": taps["s"][at], "real": taps["real"][:, at]}
        logits = fn["logits"](x, params["norm_f"], _held_weight(params["head"], float8), low)
        out = (logits, least_gap) if gaps else (logits,)
        out = out + (taps,) if tap else out
        return out if len(out) > 1 else out[0]
