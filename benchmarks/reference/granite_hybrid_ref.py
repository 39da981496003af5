"""granite-4.0-h-small's language model as its published config.json describes it
(`model_type: granitemoehybrid`, https://huggingface.co/ibm-granite/granite-4.0-h-small) and,
for the state-space mixer, as the Mamba-2 paper (arXiv:2405.21060, the SSD layer with one
group of B and C) and the open `Mamba2` / `GraniteMoeHybridMambaLayer` layers compute it,
recalled: no network here, the catalog's copy of config.json is the only text read. Plain
jax.numpy in float32 at the highest matmul precision: no cache, no kernel, no chunks, no
batching; the Mamba-2 mixer TOKEN BY TOKEN (a `lax.scan` over positions of the recurrence
as written below; the chunked dual form is the program's, never the reference's), the
attention a head at a time, the experts in a Python loop. It shares no code with
paddle_tpu and imports nothing from it; only the parameter tree's layout is the served one
(`x @ W`, W is (in, out)), so that the same weights can be given to both.

x0 = E[ids] * embedding_multiplier. Layer l (0-indexed, its kind layer_types[l]), with rm =
residual_multiplier and rms(x, w) = x / sqrt(mean(x^2) + rms_norm_eps) * w:
    x = x + rm * mixer_l(rms(x, norm1));  u = rms(x, norm2);  x = x + rm * (moe(u) + shared(u))
logits = rms(x, norm_f) E^T / logits_scaling [config: tie_word_embeddings true]. A line
marked [config] is settled by a key of the config; one marked [assumed] is not, and is listed
under `assumed` in benchmarks/configs/granite-4.0-h-small.json.

Mamba-2 mixer, layer_types[l] = "mamba" [config: mamba_n_heads H, mamba_d_head P,
mamba_d_state N, mamba_n_groups 1, mamba_d_conv K, mamba_expand, mamba_conv_bias true,
mamba_proj_bias false]. [z (HP) | xBC (HP + 2N) | dt (H)] = u W_in [assumed: the order]. A
causal depthwise convolution of width K with a bias and SiLU over xBC, a filter a channel,
zeros before position 0: c_t = SiLU(sum_{i<K} w_i xBC_{t-K+1+i} + b). [x (H, P) | B (N) |
C (N)] = c_t [assumed: the order]; ONE B and C for all heads. dt_t = softplus(dt_t +
dt_bias) (H,) [assumed: no clamp, time_step_limit]; A = -exp(A_log) (H,), a SCALAR a head.
The state S (H, P, N) float32, zero before position 0:
    S_t = exp(dt_t A)[:, None, None] * S_{t-1} + outer(dt_t x_t, B_t);   y_t = S_t C_t + D x_t.
The gated norm: g_t = y_t * SiLU(z_t) FIRST, then rms over all HP values with a learned
weight [assumed: gate before norm; one norm group]; mixer = g_t W_out. No positions
anywhere.

Attention, layer_types[l] = "attention" [config: num_attention_heads, num_key_value_heads,
attention_bias false, position_embedding_type "nope": NOTHING is rotated,
attention_multiplier]: q, k, v = u W_q, u W_k, u W_v, head_dim = hidden / heads, query head i
reads KV head i // group, scores q k^T * attention_multiplier (NOT head_dim^-0.5), causal
softmax, o W_o.

Experts [config: num_local_experts, num_experts_per_tok, intermediate_size,
shared_intermediate_size]: logits u W_r over the PUBLISHED experts, the k largest, weights =
softmax over those k; expert e: SwiGLU of intermediate_size [assumed: the half order of its
fused first product; stored here as gate and up]; plus one shared SwiGLU of
shared_intermediate_size. No correction bias, no factor, no groups.

THE HELD RANGE, as command_a_ref's: `held = (first, count)`, the routed experts whose
weights the tree holds; a pick outside adds nothing, its weight still divides the sum (it is
inside the softmax over the k picks). The vocabulary is the tree's rows.

Departures, none of which changes a value: each held expert is applied to EVERY token and
weighted by its routing weight, zero where it was not picked; W_in is used in its three
column ranges and the attention a head at a time, weights widened to float32 where they are
used, so that 2,560 rows fit beside the served weights and the pools on a chip.

THE LOGITS ARE THE PUBLISHED ONES, after `/ logits_scaling` (16): their standard deviation
under seeded weights is 0.08 where the other cells' is about 1, so every margin the cell's
verdict sets on them is a sixteenth of what it would be before the division.

WRONG programs (`wrong=`), for showing that the cell's verdict tells them from the served
tokens; none is ever the reference of a run's `correct`. `prompt_len` and `bucket` say where
the served request's prefill ended and how long its padded bucket was:
  "float8": every matrix rounded to float8_e4m3 (the precision below bfloat16);
  "state_bf16": the recurrent state rounded to bfloat16 after every position;
  "scan_bf16": the recurrence's operands x, dt, B, C of the PROMPT's rows rounded to
      bfloat16 (a prompt's scan whose products run below float32);
  "no_decay": a = 1 (the state never forgets);
  "no_dt_bias": dt = softplus(dt) without dt_bias;
  "conv_reset": the convolution's history not carried from the prefill (zeros before the
      first generated position);
  "bucket_end": the state taken at the bucket's end and not at the prompt's: the prompt's
      last position repeated (bucket - prompt_len) times into the state before the first
      generated position;
  "attn_scale": the attention's scores times head_dim^-0.5 and not attention_multiplier;
  "rotary": the attention layer WITH rotation (theta rope_theta, pairs in halves);
  "residual_one": residual_multiplier 1.

THE SAMPLER'S DRAWS (`gumbel_draws`), for judging SAMPLED requests: the engine draws a token
as argmax(logits / T + g), g standard Gumbel noise that is a pure function of the request's
seed, the position and the lane. The CONTRACT is the counters (the program's
serving/sampling.py documents them; nothing is imported from it): key_0 = (0, seed); a
position's key is threefry2x32(key, counter (1, 0)) of the position before; lane i of a
position draws bits = the first word of threefry2x32(key, counter (0, i)), u = (bits >> 8 +
1/2) / 2^24 IN FLOAT32, g = -log(-log(u)). The hash is written here from Random123's
definition (threefry2x32, 20 rounds) and held against its published test vectors. The
float32 is part of the contract, because it decides WHICH token the engine draws: above 2^23
`bits >> 8 + 1/2` is not a float32 and rounds to an even count, so the upper half of the
lattice moves in steps of 2^-24 and the noise of the likeliest winners (g above 11) lies
0.003 to 0.3 off the exact lattice's (found on the chip, PR 54's review round: with log(u)
taken exactly, as log1p(u - 1), one or two served positions in 3,072 read 0.004 to 0.15
under another lane). ONE value is not mirrored: the top of the lattice, 1 - 2^-25, rounds to
1 and the engine's g is +inf there; here it is the FINITE 25 log 2 = 17.33 that the exact
value has, which still wins its position, and `gumbel_draws` counts those lanes."""

import jax
import jax.numpy as jnp

F32 = jnp.float32
WRONG = ("float8", "state_bf16", "no_decay", "no_dt_bias", "conv_reset", "bucket_end",
         "attn_scale", "rotary", "residual_one", "scan_bf16")


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _wide(w, float8):
    """A weight in float32, through float8_e4m3 first for the wrong program."""
    w = jnp.asarray(w)
    if float8 and w.ndim >= 2:
        w = w.astype(jnp.float8_e4m3fn)
    return w.astype(F32)


def as_bfloat16(x):
    """float32 x rounded to bfloat16's eight bits of significand, still float32: by
    `reduce_precision`, because inside one compiled program the TPU's compiler DROPS a
    pair of converts float32 -> bfloat16 -> float32 (it allows excess precision), and the
    wrong program would be the right one."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


# -- the Mamba-2 mixer, token by token ------------------------------------------------

def ssd_step(S, x, dt, A, B, C):
    """One position of the recurrence: S (H, P, N), x (H, P), dt (H,), A (H,), B, C (N,).
    Returns (S_t, S_t C_t (H, P)): the skip term D x is added by the caller."""
    S = jnp.exp(dt * A)[:, None, None] * S + (dt[:, None] * x)[:, :, None] * B[None, None, :]
    return S, jnp.sum(S * C[None, None, :], -1)


def ssd_inputs(u, lp, c, float8=False, wrong=None, prompt_len=None):
    """The recurrence's inputs of normed rows u (T, h): x (T, H, P), dt (T, H), B, C (T, N),
    the gate's z (T, HP), and the convolution's pre-activation rows x|B|C (T, HP + 2N)."""
    T = u.shape[0]
    H, P, N, K = c["mamba_heads"], c["mamba_head_dim"], c["mamba_state"], c["conv_kernel"]
    inner = H * P
    w_in = lp["w_in"]
    z = u @ _wide(w_in[:, :inner], float8)
    xbc = u @ _wide(w_in[:, inner:2 * inner + 2 * N], float8)
    dt = u @ _wide(w_in[:, 2 * inner + 2 * N:], float8)
    w, b = jnp.asarray(lp["conv_w"], F32), jnp.asarray(lp["conv_b"], F32)
    padded = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    conv = sum(w[i] * padded[i:i + T] for i in range(K))
    if wrong == "conv_reset" and prompt_len is not None and prompt_len < T:
        # the first generated positions see zeros where the prompt's last rows were
        cut = jnp.where((jnp.arange(T + K - 1) < prompt_len + K - 1)[:, None], 0.0, padded)
        reset = sum(w[i] * cut[i:i + T] for i in range(K))
        conv = jnp.where((jnp.arange(T) >= prompt_len)[:, None], reset, conv)
    act = jax.nn.silu(conv + b)
    x = act[:, :inner].reshape(T, H, P)
    if wrong != "no_dt_bias":
        dt = dt + jnp.asarray(lp["dt_bias"], F32)
    return x, jax.nn.softplus(dt), act[:, inner:inner + N], act[:, inner + N:], z, xbc


def ssd_recurrence(x, dt, A, B, C, state_bf16=False, repeat_at=None, repeats=0):
    """(S C (T, H, P), the state after the last position) of the recurrence from a zero
    state, one position at a time. The wrong programs: `state_bf16` rounds the state after
    every position; `repeat_at`, `repeats`: position `repeat_at`'s update applied `repeats`
    times more (traced) before the next."""
    H, P = x.shape[1:]

    def step(S, row):
        S, y = ssd_step(S, row[0], row[1], A, row[2], row[3])
        if repeat_at is not None:
            again = jnp.where(row[4] == repeat_at, repeats, 0)
            S = jax.lax.fori_loop(
                0, again, lambda _, S: ssd_step(S, row[0], row[1], A, row[2], row[3])[0], S)
        if state_bf16:
            S = as_bfloat16(S)
        return S, y

    at = jnp.arange(x.shape[0])
    S, y = jax.lax.scan(step, jnp.zeros((H, P, B.shape[-1]), F32), (x, dt, B, C, at))
    return y, S


def _mamba_mixer(x, lp, c, rm, float8, wrong, prompt_len, bucket):
    """(x (T, h) + rm * the Mamba-2 mixer of RMSNorm(x), the state after row T - 1, the
    convolution's history there: the last K - 1 pre-activation rows, zeros before row 0)."""
    u = rms_norm(x, jnp.asarray(lp["norm1"], F32), c["rms_norm_eps"])
    xs, dt, B, C, z, xbc = ssd_inputs(u, lp, c, float8, wrong, prompt_len)
    A = -jnp.exp(jnp.asarray(lp["a_log"], F32))
    if wrong == "no_decay":
        A = jnp.zeros_like(A)
    past = wrong == "bucket_end" and prompt_len is not None
    if wrong == "scan_bf16":
        rows = (jnp.arange(x.shape[0]) < (x.shape[0] if prompt_len is None else prompt_len))
        xs, dt, B, C = (jnp.where(rows.reshape((-1,) + (1,) * (a.ndim - 1)), as_bfloat16(a), a)
                        for a in (xs, dt, B, C))
    y, S = ssd_recurrence(xs, dt, A, B, C, wrong == "state_bf16",
                          prompt_len - 1 if past else None,
                          max(bucket - prompt_len, 0) if past else 0)
    y = (y + jnp.asarray(lp["d"], F32)[:, None] * xs).reshape(x.shape[0], -1) * jax.nn.silu(z)
    y = rms_norm(y, jnp.asarray(lp["gate_norm"], F32), c["rms_norm_eps"])
    K = c["conv_kernel"]
    return x + rm * (y @ _wide(lp["w_out"], float8)), S, jnp.pad(xbc, ((K - 1, 0), (0, 0)))[-(K - 1):]


# -- the sampler's draws -------------------------------------------------------------------

def threefry2x32(k0, k1, x0, x1):
    """Random123's threefry2x32 with 20 rounds: key words k0, k1 and counter words x0, x1,
    uint32 arrays that broadcast; returns the two output words."""
    u32 = jnp.uint32
    k0, k1, x0, x1 = (jnp.asarray(a, u32) for a in (k0, k1, x0, x1))
    ks = (k0, k1, k0 ^ k1 ^ u32(0x1BD11BDA))
    x0, x1 = x0 + ks[0], x1 + ks[1]
    rotations = ((13, 15, 26, 6), (17, 29, 16, 24))
    for group in range(5):
        for r in rotations[group % 2]:
            x0 = x0 + x1
            x1 = ((x1 << u32(r)) | (x1 >> u32(32 - r))) ^ x0
        x0 = x0 + ks[(group + 1) % 3]
        x1 = x1 + ks[(group + 2) % 3] + u32(group + 1)
    return x0, x1


def gumbel_of_bits(bits):
    """Standard Gumbel noise of uint32 `bits`: u = (bits >> 8 + 1/2) / 2^24 in float32 on
    the centred lattice of 2^24 values, g = -log(-log(u)); where u rounds to 1 (the top
    value alone) the finite 25 log 2 of the exact u = 1 - 2^-25."""
    u = ((jnp.asarray(bits, jnp.uint32) >> jnp.uint32(8)).astype(F32) + F32(0.5)) \
        * F32(2.0 ** -24)
    return jnp.where(u < 1.0, -jnp.log(-jnp.log(u)), F32(25.0) * jnp.log(F32(2.0)))


@jax.jit
def _draws(seed, like):
    lanes = jnp.arange(like.shape[1], dtype=jnp.uint32)

    def position(key, _):
        bits, _ = threefry2x32(key[0], key[1], jnp.uint32(0), lanes)
        return jnp.stack(threefry2x32(key[0], key[1], jnp.uint32(1), jnp.uint32(0))), (
            gumbel_of_bits(bits), jnp.sum((bits >> jnp.uint32(8)) == jnp.uint32(0xFFFFFF)))

    key = jnp.stack([jnp.uint32(0), seed.astype(jnp.uint32)])
    return jax.lax.scan(position, key, None, length=like.shape[0])[1]


def gumbel_draws(seed, like):
    """The noise of a request's first `like.shape[0]` generated positions over
    `like.shape[1]` lanes, (positions, lanes) float32, by the module docstring's counters;
    and how many of those lanes sit on the top of the lattice, where a sampler that takes
    log(u) in float32 reads +inf and picks the lane whatever the logits."""
    noise, top = _draws(jnp.asarray(seed), like)
    return noise, int(jnp.sum(top))


# -- the attention, a head at a time ---------------------------------------------------

def _rope_halves(x, pos, theta):
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attn_qkv(x, lp, c, float8, rotate):
    """Queries (T, heads, d), keys and values (T, kv_heads, d) of x (T, h)."""
    T = x.shape[0]
    n, kv = c["heads"], c["kv_heads"]
    d = c["hidden"] // n
    u = rms_norm(x, jnp.asarray(lp["norm1"], F32), c["rms_norm_eps"])
    q = (u @ _wide(lp["wq"], float8)).reshape(T, n, d)
    k = (u @ _wide(lp["wk"], float8)).reshape(T, kv, d)
    v = (u @ _wide(lp["wv"], float8)).reshape(T, kv, d)
    if rotate:
        pos = jnp.arange(T)
        q, k = _rope_halves(q, pos, c["rope_theta"]), _rope_halves(k, pos, c["rope_theta"])
    return q, k, v


def _head(y, q, k, v, w_o, scale, rm):
    """y (T, h) + rm * one head's causal attention through its rows of W_o."""
    T = q.shape[0]
    mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    probs = jax.nn.softmax(jnp.where(mask, (q @ k.T) * scale, -jnp.inf), axis=-1)
    return y + rm * ((probs @ v) @ w_o)


# -- the feed-forward -------------------------------------------------------------------

def router(u, w_router, c):
    """u (T, hidden) float32, normed -> dense (T, E): the weights at their experts, zero
    elsewhere, over ALL the published experts: the k largest logits, a softmax over them."""
    logits = u @ w_router
    best, picks = jax.lax.top_k(logits, c["experts_per_tok"])
    weights = jax.nn.softmax(best, -1)
    return jnp.zeros_like(logits).at[jnp.arange(u.shape[0])[:, None], picks].set(weights)


def pick_gap(u, w_router, c):
    """(T,): how far the last expert picked is ahead of the first one left out, in the
    router's logits."""
    k = c["experts_per_tok"]
    best, _ = jax.lax.top_k(u @ w_router, k + 1)
    return best[:, k - 1] - best[:, k]


def _route(x, norm2, w_router, c, float8):
    u = rms_norm(x, jnp.asarray(norm2, F32), c["rms_norm_eps"])
    w_router = _wide(w_router, float8)
    return u, router(u, w_router, c), pick_gap(u, w_router, c)


def _expert(acc, u, scale, gate, up, down, float8):
    y = _swiglu(u, _wide(gate, float8), _wide(up, float8), _wide(down, float8))
    return acc + (scale[:, None] if jnp.ndim(scale) else scale) * y


def ffn(x, lp, cfg, held=None, wrong=None):
    """The feed-forward of one layer on rows x (T, h) (its norm first) in two parts, BEFORE
    the residual multiplier: (routed (T, h), shared (T, h)), and the picks' gap (T,)."""
    fn = _pieces(cfg)
    float8 = wrong == "float8"
    first, count = held if held is not None else \
        (cfg.get("experts_held_first", 0), lp["w_gate"].shape[0])
    if count != lp["w_gate"].shape[0]:
        raise ValueError(f"held {count} experts, the tree has {lp['w_gate'].shape[0]}")
    u, dense, gap = fn["route"](x, lp["norm2"], lp["router"], float8)
    routed = jnp.zeros_like(x)
    for j in range(count):
        routed = fn["expert"](routed, u, dense[:, first + j], lp["w_gate"][j], lp["w_up"][j],
                              lp["w_down"][j], float8)
    shared = fn["expert"](jnp.zeros_like(x), u, 1.0, lp["shared_gate"], lp["shared_up"],
                          lp["shared_down"], float8)
    return routed, shared, gap


def _static(cfg):
    return (("hidden", cfg["hidden_size"]), ("heads", cfg["num_attention_heads"]),
            ("kv_heads", cfg["num_key_value_heads"]), ("mamba_heads", cfg["mamba_n_heads"]),
            ("mamba_head_dim", cfg["mamba_d_head"]), ("mamba_state", cfg["mamba_d_state"]),
            ("conv_kernel", cfg["mamba_d_conv"]), ("rms_norm_eps", cfg["rms_norm_eps"]),
            ("rope_theta", float(cfg["rope_theta"])),
            ("experts_per_tok", cfg["num_experts_per_tok"]),
            ("embedding_multiplier", float(cfg["embedding_multiplier"])),
            ("logits_scaling", float(cfg["logits_scaling"])))


_PIECES = {}


def _pieces(cfg):
    key = _static(cfg)
    if key not in _PIECES:
        c = dict(key)
        _PIECES[key] = {
            "embed": jax.jit(lambda wte, tokens, float8: _wide(wte[tokens], float8)
                             * c["embedding_multiplier"], static_argnums=(2,)),
            "mamba": jax.jit(lambda x, lp, rm, float8, wrong, prompt_len, bucket: _mamba_mixer(
                x, lp, c, rm, float8, wrong, prompt_len, bucket),
                static_argnums=(2, 3, 4, 5, 6)),
            "attn_qkv": jax.jit(lambda x, lp, float8, rotate: _attn_qkv(
                x, lp, c, float8, rotate), static_argnums=(2, 3)),
            "head": jax.jit(_head, static_argnums=(5, 6), donate_argnums=(0,)),
            "route": jax.jit(lambda x, g, w, float8: _route(x, g, w, c, float8),
                             static_argnums=(3,)),
            "expert": jax.jit(_expert, static_argnums=(6,), donate_argnums=(0,)),
            "logits": jax.jit(lambda x, g, wte, float8: rms_norm(
                x, jnp.asarray(g, F32), c["rms_norm_eps"]) @ _wide(wte, float8).T
                / c["logits_scaling"], static_argnums=(3,)),
        }
    return _PIECES[key]


_MAMBA = ("norm1", "w_in", "conv_w", "conv_b", "dt_bias", "a_log", "d", "gate_norm", "w_out")
_ATTENTION = ("norm1", "wq", "wk", "wv", "wo")


def sequence_logits(params, cfg, tokens, rows=None, gaps=False, held=None, wrong=None,
                    prompt_len=None, bucket=None, cache=None):
    """tokens (T,) -> logits (len(rows), V) float32 of one sequence at the positions `rows`
    (all of them when None, in order). `cfg` is the configuration file's dict (the published
    keys; a layer's kind from `layer_types`). `held`: the module's docstring. With `gaps`,
    also each of those positions' smallest `pick_gap` over the expert layers. `wrong`: None,
    or one of WRONG (`prompt_len`, `bucket`: where the served prefill ended and its padded
    length). `cache`: a dict that is given what a prefill of `tokens` leaves behind at its last
    row: "state" [S (H, P, N) a mamba layer], "history" [(K - 1, HP + 2N) a mamba layer],
    "rows" [K | V (T, kv_heads, 2d) an attention layer]."""
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"wrong is None or one of {WRONG}, not {wrong!r}")
    fn = _pieces(cfg)
    float8 = wrong == "float8"
    if wrong not in ("conv_reset", "bucket_end", "scan_bf16"):
        prompt_len = bucket = None          # static to the mixer's piece: read by these alone
    tokens = jnp.asarray(tokens, jnp.int32)
    T = tokens.shape[0]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // heads
    rm = 1.0 if wrong == "residual_one" else float(cfg["residual_multiplier"])
    scale = d ** -0.5 if wrong == "attn_scale" else float(cfg["attention_multiplier"])
    with jax.default_matmul_precision("highest"):
        x = fn["embed"](params["wte"], tokens, float8)
        least_gap = jnp.full((T,), jnp.inf, F32)
        for lp, kind in zip(params["layers"], cfg["layer_types"]):
            if kind == "mamba":
                x, S, history = fn["mamba"](x, {k: lp[k] for k in _MAMBA}, rm, float8, wrong,
                                            prompt_len, bucket)
                if cache is not None:
                    cache.setdefault("state", []).append(S)
                    cache.setdefault("history", []).append(
                        jnp.zeros_like(history) if wrong == "conv_reset" else history)
            else:
                q, k, v = fn["attn_qkv"](x, {k: lp[k] for k in _ATTENTION}, float8,
                                         wrong == "rotary")
                if cache is not None:
                    cache.setdefault("rows", []).append(jnp.concatenate([k, v], -1))
                wo = _wide(lp["wo"], float8)
                for h in range(heads):
                    g = h // (heads // kv_heads)
                    x = fn["head"](x, q[:, h], k[:, g], v[:, g], wo[h * d:(h + 1) * d], scale,
                                   rm)
                del q, k, v
            routed, shared, gap = ffn(x, lp, cfg, held, wrong)
            x = x + rm * (routed + shared)
            least_gap = jnp.minimum(least_gap, gap)
        if rows is not None:
            x, least_gap = x[jnp.asarray(rows)], least_gap[jnp.asarray(rows)]
        logits = fn["logits"](x, params["norm_f"], params["wte"], float8)
        return (logits, least_gap) if gaps else logits
