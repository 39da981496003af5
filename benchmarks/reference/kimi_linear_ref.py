"""Kimi-Linear-48B-A3B-Instruct's language model as its published config.json describes
it (`model_type: kimi_linear`, https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct)
and, for the linear-attention mixer, as the Kimi Linear report (arXiv:2510.26692, section
"Kimi Delta Attention") and the open `fla` layer `KimiDeltaAttention` compute it, recalled:
no network here, the catalog's copy of config.json is the only text read. Plain jax.numpy
in float32 at the highest matmul precision: no cache, no kernel, no chunks, no batching;
the KDA mixer TOKEN BY TOKEN (a `lax.scan` over positions of the recurrence as written
below; the chunked form is the program's, never the reference's), the latent mixer
expanded, the experts in a Python loop. It shares no code with paddle_tpu and imports
nothing from it; only the parameter tree's layout is the served one (`x @ W`, W is (in,
out)), so that the same weights can be given to both.

Layer l (1-indexed) of x (T, h): u = RMSNorm(x), x += mixer_l(u), h = RMSNorm(x), x +=
ffn_l(h); a final RMSNorm; logits = y W_head [config: tie_word_embeddings false]. A line
marked [config] is settled by a key of the config; one marked [assumed] is not, and is
listed under `assumed` in benchmarks/configs/kimi-linear-48b-a3b.json.

KDA mixer, l in linear_attn_config.kda_layers [config: the list, num_heads n, head_dim d,
short_conv_kernel_size K]. q|k|v = u W_qkv, each (T, n d). A causal depthwise
convolution of width K and SiLU, a filter a channel, no bias, zeros before position 0:
c_t = SiLU(sum_{i<K} w_i x_{t-K+1+i}) [assumed: no bias]. A head: q_t = c^q_t / sqrt(|c^q_t|^2
+ eps) * d^-0.5, k_t = c^k_t / sqrt(|c^k_t|^2 + eps), v_t = c^v_t [assumed: eps 1e-6 inside
the root]. The decay a head and KEY CHANNEL, in log space: g_t = -exp(A_log[head]) *
softplus((u W_a_down) W_a_up + dt_bias), (n, d) [assumed: the pair's rank = d]. beta_t =
sigmoid(u W_b), (n,). The state S (n, d key, d value) float32, zero before position 0:
    S' = exp(g_t)[:, None] * S_{t-1};  S_t = S' + beta_t outer(k_t, v_t - S'^T k_t);
    o_t = S_t^T q_t.
The output gate through a second low-rank pair: z_t = (u W_g_down) W_g_up; y_t =
RMSNorm_d(o_t; w) * sigmoid(z_t) a head [assumed: the rank, the sigmoid]; mixer =
concat(y_t) W_o. No positions anywhere.

Latent mixer, l in full_attn_layers [config: kv_lora_rank, qk_nope_head_dim,
qk_rope_head_dim, v_head_dim, q_lora_rank null, mla_use_nope true: NOTHING is rotated]:
q = u W_q (n x (nope | rope)), c | kp = u W_kva (rank | rope), c = RMSNorm(c), kn | v = c
W_kvb (n x (nope | v)), k = [kn, kp shared by the heads], scores q k^T (nope + rope)^-0.5,
causal softmax, o W_o.

ffn: layer l <= first_k_dense_replace a dense SwiGLU of intermediate_size; every other
layer s = sigmoid(h W_r) over num_experts PUBLISHED experts [config:
moe_router_activation_func], the num_experts_per_token largest of s + bias [assumed: a
correction bias, as the family's], w_e = routed_scaling_factor s_e / sum of the picked s
[config: moe_renormalize], sum_e w_e SwiGLU_e(h) over experts of moe_intermediate_size,
plus num_shared_experts shared SwiGLU(s) of the same width. One group [config:
num_expert_group 1, topk_group 1]: grouped top-k is plain top-k.

THE HELD RANGE, as command_a_ref's: `held = (first, count)`, the routed experts whose
weights the tree holds; a pick outside adds nothing, its weight still divides the sum.
The vocabulary is the tree's rows.

Departures, none of which changes a value: each held expert is applied to EVERY token and
weighted by its routing weight, zero where it was not picked; the feed-forward and the
latent attention run a block of BLOCK tokens, a head and an expert at a time, their
weights widened to float32 where they are used, so that 6,144 rows fit beside the served
weights on a chip.

WRONG programs (`wrong=`), for showing that the cell's verdict tells them from the served
tokens; none is ever the reference of a run's `correct`. `prompt_len` and `bucket` say
where the served request's prefill ended and how long its padded bucket was:
  "float8": every matrix rounded to float8_e4m3 (the precision below bfloat16);
  "state_bf16": the recurrent state rounded to bfloat16 after every position;
  "no_decay": g = 0;
  "beta_one": beta = 1;
  "conv_reset": the convolution's history not carried from the prefill (zeros before the
      first generated position);
  "bucket_end": the state taken at the bucket's end and not at the prompt's: the prompt's
      last position repeated (bucket - prompt_len) times into the state before the first
      generated position;
  "rotary_on_latent": the latent layers WITH rotation (theta rope_theta, interleaved
      pairs, on the rope part of q and k);
  "kinds_shifted": the layer kinds shifted by one (latent at 3, 7, 11): each latent
      layer's mixer changes places with the KDA mixer before it."""

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
BLOCK = 2048
WRONG = ("float8", "state_bf16", "no_decay", "beta_one", "conv_reset", "bucket_end",
         "rotary_on_latent", "kinds_shifted")
L2_EPS = 1e-6


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


# -- the KDA mixer, token by token ----------------------------------------------------

def kda_step(S, q, k, v, g, beta):
    """One position of the recurrence: S (n, dk, dv), q, k, g (n, dk), v (n, dv), beta
    (n,). Returns (S_t, o_t (n, dv))."""
    Sd = jnp.exp(g)[:, :, None] * S
    u = beta[:, None] * (v - jnp.einsum("nkv,nk->nv", Sd, k))
    S = Sd + k[:, :, None] * u[:, None, :]
    return S, jnp.einsum("nkv,nk->nv", S, q)


def kda_inputs(u, lp, c, float8=False, wrong=None, prompt_len=None):
    """The recurrence's inputs of normed rows u (T, h): q, k, v, g (T, n, d), beta (T, n),
    and the gate's z (T, n, d)."""
    T = u.shape[0]
    n, d, K = c["kda_heads"], c["kda_head_dim"], c["conv_kernel"]
    qkv = u @ _wide(lp["wqkv"], float8)
    w = jnp.asarray(lp["conv_w"], F32)
    padded = jnp.pad(qkv, ((K - 1, 0), (0, 0)))
    conv = sum(w[i] * padded[i:i + T] for i in range(K))
    if wrong == "conv_reset" and prompt_len is not None and prompt_len < T:
        # the first generated positions see zeros where the prompt's last rows were
        cut = jnp.where((jnp.arange(T + K - 1) < prompt_len + K - 1)[:, None], 0.0, padded)
        reset = sum(w[i] * cut[i:i + T] for i in range(K))
        conv = jnp.where((jnp.arange(T) >= prompt_len)[:, None], reset, conv)
    act = jax.nn.silu(conv)
    q, k, v = (part.reshape(T, n, d) for part in jnp.split(act, 3, -1))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS) * d ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    a = (u @ _wide(lp["wa_down"], float8)) @ _wide(lp["wa_up"], float8)
    g = -jnp.exp(jnp.asarray(lp["a_log"], F32))[None, :, None] * jax.nn.softplus(
        a + jnp.asarray(lp["dt_bias"], F32)).reshape(T, n, d)
    beta = jax.nn.sigmoid(u @ _wide(lp["wb"], float8))
    if wrong == "no_decay":
        g = jnp.zeros_like(g)
    if wrong == "beta_one":
        beta = jnp.ones_like(beta)
    z = ((u @ _wide(lp["wg_down"], float8)) @ _wide(lp["wg_up"], float8)).reshape(T, n, d)
    return q, k, v, g, beta, z


def kda_recurrence(q, k, v, g, beta, state_bf16=False, repeat_at=None, repeats=0):
    """o (T, n, d) of the recurrence from a zero state, one position at a time. The wrong
    programs: `state_bf16` rounds the state after every position; `repeat_at`, `repeats`:
    position `repeat_at`'s update applied `repeats` times more (traced) before the next."""
    n, d = q.shape[1], q.shape[2]

    def step(S, row):
        S, o = kda_step(S, *row[:5])
        if repeat_at is not None:
            again = jnp.where(row[5] == repeat_at, repeats, 0)
            S = jax.lax.fori_loop(0, again, lambda _, S: kda_step(S, *row[:5])[0], S)
        if state_bf16:
            S = as_bfloat16(S)
        return S, o

    at = jnp.arange(q.shape[0])
    return jax.lax.scan(step, jnp.zeros((n, d, d), F32), (q, k, v, g, beta, at))[1]


def _kda_mixer(x, lp, c, float8, wrong, prompt_len, bucket):
    """x (T, h) + the KDA mixer of RMSNorm(x)."""
    u = rms_norm(x, jnp.asarray(lp["norm1"], F32), c["rms_norm_eps"])
    q, k, v, g, beta, z = kda_inputs(u, lp, c, float8, wrong, prompt_len)
    past = wrong == "bucket_end" and prompt_len is not None
    o = kda_recurrence(q, k, v, g, beta, wrong == "state_bf16",
                       prompt_len - 1 if past else None,
                       max(bucket - prompt_len, 0) if past else 0)
    y = rms_norm(o, jnp.asarray(lp["o_norm"], F32), c["rms_norm_eps"]) * jax.nn.sigmoid(z)
    return x + y.reshape(x.shape[0], -1) @ _wide(lp["wo"], float8)


# -- the latent mixer, expanded --------------------------------------------------------

def _rope_interleaved(x, pos, theta):
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[:, None] * inv_freq
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(x.shape)


def _latent_qkv(x, lp, c, float8, rotate):
    """Queries (T, n, nope + rope), keys (T, n, nope + rope), values (T, n, v) of x (T, h)."""
    T = x.shape[0]
    n, nope, rope = c["heads"], c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    rank, dv = c["kv_lora_rank"], c["v_head_dim"]
    u = rms_norm(x, jnp.asarray(lp["norm1"], F32), c["rms_norm_eps"])
    q = (u @ _wide(lp["wq"], float8)).reshape(T, n, nope + rope)
    kva = u @ _wide(lp["wkva"], float8)
    lat = rms_norm(kva[:, :rank], jnp.asarray(lp["kv_norm"], F32), c["rms_norm_eps"])
    kp = kva[:, rank:]
    if rotate:
        pos = jnp.arange(T)
        q = jnp.concatenate([q[..., :nope], _rope_interleaved(q[..., nope:], pos,
                                                              c["rope_theta"])], -1)
        kp = _rope_interleaved(kp, pos, c["rope_theta"])
    kv = (lat @ _wide(lp["wkvb"], float8)).reshape(T, n, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(kp[:, None], (T, n, rope))], -1)
    return q, k, kv[..., nope:]


def _head_block(y, q, start, k, v, w_o):
    """y (B, h) + one head of one block of queries against the whole sequence's keys."""
    i = start + jnp.arange(q.shape[0])[:, None]
    mask = jnp.arange(k.shape[0])[None, :] <= i
    scores = (q @ k.T) / math.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return y + (probs @ v) @ w_o


# -- the feed-forward -------------------------------------------------------------------

def router(h, w_router, bias, c):
    """h (T, hidden) float32, normed -> dense (T, E): the weights at their experts, zero
    elsewhere, over ALL the published experts."""
    scores = jax.nn.sigmoid(h @ w_router)
    _, picks = jax.lax.top_k(scores + bias, c["num_experts_per_token"])
    weights = jnp.take_along_axis(scores, picks, -1)
    weights = weights / weights.sum(-1, keepdims=True) * c["routed_scaling_factor"]
    return jnp.zeros_like(scores).at[jnp.arange(h.shape[0])[:, None], picks].set(weights)


def pick_gap(h, w_router, bias, c):
    """(T,): how far the last expert picked is ahead of the first one left out, in the
    ranked score (sigmoid + bias)."""
    k = c["num_experts_per_token"]
    best, _ = jax.lax.top_k(jax.nn.sigmoid(h @ w_router) + bias, k + 1)
    return best[:, k - 1] - best[:, k]


def _route(x, norm2, w_router, bias, c, float8):
    h = rms_norm(x, jnp.asarray(norm2, F32), c["rms_norm_eps"])
    w_router, bias = _wide(w_router, float8), jnp.asarray(bias, F32)
    return h, router(h, w_router, bias, c), pick_gap(h, w_router, bias, c)


def _expert(acc, h, scale, gate, up, down, float8):
    y = _swiglu(h, _wide(gate, float8), _wide(up, float8), _wide(down, float8))
    return acc + (scale[:, None] if jnp.ndim(scale) else scale) * y


def ffn(x, lp, cfg, held=None, wrong=None):
    """The feed-forward of one layer on rows x (T, h) (its norm first) in two parts,
    (routed or dense (T, h), shared (T, h)), and the picks' gap (T,) (inf for a dense
    layer)."""
    fn = _pieces(cfg)
    float8 = wrong == "float8"
    if "router" not in lp:
        h = rms_norm(x, jnp.asarray(lp["norm2"], F32), cfg["rms_norm_eps"])
        y = fn["expert"](jnp.zeros_like(x), h, 1.0, lp["gate"], lp["up"], lp["down"], float8)
        return y, jnp.zeros_like(x), jnp.full((x.shape[0],), jnp.inf, F32)
    first, count = held if held is not None else \
        (cfg.get("experts_held_first", 0), lp["w_gate"].shape[0])
    if count != lp["w_gate"].shape[0]:
        raise ValueError(f"held {count} experts, the tree has {lp['w_gate'].shape[0]}")
    h, dense, gap = fn["route"](x, lp["norm2"], lp["router"], lp["router_bias"], float8)
    routed = jnp.zeros_like(x)
    for j in range(count):
        routed = fn["expert"](routed, h, dense[:, first + j], lp["w_gate"][j], lp["w_up"][j],
                              lp["w_down"][j], float8)
    shared = fn["expert"](jnp.zeros_like(x), h, 1.0, lp["shared_gate"], lp["shared_up"],
                          lp["shared_down"], float8)
    return routed, shared, gap


def _static(cfg):
    lin = cfg["linear_attn_config"]
    return (("kda_heads", lin["num_heads"]), ("kda_head_dim", lin["head_dim"]),
            ("conv_kernel", lin["short_conv_kernel_size"]),
            ("heads", cfg["num_attention_heads"]), ("kv_lora_rank", cfg["kv_lora_rank"]),
            ("qk_nope_head_dim", cfg["qk_nope_head_dim"]),
            ("qk_rope_head_dim", cfg["qk_rope_head_dim"]), ("v_head_dim", cfg["v_head_dim"]),
            ("rms_norm_eps", cfg["rms_norm_eps"]), ("rope_theta", float(cfg["rope_theta"])),
            ("num_experts_per_token", cfg["num_experts_per_token"]),
            ("routed_scaling_factor", cfg["routed_scaling_factor"]))


_PIECES = {}


def _pieces(cfg):
    key = _static(cfg)
    if key not in _PIECES:
        c = dict(key)
        _PIECES[key] = {
            "kda": jax.jit(lambda x, lp, float8, wrong, prompt_len, bucket: _kda_mixer(
                x, lp, c, float8, wrong, prompt_len, bucket), static_argnums=(2, 3, 4, 5)),
            "latent_qkv": jax.jit(lambda x, lp, float8, rotate: _latent_qkv(
                x, lp, c, float8, rotate), static_argnums=(2, 3)),
            "head_block": jax.jit(_head_block, donate_argnums=(0,)),
            "route": jax.jit(lambda x, g, w, b, float8: _route(x, g, w, b, c, float8),
                             static_argnums=(4,)),
            "expert": jax.jit(_expert, static_argnums=(6,), donate_argnums=(0,)),
            "logits": jax.jit(lambda x, g, w, float8: rms_norm(
                x, jnp.asarray(g, F32), c["rms_norm_eps"]) @ _wide(w, float8),
                static_argnums=(3,)),
        }
    return _PIECES[key]


_KDA = ("norm1", "wqkv", "conv_w", "wa_down", "wa_up", "a_log", "dt_bias", "wb", "wg_down",
        "wg_up", "o_norm", "wo")
_LATENT = ("norm1", "wq", "wkva", "kv_norm", "wkvb", "wo")


def _mixers(params, cfg, wrong):
    """[(kind, the mixer's weights)] a layer, by the published lists; under
    "kinds_shifted" each latent layer's mixer changes places with the one before it."""
    kda = set(cfg["linear_attn_config"]["kda_layers"])
    out = [("kda", {k: lp[k] for k in _KDA}) if i + 1 in kda else
           ("latent", {k: lp[k] for k in _LATENT}) for i, lp in enumerate(params["layers"])]
    if wrong == "kinds_shifted":
        for i in range(1, len(out)):
            if out[i][0] == "latent" and out[i - 1][0] == "kda":
                out[i - 1], out[i] = out[i], out[i - 1]
    return out


def sequence_logits(params, cfg, tokens, rows=None, gaps=False, held=None, wrong=None,
                    prompt_len=None, bucket=None):
    """tokens (T,) -> logits (len(rows), V) float32 of one sequence at the positions
    `rows` (all of them when None, in order). `cfg` is the configuration file's dict (the
    published keys). `held`: the module's docstring. With `gaps`, also each of those
    positions' smallest `pick_gap` over the expert layers. `wrong`: None, or one of WRONG
    (`prompt_len`, `bucket`: where the served prefill ended and its padded length)."""
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"wrong is None or one of {WRONG}, not {wrong!r}")
    fn = _pieces(cfg)
    float8 = wrong == "float8"
    tokens = jnp.asarray(tokens, jnp.int32)
    T = tokens.shape[0]
    size = BLOCK if T % BLOCK == 0 else T
    starts = list(range(0, T, size))
    dv = cfg["v_head_dim"]
    with jax.default_matmul_precision("highest"):
        x = _wide(params["wte"][tokens], float8)
        least_gap = jnp.full((T,), jnp.inf, F32)
        for lp, (kind, mixer) in zip(params["layers"], _mixers(params, cfg, wrong)):
            if kind == "kda":
                x = fn["kda"](x, mixer, float8, wrong, prompt_len, bucket)
            else:
                q, k, v = fn["latent_qkv"](x, mixer, float8, wrong == "rotary_on_latent")
                wo = _wide(mixer["wo"], float8)
                X = [x[s:s + size] for s in starts]
                for h in range(cfg["num_attention_heads"]):
                    X = [fn["head_block"](xb, q[s:s + size, h], s, k[:, h], v[:, h],
                                          wo[h * dv:(h + 1) * dv]) for xb, s in zip(X, starts)]
                x = jnp.concatenate(X)
                del q, k, v, X
            parts = []
            for s in starts:
                routed, shared, gap = ffn(x[s:s + size], lp, cfg, held, wrong)
                parts.append((x[s:s + size] + routed + shared, gap))
            x = jnp.concatenate([p[0] for p in parts])
            least_gap = jnp.minimum(least_gap, jnp.concatenate([p[1] for p in parts]))
        if rows is not None:
            x, least_gap = x[jnp.asarray(rows)], least_gap[jnp.asarray(rows)]
        logits = fn["logits"](x, params["norm_f"], params["head"], float8)
        return (logits, least_gap) if gaps else logits
