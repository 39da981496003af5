"""Qwen3-Next-80B-A3B-Instruct's language model as its published config.json describes it
(`model_type: qwen3_next`, https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct) and, for
what the config does not itself state, as the published modelling code
(`modeling_qwen3_next.py`: `Qwen3NextGatedDeltaNet`, `Qwen3NextAttention`,
`Qwen3NextSparseMoeBlock`, `Qwen3NextRMSNorm`, `Qwen3NextRMSNormGated`) computes it,
RECALLED: no network here, the catalog's copy of config.json is the only text read. Plain
jax.numpy in float32 at the highest matmul precision: no cache, no kernel, no chunks, no
batching; the Gated-DeltaNet mixer TOKEN BY TOKEN (a `lax.scan` over positions of the
recurrence as written below; the chunked form is the program's, never the reference's), the
attention a head at a time with a plain softmax, the experts in a Python loop. It shares no
code with paddle_tpu and imports nothing from it; only the parameter tree's layout is the
served one (`x @ W`, W is (in, out)), so that the same weights can be given to both. The
sampler's draws are reference/granite_hybrid_ref.py's (threefry and the Gumbel transform
written from their definitions there; the counters are the contract).

norm(x, w) = x / sqrt(mean(x^2) + rms_norm_eps) * (1 + w) in float32 [assumed: the family's
ZERO-CENTRED weight, everywhere but the recurrent mixer's gated norm]. Layer l (0-indexed) is
full attention where (l + 1) % full_attention_interval == 0, else Gated DeltaNet [config]:
    x = x + mixer_l(norm(x, norm1));  x = x + moe(norm(x, norm2))
logits = norm(x, norm_f) W_head [config: tie_word_embeddings false]. A line marked [config]
is settled by a key of the config; one marked [assumed] is not, and is listed under `assumed`
in benchmarks/configs/qwen3-next-80b-a3b.json.

Gated DeltaNet [config: linear_num_key_heads nk, linear_num_value_heads nv,
linear_key_head_dim dk, linear_value_head_dim dv, linear_conv_kernel_dim K]. [q (nk dk) | k
(nk dk) | v (nv dv) | z (nv dv)] = u W_qkvz, [b (nv) | a (nv)] = u W_ba [assumed: the tree
holds the columns in this order; the checkpoint's are a key head at a time]. A causal
depthwise convolution of width K, no bias, and SiLU over q | k | v together, a filter a
channel, zeros before position 0. beta = sigmoid(b); g = -exp(A_log) softplus(a + dt_bias) a
VALUE head, a scalar; q, k = x / sqrt(sum x^2 + 1e-6) a key head, q also times dk^-0.5
[assumed]; value head h reads q, k of key head h // (nv / nk) [assumed: `repeat_interleave`].
The state S (nv, dk, dv) float32, zero before position 0:
    S = exp(g_t) S;  S = S + outer(k_t, beta_t (v_t - S^T k_t));  o_t = S^T q_t.
The gated norm: `w * (o_t / sqrt(mean(o_t^2) + eps))` a head FIRST, then times SiLU(z_t)
[assumed: norm before gate, a plain weight]; mixer = that W_out. No positions anywhere.

Gated attention [config: num_attention_heads, num_key_value_heads, head_dim,
partial_rotary_factor, rope_theta]: [q | gate] = u W_q a head (2 head_dim columns a head)
[assumed: the gate is the query projection's second half a head], k, v = u W_k, u W_v; q, k
normed a head over head_dim (zero-centred); rotary on the FIRST head_dim x
partial_rotary_factor values of a head, halves paired, inv_freq over that width, no scaling;
query head i reads KV head i // group; scores q k^T head_dim^-0.5, causal softmax;
mixer = (attn * sigmoid(gate)) W_o.

Experts [config: num_experts, num_experts_per_tok, moe_intermediate_size,
shared_expert_intermediate_size, norm_topk_prob true]: p = softmax(u W_r) over the
PUBLISHED experts in float32, the k largest, divided by their sum; expert e: SwiGLU of
moe_intermediate_size; plus sigmoid(u . w_s) times one shared SwiGLU of
shared_expert_intermediate_size [assumed: the token's gate].

THE HELD RANGE, as command_a_ref's: `held = (first, count)`, the routed experts whose
weights the tree holds; a pick outside adds nothing, its weight still divides the sum. The
vocabulary is the tree's rows.

Departures, none of which changes a value: each held expert is applied to EVERY token and
weighted by its routing weight, zero where it was not picked; the attention a head at a
time and, past ATTN_BLOCK rows, a block of query rows at a time (the plain softmax of each
row over all its keys: a 16,384 x 16,384 score matrix is 1 GB a head), weights widened to
float32 where they are used, so that 18,432 rows fit beside the served weights and the
pools on a chip.

WRONG programs (`wrong=`), for showing that the cell's verdict tells them from the served
tokens; none is ever the reference of a run's `correct`. What THIS layer adds (granite's and
Kimi-Linear's own wrong programs are held by their cells):
  "no_output_gate": the attention's output without `sigmoid(gate)`;
  "full_rotary": all head_dim values of a head rotated (inv_freq over head_dim);
  "norm_not_centred": `w` for `1 + w` in every zero-centred norm;
  "gate_before_norm": granite's order in the recurrent mixer, `norm(o SiLU(z))`;
  "key_heads_unshared": value head h reads key head h mod nk (a `tile`, not a
      `repeat_interleave`);
  "shared_gate_off": the shared expert added whole, no token gate;
  "state_bf16": the recurrent state rounded to bfloat16 after every position;
  "scan_bf16": the recurrence's operands q, k, v, g, beta of the PROMPT's rows rounded to
      bfloat16 (a prompt's scan whose products run below float32)."""

import jax
import jax.numpy as jnp

from .granite_hybrid_ref import as_bfloat16, gumbel_draws  # noqa: F401  (the sampler's draws)

F32 = jnp.float32
WRONG = ("no_output_gate", "full_rotary", "norm_not_centred", "gate_before_norm",
         "key_heads_unshared", "shared_gate_off", "state_bf16", "scan_bf16")
# past this many rows the attention runs a block of query rows at a time
ATTN_BLOCK = 2048
L2_EPS = 1e-6


def rms_norm(x, w, eps, centred=True):
    w = jnp.asarray(w, F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w if centred else w)


def _wide(w):
    return jnp.asarray(w).astype(F32)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


# -- the Gated-DeltaNet mixer, token by token ---------------------------------------------

def gdn_step(S, q, k, v, g, beta):
    """One position of the recurrence: S (nv, dk, dv), q, k (nv, dk) (each value head's
    own), v (nv, dv), g, beta (nv,). Returns (S_t, o_t (nv, dv))."""
    S = jnp.exp(g)[:, None, None] * S
    u = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", S, k))
    S = S + k[:, :, None] * u[:, None, :]
    return S, jnp.einsum("hkv,hk->hv", S, q)


def to_value_heads(x, nv, unshared=False):
    """A key head's rows (T, nk, dk) handed to the value heads that read it (T, nv, dk):
    value head h reads key head h // (nv / nk); the WRONG program reads h mod nk."""
    nk = x.shape[1]
    return jnp.tile(x, (1, nv // nk, 1)) if unshared else jnp.repeat(x, nv // nk, 1)


def gdn_inputs(u, lp, c):
    """The recurrence's inputs of normed rows u (T, h): q, k (T, nk, dk), v (T, nv, dv), g,
    beta (T, nv), the gate's z (T, nv dv), and the convolution's pre-activation rows q|k|v
    (T, 2 nk dk + nv dv)."""
    T = u.shape[0]
    nk, nv, dk, dv, K = (c["key_heads"], c["value_heads"], c["key_dim"], c["value_dim"],
                         c["conv_kernel"])
    kw, wide = nk * dk, 2 * nk * dk + nv * dv
    w = lp["w_qkvz"]
    qkv = u @ _wide(w[:, :wide])
    z = u @ _wide(w[:, wide:])
    ba = u @ _wide(lp["w_ba"])
    filt = jnp.asarray(lp["conv_w"], F32)
    padded = jnp.pad(qkv, ((K - 1, 0), (0, 0)))
    act = jax.nn.silu(sum(filt[i] * padded[i:i + T] for i in range(K)))
    q = act[:, :kw].reshape(T, nk, dk)
    k = act[:, kw:2 * kw].reshape(T, nk, dk)
    v = act[:, 2 * kw:].reshape(T, nv, dv)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS) * dk ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    beta = jax.nn.sigmoid(ba[:, :nv])
    g = -jnp.exp(jnp.asarray(lp["a_log"], F32)) * jax.nn.softplus(
        ba[:, nv:] + jnp.asarray(lp["dt_bias"], F32))
    return q, k, v, g, beta, z, qkv


def gdn_recurrence(q, k, v, g, beta, state_bf16=False):
    """(o (T, nv, dv), the state after the last position) of the recurrence from a zero
    state, one position at a time; q, k (T, nv, dk) each value head's own. The wrong program
    `state_bf16` rounds the state after every position."""
    def step(S, row):
        S, o = gdn_step(S, *row)
        if state_bf16:
            S = as_bfloat16(S)
        return S, o

    S0 = jnp.zeros((v.shape[1], k.shape[2], v.shape[2]), F32)
    S, o = jax.lax.scan(step, S0, (q, k, v, g, beta))
    return o, S


def _gdn_mixer(x, lp, c, centred, wrong, prompt_len):
    """(x (T, h) + the Gated-DeltaNet mixer of norm(x), the state after row T - 1, the
    convolution's history there: the last K - 1 pre-activation rows, zeros before row 0)."""
    T = x.shape[0]
    u = rms_norm(x, lp["norm1"], c["rms_norm_eps"], centred)
    q, k, v, g, beta, z, qkv = gdn_inputs(u, lp, c)
    q, k = (to_value_heads(a, c["value_heads"], wrong == "key_heads_unshared") for a in (q, k))
    if wrong == "scan_bf16":
        rows = jnp.arange(T) < (T if prompt_len is None else prompt_len)
        q, k, v, g, beta = (
            jnp.where(rows.reshape((-1,) + (1,) * (a.ndim - 1)), as_bfloat16(a), a)
            for a in (q, k, v, g, beta))
    o, S = gdn_recurrence(q, k, v, g, beta, wrong == "state_bf16")
    gate = jax.nn.silu(z).reshape(o.shape)
    if wrong == "gate_before_norm":
        y = rms_norm(o * gate, lp["gate_norm"], c["rms_norm_eps"], centred=False)
    else:
        y = rms_norm(o, lp["gate_norm"], c["rms_norm_eps"], centred=False) * gate
    K = c["conv_kernel"]
    return (x + y.reshape(T, -1) @ _wide(lp["w_out"]), S,
            jnp.pad(qkv, ((K - 1, 0), (0, 0)))[-(K - 1):])


# -- the attention, a head at a time ---------------------------------------------------

def _rope_halves(x, pos, theta):
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attn_qkv(x, lp, c, centred, full_rotary):
    """Queries (T, heads, d), keys and values (T, kv_heads, d) and the output gate (T,
    heads, d) of x (T, h)."""
    T = x.shape[0]
    n, kv, d = c["heads"], c["kv_heads"], c["head_dim"]
    eps = c["rms_norm_eps"]
    u = rms_norm(x, lp["norm1"], eps, centred)
    qg = (u @ _wide(lp["wq"])).reshape(T, n, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = (u @ _wide(lp["wk"])).reshape(T, kv, d)
    v = (u @ _wide(lp["wv"])).reshape(T, kv, d)
    q, k = rms_norm(q, lp["q_norm"], eps, centred), rms_norm(k, lp["k_norm"], eps, centred)
    r = d if full_rotary else c["rotary_dim"]
    pos = jnp.arange(T)
    turn = lambda a: jnp.concatenate([_rope_halves(a[..., :r], pos, c["rope_theta"]),
                                      a[..., r:]], -1)
    return turn(q), turn(k), v, gate


def _head(y, q, k, v, gate, w_o, gated):
    """y (T, h) + one head's causal attention, times sigmoid(gate) where `gated`, through
    its rows of W_o: the plain softmax of every row over its keys, a block of query rows at
    a time past ATTN_BLOCK rows."""
    T, d = q.shape
    scale = d ** -0.5
    at = jnp.arange(T)

    def rows(args):
        qb, pb = args
        s = jnp.where(at[None, :] <= pb[:, None], (qb @ k.T) * scale, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ v

    if T <= ATTN_BLOCK:
        o = rows((q, at))
    else:
        # whole blocks of query rows, the last one padded (its padding attends and is cut)
        n = -(-T // ATTN_BLOCK)
        padded = jnp.pad(q, ((0, n * ATTN_BLOCK - T), (0, 0)))
        o = jax.lax.map(rows, (padded.reshape(n, ATTN_BLOCK, d),
                               jnp.arange(n * ATTN_BLOCK).reshape(n, ATTN_BLOCK)))
        o = o.reshape(n * ATTN_BLOCK, d)[:T]
    if gated:
        o = o * jax.nn.sigmoid(gate)
    return y + o @ w_o


# -- the feed-forward -------------------------------------------------------------------

def router(u, w_router, c):
    """u (T, hidden) float32, normed -> dense (T, E): the weights at their experts, zero
    elsewhere, over ALL the published experts: softmax over all, the k largest, over their
    sum."""
    probs = jax.nn.softmax(u @ w_router, -1)
    best, picks = jax.lax.top_k(probs, c["experts_per_tok"])
    weights = best / best.sum(-1, keepdims=True)
    return jnp.zeros_like(probs).at[jnp.arange(u.shape[0])[:, None], picks].set(weights)


def pick_gap(u, w_router, c):
    """(T,): how far the last expert picked is ahead of the first one left out, in the
    router's logits."""
    k = c["experts_per_tok"]
    best, _ = jax.lax.top_k(u @ w_router, k + 1)
    return best[:, k - 1] - best[:, k]


def _route(x, norm2, w_router, w_token, c, centred):
    u = rms_norm(x, norm2, c["rms_norm_eps"], centred)
    w_router = _wide(w_router)
    return u, router(u, w_router, c), pick_gap(u, w_router, c), jax.nn.sigmoid(u @ _wide(w_token))


def _expert(acc, u, scale, gate, up, down):
    y = _swiglu(u, _wide(gate), _wide(up), _wide(down))
    return acc + (scale[:, None] if jnp.ndim(scale) else scale) * y


def ffn(x, lp, cfg, held=None, wrong=None):
    """The feed-forward of one layer on rows x (T, h) (its norm first) in two parts: (routed
    (T, h), the shared expert's term with the token's gate on it (T, h)), and the picks' gap
    (T,)."""
    fn = _pieces(cfg)
    first, count = held if held is not None else \
        (cfg.get("experts_held_first", 0), lp["w_gate"].shape[0])
    if count != lp["w_gate"].shape[0]:
        raise ValueError(f"held {count} experts, the tree has {lp['w_gate'].shape[0]}")
    u, dense, gap, token = fn["route"](x, lp["norm2"], lp["router"], lp["shared_token_gate"],
                                       wrong != "norm_not_centred")
    routed = jnp.zeros_like(x)
    for j in range(count):
        routed = fn["expert"](routed, u, dense[:, first + j], lp["w_gate"][j], lp["w_up"][j],
                              lp["w_down"][j])
    shared = fn["expert"](jnp.zeros_like(x), u, 1.0 if wrong == "shared_gate_off" else token,
                          lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    return routed, shared, gap


def kinds(cfg):
    """"attention" or "gdn" for each of the `num_hidden_layers` layers held."""
    every = cfg["full_attention_interval"]
    return ["attention" if (i + 1) % every == 0 else "gdn"
            for i in range(cfg["num_hidden_layers"])]


def _static(cfg):
    return (("hidden", cfg["hidden_size"]), ("heads", cfg["num_attention_heads"]),
            ("kv_heads", cfg["num_key_value_heads"]), ("head_dim", cfg["head_dim"]),
            ("rotary_dim", int(cfg["head_dim"] * cfg["partial_rotary_factor"])),
            ("key_heads", cfg["linear_num_key_heads"]),
            ("value_heads", cfg["linear_num_value_heads"]),
            ("key_dim", cfg["linear_key_head_dim"]), ("value_dim", cfg["linear_value_head_dim"]),
            ("conv_kernel", cfg["linear_conv_kernel_dim"]),
            ("rms_norm_eps", cfg["rms_norm_eps"]), ("rope_theta", float(cfg["rope_theta"])),
            ("experts_per_tok", cfg["num_experts_per_tok"]))


_PIECES = {}


def _pieces(cfg):
    key = _static(cfg)
    if key not in _PIECES:
        c = dict(key)
        _PIECES[key] = {
            "embed": jax.jit(lambda wte, tokens: _wide(wte[tokens])),
            "gdn": jax.jit(lambda x, lp, centred, wrong, prompt_len: _gdn_mixer(
                x, lp, c, centred, wrong, prompt_len), static_argnums=(2, 3, 4)),
            "attn_qkv": jax.jit(lambda x, lp, centred, full_rotary: _attn_qkv(
                x, lp, c, centred, full_rotary), static_argnums=(2, 3)),
            "head": jax.jit(_head, static_argnums=(6,), donate_argnums=(0,)),
            "route": jax.jit(lambda x, g, w, t, centred: _route(x, g, w, t, c, centred),
                             static_argnums=(4,)),
            "expert": jax.jit(_expert, donate_argnums=(0,)),
            "logits": jax.jit(lambda x, g, head, centred: rms_norm(
                x, g, c["rms_norm_eps"], centred) @ _wide(head), static_argnums=(3,)),
        }
    return _PIECES[key]


_GDN = ("norm1", "w_qkvz", "w_ba", "conv_w", "dt_bias", "a_log", "gate_norm", "w_out")
_ATTENTION = ("norm1", "wq", "wk", "wv", "q_norm", "k_norm")


def sequence_logits(params, cfg, tokens, rows=None, gaps=False, held=None, wrong=None,
                    prompt_len=None, bucket=None, cache=None):
    """tokens (T,) -> logits (len(rows), V) float32 of one sequence at the positions `rows`
    (all of them when None, in order). `cfg` is the configuration file's dict (the published
    keys; a layer's kind from `full_attention_interval`). `held`: the module's docstring.
    With `gaps`, also each of those positions' smallest `pick_gap` over the expert layers.
    `wrong`: None, or one of WRONG (`prompt_len`: where the served prefill ended; `bucket`
    is taken for the callers' sake and read by nothing here). `cache`: a dict that is given
    what a prefill of `tokens` leaves behind at its last row: "state" [S (nv, dk, dv) a
    Gated-DeltaNet layer], "history" [(K - 1, 2 nk dk + nv dv) such a layer], "rows" [K | V
    (T, kv_heads, 2d) an attention layer]."""
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"wrong is None or one of {WRONG}, not {wrong!r}")
    fn = _pieces(cfg)
    if wrong != "scan_bf16":
        prompt_len = None                   # static to the mixer's piece: read by that alone
    centred = wrong != "norm_not_centred"
    tokens = jnp.asarray(tokens, jnp.int32)
    T = tokens.shape[0]
    heads, kv_heads, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                          cfg["head_dim"])
    with jax.default_matmul_precision("highest"):
        x = fn["embed"](params["wte"], tokens)
        least_gap = jnp.full((T,), jnp.inf, F32)
        for lp, kind in zip(params["layers"], kinds(cfg)):
            if kind == "gdn":
                x, S, history = fn["gdn"](x, {k: lp[k] for k in _GDN}, centred, wrong,
                                          prompt_len)
                if cache is not None:
                    cache.setdefault("state", []).append(S)
                    cache.setdefault("history", []).append(history)
            else:
                q, k, v, gate = fn["attn_qkv"](x, {k: lp[k] for k in _ATTENTION}, centred,
                                               wrong == "full_rotary")
                if cache is not None:
                    cache.setdefault("rows", []).append(jnp.concatenate([k, v], -1))
                wo = _wide(lp["wo"])
                for h in range(heads):
                    g = h // (heads // kv_heads)
                    x = fn["head"](x, q[:, h], k[:, g], v[:, g], gate[:, h],
                                   wo[h * d:(h + 1) * d], wrong != "no_output_gate")
                del q, k, v, gate
            routed, shared, gap = ffn(x, lp, cfg, held, wrong)
            x = x + routed + shared
            least_gap = jnp.minimum(least_gap, gap)
        if rows is not None:
            x, least_gap = x[jnp.asarray(rows)], least_gap[jnp.asarray(rows)]
        logits = fn["logits"](x, params["norm_f"], params["head"], centred)
        return (logits, least_gap) if gaps else logits
