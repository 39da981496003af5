"""Qwen3-Next-80B-A3B-Instruct (`model_type: qwen3_next`) on the serving
path: a HYBRID decoder, three layers of GATED DELTANET (delta-rule linear
attention with a SCALAR decay a head) to every layer of GATED grouped-query
attention of head size 256, over softmax-routed experts of which this chip
may hold a SHARE, with a shared expert weighed by the token.

Served through serving.model.ServingModel by the same engine, scheduler,
cache manager and fused chunk loop as every other model. A layer is
`x += mixer(norm(x)); x += moe(norm(x))` with the family's ZERO-CENTRED
norm, `norm(x) = x rsqrt(mean(x^2) + eps) (1 + w)` in float32
(`_decoder.rms(..., centred=True)`; the recurrent mixer's gated norm alone
has a plain weight). Layer i is full attention where `(i + 1) %
full_attention_interval == 0`, else Gated DeltaNet:

  * a GATED DELTANET layer keeps NO rows a token. What a slot carries is a
    FIXED-SIZE STATE, S (value heads, key 128, value 128) float32, and the
    last `gdn_conv - 1` pre-activation rows of q | k | v (8,192 channels
    at the published widths): one block each of the state groups `gdn` and
    `conv` (models/_recurrent.py, which Kimi-Linear's and granite's layers
    share). The mixer: [q | k | v | z] = u W_qkvz, [b | a] = u W_ba; q | k |
    v through a causal depthwise convolution of width 4 (no bias) and SiLU;
    q and k l2-normalised a KEY head (q also times d^-0.5); `beta =
    sigmoid(b)`, `g = -exp(A_log) softplus(a + dt_bias)` a VALUE head, a
    scalar; value head h reads q, k of key head h // (value heads / key
    heads);
        S' = exp(g) S;  S = S' + beta outer(k, v - S'^T k);  o = S^T q
    in float32; then `w RMSNorm_d(o) SiLU(z)` a head (the norm FIRST, then
    the gate) and W_out. No positions anywhere.
    The recurrence is models/_delta.py's, Kimi-Linear's own: a scalar
    decay is a channel decay whose channels agree and a shared key head a
    key head read twice, so the step (`_delta.step_blocks`: ops/kda_step.py
    on a TPU, `recurrence_path`) and a prompt's chunked scan (`_delta.scan`:
    ops/kda_chunk.py on a TPU in buckets of whole 128-row tiles,
    `prefill_recurrence_path`; `_delta.kda_chunked` elsewhere) take them as
    BROADCAST operands. Rows at or past `real_len` get beta = 0 and g = 0:
    the state and the history written are those AT `real_len`;
  * a GATED ATTENTION layer is models/_grouped.py's grouped-query attention
    of FULL layers alone at head size 256, whose `_project` here norms q
    and k a head (zero-centred), rotates the FIRST `rotary_dim` values of a
    head (`partial_rotary_factor`; halves paired, plain frequencies) and
    splits an OUTPUT GATE off the query projection (a head's [q | gate]):
    `y = (attn * sigmoid(gate)) W_o`. Rows in the primary cache group
    `full`, prefilled through the flash forward and decoded through the
    grouped paged kernel;
  * every layer's feed-forward is the shared expert layer
    (models/_experts.py) under its "softmax" rule (softmax over all
    outputs, the k largest over their sum), ONE shared SwiGLU weighed by
    the token (`shared_expert_combination = "token_gate"`), and
    `experts_held = (first, count)`: the routed experts this chip holds
    (None: all);
  * `vocab_size` is the rows of the embedding and of the untied head HELD
    here; `vocab_slice` (first, rows, of) names them in the published
    vocabulary.

Parameters (`x @ W`, W is (in, out); no bias): wte (V, h), head (h, V),
norm_f (h,); layers[i]: norm1, norm2 (h,), router (h, E), w_gate, w_up
(held, h, F), w_down (held, F, h), shared_gate, shared_up (h, Fs),
shared_down (Fs, h), shared_token_gate (h,); a Gated-DeltaNet layer's
w_qkvz (h, 2 nk dk + 2 nv dv: q | k | v | z), w_ba (h, 2 nv: b | a), conv_w
(K, 2 nk dk + nv dv), dt_bias, a_log (nv,) float32, gate_norm (dv,), w_out
(nv dv, h); an attention layer's wq (h, heads 2d: a head's [q | gate]), wk,
wv (h, kv_heads d), q_norm, k_norm (d,), wo (heads d, h). The published
checkpoint lays W_qkvz and W_ba out a KEY head at a time; a loader permutes
their columns once.

Named scopes: `embed`, `norm`, `gdn/project` (W_qkvz, W_ba), `gdn/conv`
(the convolution, SiLU, l2 norms, the decay's softplus, beta), `gdn/recur`
(the state's read-modify-write and `o`; a prompt's scan and the state
block's write), `gdn/gate` (the head norm, the gate, W_out), `attn/project`
(projections, q/k norms under `norm`, the rotation, W_o), `attn/full`,
`attn/gate`, `moe/*`, `head`. In-graph counters beside the expert layer's:
`gdn_state_steps` (live slots x Gated-DeltaNet layers a step),
`gdn_prefill_rows` (real rows x those layers), `gdn_prefill_chunks` (chunks
the scan visited x those layers), `decode_rows_full` (live positions x
attention layers a step) and command-a's held-pick four.
`engine.stats()["state"]` names both paths.

Refused by the engine (`serving.model.require_features`): int8 weights or
cache, adapters, speculation, a mesh plan, chunked prefill and host swap,
each with what a state group lacks for it; migration at the call; prefix
hits are off.
"""

from __future__ import annotations

from ..serving import pages as _pages
from ..serving.model import group_columns
from . import _decoder, _delta, _experts, _grouped, _recurrent

__all__ = ["Qwen3NextConfig", "init_params", "forward_logits",
           "prefill_pages", "decode_step_pages", "gdn_prompt_inputs",
           "gdn_scan", "gdn_step_inputs", "gdn_state_update",
           "recurrence_path", "prefill_recurrence_path",
           "QWEN3_NEXT_SERVING_MODEL"]

FULL, GDN, CONV = "full", "gdn", "conv"
GROUPS = (FULL, GDN, CONV)
LINEAR, ATTENTION = "linear_attention", "full_attention"


class Qwen3NextConfig:
    """The published keys under this package's names (defaults are
    Qwen3-Next-80B-A3B-Instruct's `config.json`, whole: every expert and
    the whole vocabulary held) and what the published config leaves open
    (`l2_eps`, the state's type: benchmarks/configs/qwen3-next-80b-a3b.json
    `assumed`)."""

    # what models/_experts.py reads beside the keys: its "softmax" rule
    # renormalised (`norm_topk_prob`), one shared expert weighed by the token
    router_scoring = "softmax"
    n_shared_experts = 1
    shared_expert_combination = "token_gate"

    def __init__(self, vocab_size=151936, hidden=2048, layers=48, heads=16,
                 kv_heads=2, head_dim=256, full_attention_interval=4,
                 partial_rotary_factor=0.25, rope_theta=10000000.0,
                 gdn_key_heads=16, gdn_value_heads=32, gdn_key_dim=128,
                 gdn_value_dim=128, gdn_conv=4, moe_intermediate=512,
                 shared_intermediate=512, n_routed_experts=512,
                 experts_per_tok=10, rms_eps=1e-6, experts_held=None,
                 vocab_slice=None, l2_eps=1e-6, state_dtype="float32",
                 max_pos=262144, init_range=0.02,
                 name="Qwen3-Next-80B-A3B-Instruct"):
        interval = int(full_attention_interval)
        if interval < 2 or layers < interval:
            raise ValueError(
                f"full_attention_interval {full_attention_interval} over "
                f"{layers} layers leaves no attention layer or no "
                "Gated-DeltaNet layer: the primary cache group is the "
                "attention layers' and the state groups the others'")
        if gdn_value_heads % gdn_key_heads:
            raise ValueError(f"{gdn_value_heads} value heads do not share "
                             f"{gdn_key_heads} key heads evenly")
        rotary = int(head_dim * partial_rotary_factor)
        if rotary < 2 or rotary % 2 or rotary > head_dim:
            raise ValueError(
                f"partial_rotary_factor {partial_rotary_factor} of a head of "
                f"{head_dim} rotates {rotary} values: not an even count "
                "inside the head")
        experts_held, vocab_slice = _experts.checked_share(
            experts_held, n_routed_experts, vocab_slice, vocab_size)
        self.vocab_size = vocab_size
        self.hidden = hidden
        self.layers = layers
        self.full_attention_interval = interval
        self.layer_types = tuple(
            ATTENTION if (i + 1) % interval == 0 else LINEAR
            for i in range(layers))
        self.rotary_dim = rotary
        self.rope_theta = rope_theta
        self.gdn_key_heads = gdn_key_heads
        self.gdn_value_heads = gdn_value_heads
        self.gdn_key_dim = gdn_key_dim
        self.gdn_value_dim = gdn_value_dim
        self.gdn_conv = gdn_conv
        self.moe_intermediate = moe_intermediate
        self.shared_intermediate = shared_intermediate
        self.n_routed_experts = n_routed_experts
        self.experts_per_tok = experts_per_tok
        self.rms_eps = rms_eps
        self.experts_held = experts_held
        self.vocab_slice = vocab_slice
        # ASSUMED (not keys of the published config)
        self.l2_eps = l2_eps
        self.state_dtype = state_dtype
        self.max_pos = max_pos
        self.init_range = init_range
        self.name = name
        # the attention layers as models/_grouped.py reads them: FULL layers
        # alone, the softmax scale head_dim^-0.5 (the rotation is `_project`'s)
        n_attention = self.layer_types.count(ATTENTION)
        self.attention = _grouped.GroupedConfig(
            vocab_size=vocab_size, hidden=hidden, layers=n_attention,
            heads=heads, kv_heads=kv_heads, head_dim=head_dim,
            moe_intermediate=moe_intermediate,
            n_routed_experts=n_routed_experts,
            experts_per_tok=experts_per_tok,
            layer_types=(_grouped.FULL,) * n_attention, rms_eps=rms_eps,
            rope_theta=rope_theta, max_pos=max_pos, init_range=init_range,
            name=name)

    @property
    def key_width(self):
        """Channels of q and of k: key heads x key head size."""
        return self.gdn_key_heads * self.gdn_key_dim

    @property
    def value_width(self):
        """Channels of v and of the gate z: value heads x value head size."""
        return self.gdn_value_heads * self.gdn_value_dim

    @property
    def conv_width(self):
        """Channels the convolution runs over: q | k | v."""
        return 2 * self.key_width + self.value_width

    @property
    def state_shape(self):
        """A slot's recurrent state of one layer: (value heads, key, value)."""
        return (self.gdn_value_heads, self.gdn_key_dim, self.gdn_value_dim)

    def kind(self, li):
        return self.layer_types[li]

    def index_in_group(self, li):
        """Layer li's index among the layers of its kind: its plane of
        its cache groups' arenas."""
        return self.layer_types[:li].count(self.layer_types[li])

    def cache_specs(self):
        """The three cache groups: the attention layers' rows (primary),
        the recurrent state and the convolution's history."""
        return (_grouped.specs(self.attention)[0],
                *_recurrent.specs(
                    self.layer_types.count(LINEAR), self.gdn_value_heads,
                    self.gdn_value_dim, self.state_shape, self.state_dtype,
                    self.gdn_conv - 1, self.conv_width, names=(GDN, CONV)))

    def serving_model(self):
        if self.name == QWEN3_NEXT_SERVING_MODEL.name:
            return QWEN3_NEXT_SERVING_MODEL
        return _Qwen3NextServingModel(self.name)


def init_params(cfg: Qwen3NextConfig, key, dtype):
    """Seeded random weights on the default device, one jitted maker a
    KIND of layer: normal(0, init_range) matrices (the router's and the
    token gate's vector too), the held experts' matrices alone; the
    ZERO-CENTRED norm weights uniform in +-0.5, AWAY from zero (the
    published initialiser is zeros, under which `1 + w` and the plain norm
    `w = 1` are one program and `w` for `1 + w` is no program at all), the
    gated norm's plain weight uniform in [0.5, 1.5]; the convolution's
    filters uniform in +-K^-0.5; `a_log = log(uniform(0, 16))` and
    `dt_bias` the inverse softplus of a log-uniform step in [0.001, 0.1]
    (the published initialiser, recalled: they decide how fast a seeded
    state forgets)."""
    import jax
    import jax.numpy as jnp

    h, att = cfg.hidden, cfg.attention
    nv, K, W = cfg.gdn_value_heads, cfg.gdn_conv, cfg.conv_width
    E, F = _experts.held_experts(cfg)[1], cfg.moe_intermediate
    Fs = cfg.shared_intermediate
    std = cfg.init_range

    def normal(k, shape):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    def centred(k, shape):
        return jax.random.uniform(k, shape, jnp.float32, -0.5,
                                  0.5).astype(dtype)

    mixers = {
        LINEAR: {"w_qkvz": (h, W + cfg.value_width), "w_ba": (h, 2 * nv),
                 "w_out": (cfg.value_width, h)},
        ATTENTION: {"wq": (h, att.heads * 2 * att.head_dim),
                    "wk": (h, att.kv_heads * att.head_dim),
                    "wv": (h, att.kv_heads * att.head_dim),
                    "wo": (att.heads * att.head_dim, h)}}
    ffn = {"router": (h, cfg.n_routed_experts), "w_gate": (E, h, F),
           "w_up": (E, h, F), "w_down": (E, F, h), "shared_gate": (h, Fs),
           "shared_up": (h, Fs), "shared_down": (Fs, h),
           "shared_token_gate": (h,)}

    def layer(kind, k):
        shapes = dict(mixers[kind], **ffn)
        ks = jax.random.split(k, len(shapes) + 6)
        lp = {name: normal(kk, shape)
              for (name, shape), kk in zip(shapes.items(), ks)}
        lp.update(norm1=centred(ks[-6], (h,)), norm2=centred(ks[-5], (h,)))
        if kind == LINEAR:
            bound = K ** -0.5
            lp["conv_w"] = jax.random.uniform(
                ks[-4], (K, W), jnp.float32, -bound, bound).astype(dtype)
            lp["a_log"] = jnp.log(jax.random.uniform(
                ks[-3], (nv,), jnp.float32, 1e-3, 16.0))
            dt = jnp.exp(jax.random.uniform(
                ks[-2], (nv,), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
            lp["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
            lp["gate_norm"] = jax.random.uniform(
                ks[-1], (cfg.gdn_value_dim,), jnp.float32, 0.5,
                1.5).astype(dtype)
        else:
            lp["q_norm"] = centred(ks[-4], (att.head_dim,))
            lp["k_norm"] = centred(ks[-3], (att.head_dim,))
        return lp

    def top(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"wte": normal(k1, (cfg.vocab_size, h)),
                "head": normal(k2, (h, cfg.vocab_size)),
                "norm_f": centred(k3, (h,))}

    make = {}
    keys = jax.random.split(key, cfg.layers + 1)
    params = jax.jit(top)(keys[-1])
    params["layers"] = []
    for li in range(cfg.layers):
        kind = cfg.kind(li)
        if kind not in make:
            make[kind] = jax.jit(lambda k, kind=kind: layer(kind, k))
        params["layers"].append(make[kind](keys[li]))
    return params


# -- the Gated-DeltaNet mixer's pieces ---------------------------------------------

def recurrence_path(cfg):
    """ "kernel" where the step's read-modify-write of the state is the
    Pallas kernel ops/kda_step.py (a TPU, the state float32 blocks of
    whole (128, 128) tiles), "xla" elsewhere (the CPU)."""
    if _pages.kernel_beside() and cfg.gdn_key_dim % _pages.LANES == 0 \
            and cfg.gdn_value_dim % _pages.LANES == 0 \
            and cfg.state_dtype == "float32":
        return "kernel"
    return "xla"


def prefill_recurrence_path(cfg, bucket=None):
    """ "kernel" where a prompt's chunked scan is the Pallas kernel
    ops/kda_chunk.py: the step's rule, a bucket of whole 128-row tiles
    (None: the verdict for such buckets) and value heads in pairs; "xla"
    (`_delta.kda_chunked`, plain `jax.numpy`) elsewhere: the CPU, odd
    widths."""
    if recurrence_path(cfg) == "kernel" and cfg.gdn_value_heads % 2 == 0 \
            and _pages.kernel_beside(bucket=bucket):
        return "kernel"
    return "xla"


def _gdn_project(cfg, lp, u):
    """`gdn/project`: q | k | v before the convolution (T, 2 nk dk + nv
    dv), the gate's z (T, nv dv), beta's b and the decay's a (T, nv)."""
    import jax
    nv = cfg.gdn_value_heads
    with jax.named_scope("gdn/project"):
        qkvz = u @ lp["w_qkvz"]
        ba = u @ lp["w_ba"]
    return (qkvz[:, :cfg.conv_width], qkvz[:, cfg.conv_width:], ba[:, :nv],
            ba[:, nv:])


def _gdn_activate(cfg, lp, conv, b, a):
    """The rest of `gdn/conv` behind the convolution's sum `conv` (T, 2 nk
    dk + nv dv) float32: SiLU, the split, the l2 norms a KEY head, each key
    head's q, k handed to its value heads, the decay a value head over its
    key channels, beta, all float32: q, k, g (T, nv, dk), v (T, nv, dv),
    beta (T, nv)."""
    import jax
    import jax.numpy as jnp
    T = conv.shape[0]
    nk, nv, dk = cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim
    kw = cfg.key_width
    act = jax.nn.silu(conv)
    q = act[:, :kw].reshape(T, nk, dk)
    k = act[:, kw:2 * kw].reshape(T, nk, dk)
    v = act[:, 2 * kw:].reshape(T, nv, cfg.gdn_value_dim)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + cfg.l2_eps) \
        * dk ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + cfg.l2_eps)
    # value head h reads key head h // (nv / nk)
    q, k = jnp.repeat(q, nv // nk, 1), jnp.repeat(k, nv // nk, 1)
    g = -jnp.exp(lp["a_log"]) * jax.nn.softplus(
        a.astype(jnp.float32) + lp["dt_bias"])
    beta = jax.nn.sigmoid(b.astype(jnp.float32))
    return q, k, v, jnp.broadcast_to(g[..., None], (T, nv, dk)), beta


def _gdn_gate(cfg, lp, o, z):
    """`gdn/gate`: the head norm of o (T, nv, dv) float32 under its plain
    weight FIRST, then the gate SiLU(z), z (T, nv dv), then `W_out`."""
    import jax
    import jax.numpy as jnp
    T = o.shape[0]
    with jax.named_scope("gdn/gate"):
        y = _decoder.rms(o, lp["gate_norm"], cfg.rms_eps)
        y = y * jax.nn.silu(z.astype(jnp.float32)).reshape(o.shape)
        return y.reshape(T, -1).astype(z.dtype) @ lp["w_out"]


def gdn_prompt_inputs(cfg, lp, u, real_len):
    """`gdn/project` and `gdn/conv` of a PROMPT: for one sequence's rows u
    (B, h), `real_len` of them real, the scan's operands q, k, g (B, nv,
    dk), v (B, nv, dv), beta (B, nv), float32, g and beta 0 at and past
    `real_len`, the gate's z (B, nv dv) and the history at `real_len` (K -
    1, 2 nk dk + nv dv). Returns (q, k, v, g, beta, z, hist). A prefill
    runs THIS and then `gdn_scan`; so does the scan limit of the cell
    `qwen3-next-longmix-offline`
    (benchmarks/modes/serve-closed-qwen3-next.py)."""
    import jax
    import jax.numpy as jnp
    qkv, z, b, a = _gdn_project(cfg, lp, u)
    with jax.named_scope("gdn/conv"):
        summed, hist = _recurrent.conv_prompt(qkv, lp["conv_w"], real_len)
        q, k, v, g, beta = _gdn_activate(cfg, lp, summed, b, a)
        live = jnp.arange(u.shape[0]) < real_len
        g = jnp.where(live[:, None, None], g, 0.0)
        beta = jnp.where(live[:, None], beta, 0.0)
    return q, k, v, g, beta, z, hist


def gdn_scan(q, k, v, g, beta, real_len, path):
    """`gdn/recur` of a PROMPT: `gdn_prompt_inputs`' operands through the
    recurrence from a zero state, by the kernel ops/kda_chunk.py or by
    `_delta.kda_chunked` (`path`: `prefill_recurrence_path`). Returns (o
    (B, nv, dv) float32, S (nv, dk, dv) at `real_len`, the chunks the scan
    visited). A prefill runs THIS; so does the scan limit of the cell
    `qwen3-next-longmix-offline`."""
    import jax
    with jax.named_scope("gdn/recur"):
        return _delta.scan(q, k, v, g, beta, real_len, path)


def _gdn_prompt(cfg, lp, u, real_len, path):
    """A Gated-DeltaNet layer's mixer over ONE sequence's rows u (B, h),
    `real_len` of them real, from a zero state, the scan by `path`
    (`prefill_recurrence_path`): (the mixer's output (B, h), the state S
    at `real_len` (nv, dk, dv) float32, the history at `real_len`, the
    chunks the scan visited)."""
    q, k, v, g, beta, z, hist = gdn_prompt_inputs(cfg, lp, u, real_len)
    o, S, visited = gdn_scan(q, k, v, g, beta, real_len, path)
    return _gdn_gate(cfg, lp, o, z), S, hist, visited


def gdn_step_inputs(cfg, lp, u, arenas, lg, conv_ids, done):
    """`gdn/project` and `gdn/conv` of a step: for every slot's row u (S,
    h) the recurrence's operands q, k, g (S, nv, dk), v (S, nv, dv), beta
    (S, nv), all float32, and the gate's z (S, nv dv), the convolution
    taken over the slot's history, block `conv_ids` (S,) of layer `lg` of
    its arena, which moves one row on (a frozen slot's to scratch).
    Returns (q, k, v, g, beta, z, arenas)."""
    import jax
    qkv, z, b, a = _gdn_project(cfg, lp, u)
    with jax.named_scope("gdn/conv"):
        summed, arenas[CONV] = _recurrent.conv_step(
            arenas[CONV], lg, conv_ids, done, qkv, lp["conv_w"])
        q, k, v, g, beta = _gdn_activate(cfg, lp, summed, b, a)
    return q, k, v, g, beta, z, arenas


def gdn_state_update(arenas, lg, state_ids, done, q, k, v, g, beta, path):
    """`gdn/recur` of a step: every slot's state, block `state_ids` (S,)
    of layer `lg` of its arena, read, moved one position on and written
    ONCE (a frozen slot's to scratch), by the kernel ops/kda_step.py or by
    XLA's gather-update-scatter (`path`: `recurrence_path`). Returns (o
    (S, nv, dv) float32, arenas). The served step runs THIS; so does the
    numeric check of the cell `qwen3-next-longmix-offline`, on the
    engine's own blocks (benchmarks/modes/serve-closed-qwen3-next.py)."""
    import jax
    with jax.named_scope("gdn/recur"):
        o, arenas[GDN] = _delta.step_blocks(
            arenas[GDN], lg, state_ids, done, q, k, v, g, beta, path)
    return o, arenas


def _zero_counters(cfg):
    import jax.numpy as jnp
    zero = jnp.zeros((), jnp.int32)
    return dict(_experts.zero_counters(cfg), gdn_state_steps=zero,
                gdn_prefill_rows=zero, gdn_prefill_chunks=zero,
                decode_rows_full=zero)


# -- the block's other pieces ------------------------------------------------------

def _norm(cfg, x, w):
    """The stage `norm`: the family's zero-centred RMS norm."""
    import jax
    with jax.named_scope("norm"):
        return _decoder.rms(x, w, cfg.rms_eps, centred=True)


def _project(cfg, lp, u, pos):
    """q (T, heads, d), k, v (T, kv_heads, d) and the output gate (T, heads
    d) of normed tokens u (T, h) at positions pos (T,): no bias; the query
    projection is a head's [q | gate]; q and k normed over a head's d values
    (zero-centred), then rotated on their FIRST `rotary_dim` values in
    halves at plain frequencies of that width, the rest passed."""
    import jax.numpy as jnp
    att, T, r = cfg.attention, u.shape[0], cfg.rotary_dim
    d = att.head_dim
    qg = (u @ lp["wq"]).reshape(T, att.heads, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = (u @ lp["wk"]).reshape(T, att.kv_heads, d)
    v = (u @ lp["wv"]).reshape(T, att.kv_heads, d)
    q, k = _norm(cfg, q, lp["q_norm"]), _norm(cfg, k, lp["k_norm"])

    def rotated(x):
        turned = _decoder.rope(x[..., :r], pos[:, None], cfg.rope_theta,
                               None, interleaved=False)
        return jnp.concatenate([turned, x[..., r:]], -1)

    return rotated(q), rotated(k), v, gate.reshape(T, -1)


def _gated_out(lp, o, gate):
    """`attn/gate` and the output projection: o (T, heads, d) times
    sigmoid(gate) (T, heads d) in float32, then `W_o`."""
    import jax
    import jax.numpy as jnp
    T = o.shape[0]
    with jax.named_scope("attn/gate"):
        y = (o.reshape(T, -1).astype(jnp.float32)
             * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(gate.dtype)
    with jax.named_scope("attn/project"):
        return y @ lp["wo"]


def _ffn(cfg, lp, x, live, counters):
    """x + moe(norm(x)); the token's gate on the shared expert is the
    expert layer's."""
    y, counters = _experts.experts(cfg, lp, _norm(cfg, x, lp["norm2"]), live,
                                   counters)
    return x + y, counters


def _head(cfg, params, x):
    """The stage `head`: the final zero-centred norm and the untied head;
    logits float32."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope("head"):
        y = _decoder.rms(x, params["norm_f"], cfg.rms_eps, centred=True)
        return jnp.dot(y, params["head"], preferred_element_type=jnp.float32)


# -- the whole sequence, no cache (tests; generation never runs it) --------------

def forward_logits(params, cfg, tokens):
    """Logits (T, V) float32 of one sequence tokens (T,): the served math
    without a cache (the chunked scan, masked attention)."""
    import jax.numpy as jnp
    T = tokens.shape[0]
    pos = jnp.arange(T)
    x = _decoder.embed(params, tokens, _decoder.act_dtype(params))
    counters = _zero_counters(cfg)
    live = jnp.ones((T,), bool)
    for li, lp in enumerate(params["layers"]):
        u = _norm(cfg, x, lp["norm1"])
        if cfg.kind(li) == LINEAR:
            # the oracle, never the kernel it is held against
            y = _gdn_prompt(cfg, lp, u, T, "xla")[0]
        else:
            q, k, v, gate = _project(cfg, lp, u, pos)
            o = _grouped.attend_rows(cfg.attention, q, k, v, "full", False)
            y = _gated_out(lp, o, gate)
        x, counters = _ffn(cfg, lp, x + y, live, counters)
    return _head(cfg, params, x)


# -- prefill into the pages and the state blocks ---------------------------------

def prefill_pages(params, cfg, tokens, pfx_len, real_len, arena, pages):
    """Prefill ONE sequence's COLD prompt tokens (1, B) (`pfx_len` is 0: a
    model with state groups takes no prefix hits): the attention layers'
    rows as whole pages of the primary group's columns, each
    Gated-DeltaNet layer's state and history AT `real_len` into the slot's
    blocks of the state groups (written whole, never read). Returns
    (logits (1, V) float32 of position real_len - 1, arena, counters)."""
    import jax
    import jax.numpy as jnp

    arenas = _recurrent.by_name(GROUPS, arena)
    B = tokens.shape[1]
    bs = arenas[FULL].shape[4]
    dtype = arenas[FULL].dtype
    cols = group_columns(cfg.cache_specs(), pages.shape[0], bs)
    rows = pages[cols[0]]
    state_id, conv_id = _recurrent.block_ids(pages, cols[1:])
    flash = _grouped.prefill_attention_path(arenas[FULL], B) == "flash"
    recurrence = prefill_recurrence_path(cfg, B)
    _delta.note_prefill(cfg, B, recurrence)
    j = jnp.arange(B)
    live = j < real_len
    x = _decoder.embed(params, tokens[0], dtype)
    counters = _zero_counters(cfg)
    chunks = 0
    for li, lp in enumerate(params["layers"]):
        lg = cfg.index_in_group(li)
        u = _norm(cfg, x, lp["norm1"])
        if cfg.kind(li) == LINEAR:
            y, S, hist, visited = _gdn_prompt(cfg, lp, u, real_len,
                                              recurrence)
            chunks = chunks + visited
            with jax.named_scope("gdn/recur"):
                arenas[GDN] = _recurrent.write_block(arenas[GDN], lg,
                                                     state_id, S)
            with jax.named_scope("gdn/conv"):
                arenas[CONV] = _recurrent.write_block(arenas[CONV], lg,
                                                      conv_id, hist)
        else:
            with jax.named_scope("attn/project"):
                q, k, v, gate = _project(cfg, lp, u, pfx_len + j)
                kv = jnp.concatenate([k, v], -1).astype(dtype)
                arenas[FULL] = _grouped.write_prompt(
                    arenas[FULL], lg, rows, pfx_len, real_len, kv, "full")
            with jax.named_scope("attn/full"):
                o = _grouped.attend_rows(cfg.attention, q, k, v, "full",
                                         flash, real_len)
            y = _gated_out(lp, o, gate)
        x, counters = _ffn(cfg, lp, x + y, live, counters)
    n_linear = cfg.layer_types.count(LINEAR)
    counters["gdn_prefill_rows"] = (real_len * n_linear).astype(jnp.int32)
    counters["gdn_prefill_chunks"] = jnp.asarray(chunks, jnp.int32)
    last = x[real_len - 1][None]
    return (_head(cfg, params, last), _recurrent.in_order(GROUPS, arenas),
            counters)


# -- decode through the pages and the state blocks --------------------------------

def decode_attention_path(arena, arena_constraint=None):
    """{group: path} of the attention layers' group: models/_grouped.py's
    verdict on ITS arena (the state groups attend nothing)."""
    return _grouped.decode_attention_path(arena[0], arena_constraint)


def decode_step_pages(params, cfg, tokens, arena, pt, ts, done=None,
                      attention=None, recurrence=None):
    """One decode step of every slot: tokens, ts (S,), pt (S, P + 2). An
    attention layer writes each live slot's row at ts and attends over
    0..ts; a Gated-DeltaNet layer reads, updates and writes each live
    slot's history and state block ONCE. A frozen slot's writes reach
    scratch block 0 alone. Returns (logits (S, V) float32, arena,
    counters)."""
    import jax
    import jax.numpy as jnp

    arenas = _recurrent.by_name(GROUPS, arena)
    s_dim = pt.shape[0]
    bs = arenas[FULL].shape[4]
    dtype = arenas[FULL].dtype
    cols = group_columns(cfg.cache_specs(), pt.shape[1], bs)
    table = pt[:, cols[0]]
    state_ids, conv_ids = _recurrent.block_ids(pt, cols[1:])
    if attention is None:
        attention = decode_attention_path(arena)
    if recurrence is None:
        recurrence = recurrence_path(cfg)
    live = jnp.ones((s_dim,), bool) if done is None else ~done
    lo = jnp.zeros_like(ts)
    x = _decoder.embed(params, tokens, dtype)
    counters = _zero_counters(cfg)
    for li, lp in enumerate(params["layers"]):
        lg = cfg.index_in_group(li)
        u = _norm(cfg, x, lp["norm1"])
        if cfg.kind(li) == LINEAR:
            q, k, v, g, beta, z, arenas = gdn_step_inputs(
                cfg, lp, u, arenas, lg, conv_ids, done)
            o, arenas = gdn_state_update(arenas, lg, state_ids, done, q, k,
                                         v, g, beta, recurrence)
            y = _gdn_gate(cfg, lp, o, z)
        else:
            with jax.named_scope("attn/project"):
                q, k, v, gate = _project(cfg, lp, u, ts)
            with jax.named_scope("attn/full"):
                o, arenas[FULL] = _grouped.attend_step(
                    cfg.attention, q, k, v, arenas[FULL], lg, table, ts,
                    done, lo, "full", attention["full"])
            y = _gated_out(lp, o.astype(dtype), gate)
        x, counters = _ffn(cfg, lp, x + y.astype(dtype), live, counters)
    n_live = jnp.sum(live).astype(jnp.int32)
    counters["gdn_state_steps"] = n_live * cfg.layer_types.count(LINEAR)
    counters["decode_rows_full"] = (
        jnp.sum(jnp.where(live, ts + 1, 0)).astype(jnp.int32)
        * cfg.layer_types.count(ATTENTION))
    return (_head(cfg, params, x), _recurrent.in_order(GROUPS, arenas),
            counters)


# -- the engine's view of this model ---------------------------------------------

class _Qwen3NextServingModel(_experts.ExpertBlockModel):
    prefill_pages = staticmethod(prefill_pages)
    decode_step_pages = staticmethod(decode_step_pages)

    own_counters = ("gdn_state_steps", "gdn_prefill_rows",
                    "gdn_prefill_chunks", "decode_rows_full",
                    "moe_picks_routed", "moe_picks_held",
                    "decode_moe_picks_routed", "decode_moe_picks_held")

    def cache_spec(self, cfg):
        return cfg.cache_specs()

    def decode_attention_path(self, arena, arena_constraint=None):
        return decode_attention_path(arena, arena_constraint)

    def prefill_attention_path(self, arena, bucket, arena_constraint=None):
        return _grouped.prefill_attention_path(arena[0], bucket,
                                               arena_constraint)

    def describe(self, cfg):
        first, count = _experts.held_experts(cfg)
        prefill_path, kernel_buckets = _delta.prefill_paths_taken(
            cfg, prefill_recurrence_path(cfg))
        return {"experts_held": {"first": first, "count": count,
                                 "of": cfg.n_routed_experts},
                "vocab_slice": dict(zip(("first", "rows", "of"),
                                        cfg.vocab_slice)),
                "state": {"recurrence_path": recurrence_path(cfg),
                          "prefill_recurrence_path": prefill_path,
                          "prefill_kernel_buckets": kernel_buckets,
                          "prefill_chunk_rows": _delta.KDA_CHUNK}}

    def _counters(self, cfg, c, decode):
        import jax.numpy as jnp
        routed = c["router_tokens"] * cfg.experts_per_tok
        held = jnp.sum(c["expert_tokens"]).astype(jnp.int32)
        return super()._counters(cfg, dict(
            c, moe_picks_routed=routed, moe_picks_held=held,
            decode_moe_picks_routed=routed, decode_moe_picks_held=held),
            decode)


QWEN3_NEXT_SERVING_MODEL = _Qwen3NextServingModel(
    "Qwen3-Next-80B-A3B-Instruct")
