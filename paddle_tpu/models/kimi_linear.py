"""Kimi-Linear-48B-A3B-Instruct (`model_type: kimi_linear`) on the serving
path: a HYBRID decoder, three layers of delta-rule LINEAR attention (KDA)
to every layer of position-free LATENT attention, over sigmoid-routed
experts of which this chip may hold a SHARE.

Served through serving.model.ServingModel by the same engine, scheduler,
cache manager and fused chunk loop as every other model. A layer is
`x += mixer(RMSNorm(x)); x += ffn(RMSNorm(x))`, and its kind is read from
the two published lists (`kda_layers`, `full_attn_layers`, 1-indexed):

  * a KDA layer keeps NO rows a token. What a slot carries is a FIXED-SIZE
    STATE: S (heads, key 128, value 128) float32 and the last
    `short_conv_kernel_size - 1` pre-activation rows of q|k|v (the
    convolution's history). Both are STATE GROUPS of the one cache manager
    (serving/model.py `CacheSpec.state`): a slot's state of one layer is
    one block of the group `state` (float32 beside the bfloat16 latent
    arena) and its history one block of the group `conv` (the arena's
    type), each one column of the page table, handed out at admission and
    taken back at retirement with the pages. Two groups and not one,
    because the two differ in type and in shape and a block has one of
    each: the history in the state's block would be 72 KB of bfloat16
    riding as float32 in two more "heads".
    The mixer (arXiv:2510.26692, "Kimi Delta Attention"): q|k|v = u Wqkv;
    a causal depthwise convolution of width 4 and SiLU, a filter a
    channel; q and k l2-normalised a head (q also times d^-0.5); a decay a
    head and KEY CHANNEL in log space, `g = -exp(A_log) softplus((u
    Wa_down) Wa_up + dt_bias)`; `beta = sigmoid(u Wb)`; the recurrence
        S' = exp(g)[:, None] S;  S = S' + beta outer(k, v - S'^T k);
        o = S^T q
    in float32; then `RMSNorm_d(o) sigmoid((u Wg_down) Wg_up)` a head and
    `Wo`. No positions anywhere.
    Its two programs (the recurrence itself is models/_delta.py's, which
    models/qwen3_next.py shares): the RECURRENT STEP (`kda_step`: one position a slot,
    ONE read-modify-write of the slot's state block; a frozen slot's write
    goes to scratch block 0) and the CHUNKED PREFILL (`kda_chunked`: the
    same recurrence over a prompt in chunks of `KDA_CHUNK` rows, solved
    inside a chunk through the cumulative decay and the unit-lower-
    triangular system of the delta rule, carried between chunks by a
    `lax.scan`; algebraically the recurrence, to float32 rounding. No
    positive exponent is ever formed: decays are taken against a point
    inside the chunk, sub-chunk by sub-chunk of 16 rows, and elementwise
    inside a sub-chunk. Rows at or past `real_len` get beta = 0 and g = 0,
    so the state and the history written are those AT `real_len`, not at
    the bucket's end). On a TPU, in buckets of whole 128-row tiles, the
    chunked form runs as ONE Pallas call a layer, ops/kda_chunk.py
    (`prefill_recurrence_path`): a chunk's Gram matrices, its triangular
    system, the three state products and the state's carry stay in VMEM,
    and a chunk wholly past `real_len` is passed by; `kda_chunked` in
    `jax.numpy` is the CPU's path, odd widths' and the tests' oracle;
  * a latent layer is models/_latent.py's (Moonlight's) with `mla_use_nope`:
    nothing is rotated; the cached row stays 576 values in 640 lanes and
    the flash forward and the latent paged kernel run as they are. Its
    group `latent` is the primary one;
  * layer 1 is a dense SwiGLU, every other layer the shared expert layer
    (models/_experts.py) with Moonlight's `route` (sigmoid, correction
    bias, renormalised, times `routed_scaling_factor`), ONE shared expert,
    and `experts_held = (first, count)`: the routed experts this chip
    holds (None: all);
  * `vocab_size` is the rows of the embedding and of the head HELD here;
    `vocab_slice` (first, rows, of) names them in the published
    vocabulary.

Parameters (`x @ W`, W is (in, out); no bias): wte (V, h), head (h, V),
norm_f (h,), layers[i]: norm1, norm2 (h,) and the feed-forward's as
models/moonlight.py lists them; a KDA layer's wqkv (h, 3 n d), conv_w (4,
3 n d), wa_down (h, r), wa_up (r, n d), dt_bias (n d,) float32, a_log (n,)
float32, wb (h, n), wg_down (h, r), wg_up (r, n d), o_norm (d,), wo (n d,
h); a latent layer's wq, wkva, kv_norm, wkvb, wo.

Named scopes: `embed`, `norm`, `kda/project` (q|k|v, the two low-rank
pairs, beta), `kda/conv` (convolution, SiLU, l2 norms, the decay's
softplus), `kda/recur` (the state's read-modify-write and `o`; the chunked
scan in a prefill, `kda_chunk` in a trace where it is the kernel),
`kda/gate` (the head norm, sigmoid gate, `Wo`), `mla/*`, `moe/*`,
`ffn/dense`, `head`. In-graph counters beside the expert layer's:
`kda_state_steps` (live slots x KDA layers a step), `kda_prefill_rows`
(real rows x KDA layers), `kda_prefill_chunks` (chunks the scan visited x
KDA layers: `kda_prefill_rows / (64 kda_prefill_chunks)` is the share of
visited rows that were real; the `jax.numpy` form visits every chunk of
the bucket), `mla_decode_rows` (live positions x latent layers a step),
and command-a's held-pick counters. `engine.stats()["state"]` names both
paths, `recurrence_path` (the step's) and `prefill_recurrence_path` (what the
prefills TRACED so far took, `prefill_kernel_buckets` the buckets in which
that was the kernel; the rule's word before any is traced).

Refused by the engine (`serving.model.require_features`): int8 weights or
cache, adapters, speculation, a mesh plan, chunked prefill and host swap,
each with what a state group lacks for it; migration at the call; prefix
hits are off.
"""

from __future__ import annotations

from ..serving import pages as _pages
from ..serving.model import CacheSpec, group_columns
from . import _decoder, _delta, _experts, _latent, _recurrent

__all__ = ["KimiLinearConfig", "init_params", "forward_logits",
           "prefill_pages", "decode_step_pages", "kda_step_inputs",
           "kda_state_update", "recurrence_path", "prefill_recurrence_path",
           "KIMI_LINEAR_SERVING_MODEL"]

LATENT, STATE, CONV = "latent", _recurrent.STATE, _recurrent.CONV
GROUPS = (LATENT, STATE, CONV)


def _published_kinds(layers):
    """Kimi-Linear's lists at a depth of `layers`: a period is three KDA
    layers and a latent one, and the LAST layer is latent whatever the
    period says (27: the published `full_attn_layers` end in 24, 27)."""
    full = [i for i in range(1, layers + 1) if i % 4 == 0]
    if layers == 27:
        full.append(27)
    return [i for i in range(1, layers + 1) if i not in full], full


class KimiLinearConfig:
    """The published keys under this package's names (defaults are
    Kimi-Linear-48B-A3B-Instruct's `config.json`, whole: every expert and
    the whole vocabulary held) and what the published text leaves open
    (`kda_decay_rank`, `kda_gate_rank`, `l2_eps`, the state's type:
    benchmarks/configs/kimi-linear-48b-a3b.json `assumed`)."""

    # what models/_latent.py and models/_experts.py read beside the keys
    q_lora_rank = None
    rope_scaling = None
    rope_theta = 10000.0          # never read: `mla_use_nope`
    mla_use_nope = True
    router_scoring = "sigmoid"

    def __init__(self, vocab_size=163840, hidden=2304, layers=27, heads=32,
                 kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                 v_head_dim=128, kda_heads=32, kda_head_dim=128,
                 short_conv_kernel_size=4, kda_layers=None,
                 full_attn_layers=None, intermediate=9216,
                 moe_intermediate=1024, n_routed_experts=256,
                 n_shared_experts=1, experts_per_tok=8, first_k_dense=1,
                 routed_scaling_factor=2.446, rms_eps=1e-5,
                 experts_held=None, vocab_slice=None, kda_decay_rank=None,
                 kda_gate_rank=None, l2_eps=1e-6, state_dtype="float32",
                 max_pos=1048576, init_range=0.02,
                 name="Kimi-Linear-48B-A3B-Instruct"):
        if kda_layers is None and full_attn_layers is None:
            kda_layers, full_attn_layers = _published_kinds(layers)
        kda_layers = tuple(int(i) for i in kda_layers)
        full_attn_layers = tuple(int(i) for i in full_attn_layers)
        if sorted(kda_layers + full_attn_layers) != \
                list(range(1, layers + 1)):
            raise ValueError(
                f"kda_layers {kda_layers} and full_attn_layers "
                f"{full_attn_layers} do not name each of {layers} layers "
                "once (1-indexed, as published)")
        experts_held, vocab_slice = _experts.checked_share(
            experts_held, n_routed_experts, vocab_slice, vocab_size)
        self.vocab_size = vocab_size
        self.hidden = hidden
        self.layers = layers
        self.heads = heads
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.kda_heads = kda_heads
        self.kda_head_dim = kda_head_dim
        self.short_conv_kernel_size = short_conv_kernel_size
        self.kda_layers = kda_layers
        self.full_attn_layers = full_attn_layers
        self.intermediate = intermediate
        self.moe_intermediate = moe_intermediate
        self.n_routed_experts = n_routed_experts
        self.n_shared_experts = n_shared_experts
        self.experts_per_tok = experts_per_tok
        self.first_k_dense = first_k_dense
        self.routed_scaling_factor = routed_scaling_factor
        self.rms_eps = rms_eps
        self.experts_held = experts_held
        self.vocab_slice = vocab_slice
        # ASSUMED (not keys of the published config): the two low-rank
        # pairs' rank (None: the head size), the l2 norm's eps, the
        # state's type
        self.kda_decay_rank = kda_decay_rank or kda_head_dim
        self.kda_gate_rank = kda_gate_rank or kda_head_dim
        self.l2_eps = l2_eps
        self.state_dtype = state_dtype
        self.max_pos = max_pos
        self.init_range = init_range
        self.name = name

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def row_values(self):
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def row_width(self):
        return -(-self.row_values // _pages.LANES) * _pages.LANES

    @property
    def kda_width(self):
        """Channels of q, of k and of v: heads x head size."""
        return self.kda_heads * self.kda_head_dim

    def kind(self, li):
        """"kda" or "mla": layer li's mixer (li from 0; the published
        lists count from 1)."""
        return "kda" if li + 1 in self.kda_layers else "mla"

    def index_in_group(self, li):
        """Layer li's index among the layers of its kind: its plane of
        its cache group's arena."""
        same = self.kda_layers if self.kind(li) == "kda" \
            else self.full_attn_layers
        return same.index(li + 1)

    @property
    def state_shape(self):
        """A slot's recurrent state of one layer: (heads, key, value)."""
        return (self.kda_heads, self.kda_head_dim, self.kda_head_dim)

    @property
    def conv_shape(self):
        """A slot's convolution history of one layer, the last
        `short_conv_kernel_size - 1` pre-activation rows of q|k|v, as
        the block stores it (288 x 128 at the published widths)."""
        return _recurrent.history_shape(self.short_conv_kernel_size - 1,
                                        3 * self.kda_width)

    def cache_specs(self):
        """The three cache groups: the latent rows (primary), the
        recurrent state and the convolution's history (state groups)."""
        return (CacheSpec(len(self.full_attn_layers), 1, self.row_width,
                          name=LATENT),
                *_recurrent.specs(
                    len(self.kda_layers), self.kda_heads, self.kda_head_dim,
                    self.state_shape, self.state_dtype,
                    self.short_conv_kernel_size - 1, 3 * self.kda_width))

    def serving_model(self):
        if self.name == KIMI_LINEAR_SERVING_MODEL.name:
            return KIMI_LINEAR_SERVING_MODEL
        return _KimiLinearServingModel(self.name)


def init_params(cfg: KimiLinearConfig, key, dtype):
    """Seeded random weights on the default device, one jitted maker a
    KIND of layer (mixer x feed-forward): normal(0, init_range) matrices,
    unit norms, a small non-zero router correction bias, the held
    experts' matrices alone; the convolution's filters uniform in
    +-k^-0.5 (a filter of normal(0, 0.02) would leave v, which no norm
    rescales, at nothing); `a_log` so that exp(a_log) is uniform in [1,
    16] and `dt_bias` the inverse softplus of a log-uniform step in
    [0.001, 0.1] (the family's usual initialisation: they decide how fast
    a seeded state forgets)."""
    import jax
    import jax.numpy as jnp

    h, n, d = cfg.hidden, cfg.kda_heads, cfg.kda_head_dim
    C = cfg.kda_width
    E, F = _experts.held_experts(cfg)[1], cfg.moe_intermediate
    Fs = cfg.n_shared_experts * F
    K = cfg.short_conv_kernel_size
    std = cfg.init_range

    def normal(k, shape):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    mixers = {
        "kda": {"wqkv": (h, 3 * C), "wa_down": (h, cfg.kda_decay_rank),
                "wa_up": (cfg.kda_decay_rank, C), "wb": (h, n),
                "wg_down": (h, cfg.kda_gate_rank),
                "wg_up": (cfg.kda_gate_rank, C), "wo": (C, h)},
        "mla": {"wq": (h, cfg.heads * cfg.qk_head_dim),
                "wkva": (h, cfg.row_values),
                "wkvb": (cfg.kv_lora_rank,
                         cfg.heads * (cfg.qk_nope_head_dim
                                      + cfg.v_head_dim)),
                "wo": (cfg.heads * cfg.v_head_dim, h)}}
    ffns = {False: {"gate": (h, cfg.intermediate),
                    "up": (h, cfg.intermediate),
                    "down": (cfg.intermediate, h)},
            True: {"router": (h, cfg.n_routed_experts),
                   "w_gate": (E, h, F), "w_up": (E, h, F),
                   "w_down": (E, F, h), "shared_gate": (h, Fs),
                   "shared_up": (h, Fs), "shared_down": (Fs, h)}}

    def layer(kind, routed, k):
        shapes = dict(mixers[kind], **ffns[routed])
        ks = jax.random.split(k, len(shapes) + 4)
        lp = {name: normal(kk, shape)
              for (name, shape), kk in zip(shapes.items(), ks)}
        lp.update(norm1=jnp.ones((h,), dtype), norm2=jnp.ones((h,), dtype))
        if kind == "mla":
            lp["kv_norm"] = jnp.ones((cfg.kv_lora_rank,), dtype)
        else:
            bound = K ** -0.5
            lp["conv_w"] = jax.random.uniform(
                ks[-4], (K, 3 * C), jnp.float32, -bound, bound).astype(dtype)
            lp["a_log"] = jnp.log(jax.random.uniform(
                ks[-3], (n,), jnp.float32, 1.0, 16.0))
            dt = jnp.exp(jax.random.uniform(
                ks[-2], (C,), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
            lp["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
            lp["o_norm"] = jnp.ones((d,), dtype)
        if routed:
            lp["router_bias"] = 0.01 * jax.random.normal(
                ks[-1], (cfg.n_routed_experts,), jnp.float32)
        return lp

    def top(k):
        k1, k2 = jax.random.split(k)
        return {"wte": normal(k1, (cfg.vocab_size, h)),
                "head": normal(k2, (h, cfg.vocab_size)),
                "norm_f": jnp.ones((h,), dtype)}

    make = {}
    keys = jax.random.split(key, cfg.layers + 1)
    params = jax.jit(top)(keys[-1])
    params["layers"] = []
    for li in range(cfg.layers):
        which = (cfg.kind(li), li >= cfg.first_k_dense)
        if which not in make:
            make[which] = jax.jit(
                lambda k, which=which: layer(*which, k))
        params["layers"].append(make[which](keys[li]))
    return params


# -- the KDA mixer's pieces ------------------------------------------------------

def recurrence_path(cfg):
    """ "kernel" where the step's read-modify-write of the state is the
    Pallas kernel ops/kda_step.py (a TPU, the state float32 blocks of
    whole (128, 128) tiles), "xla" elsewhere (the CPU)."""
    if _pages.kernel_beside() and cfg.kda_head_dim % _pages.LANES == 0 \
            and cfg.state_dtype == "float32":
        return "kernel"
    return "xla"


def prefill_recurrence_path(cfg, bucket=None):
    """ "kernel" where a prompt's chunked scan is the Pallas kernel
    ops/kda_chunk.py: the step's rule, a bucket of whole 128-row tiles
    (None: the verdict for such buckets) and heads in pairs; "xla"
    (`kda_chunked`, plain `jax.numpy`) elsewhere: the CPU, odd widths."""
    if recurrence_path(cfg) == "kernel" and cfg.kda_heads % 2 == 0 \
            and _pages.kernel_beside(bucket=bucket):
        return "kernel"
    return "xla"


def _kda_project(cfg, lp, u):
    """`kda/project`: q|k|v before the convolution (T, 3C), the decay's
    pre-activation a (T, C), beta's b (T, n) and the gate's z (T, C)."""
    import jax
    with jax.named_scope("kda/project"):
        qkv = u @ lp["wqkv"]
        a = (u @ lp["wa_down"]) @ lp["wa_up"]
        b = u @ lp["wb"]
        z = (u @ lp["wg_down"]) @ lp["wg_up"]
    return qkv, a, b, z


def _kda_activate(cfg, lp, conv, a, b):
    """The rest of `kda/conv` behind the convolution's sum `conv` (T, 3C)
    float32: SiLU, the split, the l2 norms, the decay and beta, all
    float32: q, k, v, g (T, n, d), beta (T, n)."""
    import jax
    import jax.numpy as jnp
    T = conv.shape[0]
    n, d = cfg.kda_heads, cfg.kda_head_dim
    act = jax.nn.silu(conv)
    q, k, v = (part.reshape(T, n, d) for part in jnp.split(act, 3, -1))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + cfg.l2_eps) \
        * d ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + cfg.l2_eps)
    g = -jnp.exp(lp["a_log"])[None, :, None] * jax.nn.softplus(
        a.astype(jnp.float32) + lp["dt_bias"]).reshape(T, n, d)
    beta = jax.nn.sigmoid(b.astype(jnp.float32))
    return q, k, v, g, beta


def _kda_gate(cfg, lp, o, z):
    """`kda/gate`: the head norm of o (T, n, d) float32, the sigmoid
    gate z (T, C), `Wo`."""
    import jax
    import jax.numpy as jnp
    T = o.shape[0]
    with jax.named_scope("kda/gate"):
        y = _decoder.rms(o, lp["o_norm"], cfg.rms_eps)
        y = y * jax.nn.sigmoid(z.astype(jnp.float32)).reshape(o.shape)
        return y.reshape(T, -1).astype(z.dtype) @ lp["wo"]


def _kda_prompt(cfg, lp, u, real_len, path):
    """A KDA layer's mixer over ONE sequence's rows u (B, h), `real_len`
    of them real, from a zero state, the scan by `path`
    (`prefill_recurrence_path`): (the mixer's output (B, h), the state S
    at `real_len` (n, d, d) float32, the history at `real_len` (K - 1,
    3C), the chunks the scan visited: the kernel passes by those wholly
    past `real_len`)."""
    import jax
    import jax.numpy as jnp
    B = u.shape[0]
    qkv, a, b, z = _kda_project(cfg, lp, u)
    with jax.named_scope("kda/conv"):
        summed, hist = _recurrent.conv_prompt(qkv, lp["conv_w"], real_len)
        q, k, v, g, beta = _kda_activate(cfg, lp, summed, a, b)
        live = jnp.arange(B) < real_len
        g = jnp.where(live[:, None, None], g, 0.0)
        beta = jnp.where(live[:, None], beta, 0.0)
    with jax.named_scope("kda/recur"):
        o, S, visited = _delta.scan(q, k, v, g, beta, real_len, path)
    return _kda_gate(cfg, lp, o, z), S, hist, visited


def kda_step_inputs(cfg, lp, u, arenas, lg, conv_ids, done):
    """`kda/project` and `kda/conv` of a step: for every slot's row u (S,
    h) the recurrence's operands q, k, v, g (S, n, d), beta (S, n), all
    float32, and the gate's z (S, C), the convolution taken over the
    slot's history, block `conv_ids` (S,) of layer `lg` of its arena, which
    moves one row on (a frozen slot's to scratch). Returns (q, k, v, g,
    beta, z, arenas)."""
    import jax
    qkv, a, b, z = _kda_project(cfg, lp, u)
    with jax.named_scope("kda/conv"):
        summed, arenas[CONV] = _recurrent.conv_step(
            arenas[CONV], lg, conv_ids, done, qkv, lp["conv_w"])
        q, k, v, g, beta = _kda_activate(cfg, lp, summed, a, b)
    return q, k, v, g, beta, z, arenas


def kda_state_update(arenas, lg, state_ids, done, q, k, v, g, beta, path):
    """`kda/recur` of a step: every slot's state, block `state_ids` (S,)
    of layer `lg` of its arena, read, moved one position on and written
    ONCE (a frozen slot's to scratch), by the kernel ops/kda_step.py or by
    XLA's gather-update-scatter (`path`: `recurrence_path`). Returns (o
    (S, n, d) float32, arenas). The served step runs THIS; so does the
    numeric check of the cell `kimi-linear-longgen-offline`, on the
    engine's own blocks (benchmarks/modes/serve-closed-kimi-linear.py)."""
    import jax
    with jax.named_scope("kda/recur"):
        o, arenas[STATE] = _delta.step_blocks(
            arenas[STATE], lg, state_ids, done, q, k, v, g, beta, path)
    return o, arenas


def _kda_decode(cfg, lp, u, arenas, lg, state_ids, conv_ids, done, path):
    """A KDA layer's mixer one position on for every slot: u (S, h); the
    slot's history and state are blocks `conv_ids`, `state_ids` (S,) of
    layer `lg` of their arenas. Returns (the mixer's output (S, h),
    arenas)."""
    q, k, v, g, beta, z, arenas = kda_step_inputs(cfg, lp, u, arenas, lg,
                                                  conv_ids, done)
    o, arenas = kda_state_update(arenas, lg, state_ids, done, q, k, v, g,
                                 beta, path)
    return _kda_gate(cfg, lp, o, z), arenas


def _zero_counters(cfg):
    import jax.numpy as jnp
    zero = jnp.zeros((), jnp.int32)
    return dict(_experts.zero_counters(cfg), kda_state_steps=zero,
                kda_prefill_rows=zero, kda_prefill_chunks=zero,
                mla_decode_rows=zero)


# -- the whole sequence, no cache (tests; generation never runs it) --------------

def forward_logits(params, cfg, tokens):
    """Logits (T, V) float32 of one sequence tokens (T,): the served math
    without a cache (the chunked KDA, the expanded latent attention)."""
    import jax.numpy as jnp
    T = tokens.shape[0]
    pos = jnp.arange(T)
    x = _decoder.embed(params, tokens, _decoder.act_dtype(params))
    mask = pos[None, :] <= pos[:, None]
    scale = _latent.attention_scale(cfg)
    counters = _zero_counters(cfg)
    live = jnp.ones((T,), bool)
    for li, lp in enumerate(params["layers"]):
        u = _decoder.rms(x, lp["norm1"], cfg.rms_eps)
        if cfg.kind(li) == "kda":
            # the oracle, never the kernel it is held against
            y = _kda_prompt(cfg, lp, u, T, "xla")[0]
        else:
            q_nope, q_rope, c, k_rope = _latent.project(cfg, lp, u, pos)
            k, v = _latent.expand(cfg, lp, c, k_rope)
            q = jnp.concatenate([q_nope, q_rope], -1)
            o = _decoder.masked_attention(q, k, v, mask, scale)
            y = o.reshape(T, -1) @ lp["wo"]
        x = x + y
        y, counters, _ = _experts.ffn(cfg, lp, x, live, counters)
        x = x + y
    return _decoder.head(cfg, params, x)


# -- prefill into the pages and the state blocks ---------------------------------

def prefill_pages(params, cfg, tokens, pfx_len, real_len, arena, pages):
    """Prefill ONE sequence's COLD prompt tokens (1, B) (`pfx_len` is 0: a
    model with state groups takes no prefix hits): the latent layers' rows
    as whole pages of the latent group's columns, each KDA layer's state
    and history AT `real_len` into the slot's blocks of the state groups
    (written whole, never read). Returns (logits (1, V) float32 of
    position real_len - 1, arena, counters)."""
    import jax
    import jax.numpy as jnp

    arenas = _recurrent.by_name(GROUPS, arena)
    latent = arenas[LATENT]
    B = tokens.shape[1]
    bs = latent.shape[4]
    dtype = latent.dtype
    cols = group_columns(cfg.cache_specs(), pages.shape[0], bs)
    rows = pages[cols[0]]
    state_id, conv_id = _recurrent.block_ids(pages, cols[1:])
    flash = _pages.kernel_beside(bucket=B)
    recurrence = prefill_recurrence_path(cfg, B)
    _delta.note_prefill(cfg, B, recurrence)
    j = jnp.arange(B)
    pos = pfx_len + j
    live = j < real_len
    x = _decoder.embed(params, tokens[0], dtype)
    counters = _zero_counters(cfg)
    chunks = 0
    for li, lp in enumerate(params["layers"]):
        lg = cfg.index_in_group(li)
        if cfg.kind(li) == "kda":
            with jax.named_scope("norm"):
                u = _decoder.rms(x, lp["norm1"], cfg.rms_eps)
            y, S, hist, visited = _kda_prompt(cfg, lp, u, real_len,
                                              recurrence)
            chunks = chunks + visited
            with jax.named_scope("kda/recur"):
                arenas[STATE] = _recurrent.write_block(arenas[STATE], lg,
                                                       state_id, S)
            with jax.named_scope("kda/conv"):
                arenas[CONV] = _recurrent.write_block(arenas[CONV], lg,
                                                      conv_id, hist)
        else:
            y, arenas[LATENT] = _latent.prefill_attend(
                cfg, lp, x, j, pos, arenas[LATENT], lg, rows, pfx_len,
                real_len, flash, cold_only=True)
        x = x + y
        y, counters, _ = _experts.ffn(cfg, lp, x, live, counters)
        x = x + y
    counters["kda_prefill_rows"] = (real_len * len(cfg.kda_layers)
                                    ).astype(jnp.int32)
    counters["kda_prefill_chunks"] = jnp.asarray(chunks, jnp.int32)
    last = x[real_len - 1][None]
    return _decoder.head(cfg, params, last), _recurrent.in_order(GROUPS, arenas), counters


# -- decode through the pages and the state blocks --------------------------------

def decode_attention_path(arena, arena_constraint=None):
    """{group: path} of the latent group: models/_latent.py's verdict on
    ITS arena (the state groups attend nothing)."""
    return {LATENT: _latent.decode_attention_path(arena[0],
                                                  arena_constraint)}


def decode_step_pages(params, cfg, tokens, arena, pt, ts, done=None,
                      attention=None, recurrence=None):
    """One decode step of every slot: tokens, ts (S,), pt (S, P + 2). A
    latent layer writes each live slot's row at ts and attends over 0..ts
    (absorbed); a KDA layer reads, updates and writes each live slot's
    history and state block ONCE. A frozen slot's writes reach scratch
    block 0 alone. Returns (logits (S, V) float32, arena, counters)."""
    import jax
    import jax.numpy as jnp

    arenas = _recurrent.by_name(GROUPS, arena)
    s_dim = pt.shape[0]
    bs = arenas[LATENT].shape[4]
    dtype = arenas[LATENT].dtype
    cols = group_columns(cfg.cache_specs(), pt.shape[1], bs)
    table = pt[:, cols[0]]
    state_ids, conv_ids = _recurrent.block_ids(pt, cols[1:])
    if attention is None:
        attention = decode_attention_path(arena)
    if recurrence is None:
        recurrence = recurrence_path(cfg)
    live = jnp.ones((s_dim,), bool) if done is None else ~done
    x = _decoder.embed(params, tokens, dtype)
    counters = _zero_counters(cfg)
    for li, lp in enumerate(params["layers"]):
        lg = cfg.index_in_group(li)
        if cfg.kind(li) == "kda":
            with jax.named_scope("norm"):
                u = _decoder.rms(x, lp["norm1"], cfg.rms_eps)
            y, arenas = _kda_decode(cfg, lp, u, arenas, lg, state_ids,
                                    conv_ids, done, recurrence)
        else:
            y, arenas[LATENT] = _latent.step_attend(
                cfg, lp, x, ts, arenas[LATENT], lg, table, done,
                attention[LATENT])
        x = x + y.astype(dtype)
        y, counters, _ = _experts.ffn(cfg, lp, x, live, counters)
        x = x + y
    n_live = jnp.sum(live).astype(jnp.int32)
    counters["kda_state_steps"] = n_live * len(cfg.kda_layers)
    counters["mla_decode_rows"] = (
        jnp.sum(jnp.where(live, ts + 1, 0)).astype(jnp.int32)
        * len(cfg.full_attn_layers))
    return _decoder.head(cfg, params, x), _recurrent.in_order(GROUPS, arenas), counters


# -- the engine's view of this model ---------------------------------------------

class _KimiLinearServingModel(_experts.ExpertBlockModel):
    prefill_pages = staticmethod(prefill_pages)
    decode_step_pages = staticmethod(decode_step_pages)

    own_counters = ("kda_state_steps", "kda_prefill_rows",
                    "kda_prefill_chunks", "mla_decode_rows",
                    "moe_picks_routed", "moe_picks_held",
                    "decode_moe_picks_routed", "decode_moe_picks_held")

    def cache_spec(self, cfg):
        return cfg.cache_specs()

    def decode_attention_path(self, arena, arena_constraint=None):
        return decode_attention_path(arena, arena_constraint)

    def prefill_attention_path(self, arena, bucket, arena_constraint=None):
        return "flash" if _pages.kernel_beside(bucket=bucket) else "gather"

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


KIMI_LINEAR_SERVING_MODEL = _KimiLinearServingModel(
    "Kimi-Linear-48B-A3B-Instruct")
