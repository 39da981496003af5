"""granite-4.0-h-small (`model_type: granitemoehybrid`) on the serving path:
a HYBRID decoder, nine layers of a Mamba-2 STATE-SPACE mixer to every layer
of position-free grouped-query attention, over softmax-routed experts of
which this chip may hold a SHARE, with the family's four multipliers.

Served through serving.model.ServingModel by the same engine, scheduler,
cache manager and fused chunk loop as every other model. With `rm =
residual_multiplier` a layer is
    x += rm mixer(RMSNorm(x));  u = RMSNorm(x);  x += rm (moe(u) + shared(u))
on `x0 = wte[ids] * embedding_multiplier`, and the logits are `RMSNorm(x)
wte^T / logits_scaling` (the head is the embedding). A layer's kind is read
from the published `layer_types` ("mamba" or "attention"):

  * a MAMBA layer keeps NO rows a token. What a slot carries is a
    FIXED-SIZE STATE, S (heads H, head channels P, state N) float32, and the
    last `mamba_conv - 1` pre-activation rows of x|B|C: one block each of
    the state groups `ssm` and `conv` (models/_recurrent.py, which
    models/kimi_linear.py's delta-rule layers share). The mixer (Mamba-2,
    arXiv:2405.21060, one group of B and C): [z | xBC | dt] = u W_in; xBC =
    SiLU(causal depthwise convolution of width 4 + bias); [x (H, P) | B (N) |
    C (N)] = xBC; dt = softplus(dt + dt_bias), a = exp(dt A), A = -exp(A_log),
    a SCALAR a head;
        S = a S + outer(dt x, B);   y = S C + D x
    in float32; then RMSNorm over all H P values of `y SiLU(z)` (the gate
    first, then the norm) and W_out. No positions anywhere.
    Its two programs: the RECURRENT STEP (`ssd_step`: one position a slot,
    ONE read-modify-write of the slot's 4 MB state block, by the Pallas
    kernel ops/ssd_step.py on a TPU, `recurrence_path`; a frozen slot's
    write goes to scratch block 0) and the CHUNKED SCAN of a prompt
    (`ssd_chunked`: the dual form in chunks of `mamba_chunk` rows, a
    chunk's rows against each other through the decay's cumulative sum and
    one (Q, Q) Gram matrix of C and B, the state carried between chunks by a
    `lax.scan`; plain `jax.numpy` in float32 at `highest`; every exponent is
    <= 0. A row at or past `real_len` gets dt = 0: it decays by 1 and adds
    nothing, so the state and the history written are those AT `real_len`,
    not at the bucket's end);
  * an ATTENTION layer is models/_grouped.py's grouped-query attention of
    FULL layers alone, whose `_project` here rotates NOTHING
    (`position_embedding_type: "nope"`), under its key `attention_scale`
    (the published `attention_multiplier`, not head_dim^-0.5): rows in the
    primary cache group `full`, prefilled through the flash forward and
    decoded through the grouped paged kernel;
  * every layer's feed-forward is the shared expert layer
    (models/_experts.py) under its "softmax" rule (the published rule, the k
    largest logits and a softmax over those k, is the same numbers), ONE
    shared SwiGLU of `shared_intermediate` added to the picks' sum, and
    `experts_held = (first, count)`: the routed experts this chip holds
    (None: all);
  * `vocab_size` is the rows of the embedding HELD here; `vocab_slice`
    (first, rows, of) names them in the published vocabulary.

Parameters (`x @ W`, W is (in, out)): wte (V, h), norm_f (h,); layers[i]:
norm1, norm2 (h,), router (h, E), w_gate, w_up (held, h, F), w_down (held,
F, h), shared_gate, shared_up (h, Fs), shared_down (Fs, h); a mamba layer's
w_in (h, 2 HP + 2 N + H), conv_w (K, HP + 2 N), conv_b (HP + 2 N,), dt_bias,
a_log, d (H,) float32, gate_norm (HP,), w_out (HP, h); an attention layer's
wq (h, heads d), wk, wv (h, kv_heads d), wo (heads d, h). No bias but the
convolution's.

Named scopes: `embed`, `norm`, `ssd/project` (W_in), `ssd/conv` (the
convolution, SiLU, the split, dt's softplus), `ssd/scan` (a prompt's
chunked scan and the state block's write), `ssd/step` (a step's
read-modify-write of the state), `ssd/gate` (the skip term, the gate, the
norm, W_out), `attn/project`, `attn/full`, `moe/*`, `head`. In-graph
counters beside the expert layer's: `ssd_state_steps` (live slots x mamba
layers a step), `ssd_prefill_rows` (real rows x mamba layers),
`decode_rows_full` (live positions x attention layers a step) and
command-a's held-pick four. `engine.stats()["state"]` names the step's
path (`recurrence_path`).

Refused by the engine (`serving.model.require_features`): int8 weights or
cache, adapters, speculation, a mesh plan, chunked prefill and host swap,
each with what a state group lacks for it; migration at the call; prefix
hits are off.
"""

from __future__ import annotations

from ..serving import pages as _pages
from ..serving.model import group_columns
from . import _decoder, _experts, _grouped, _recurrent

__all__ = ["GraniteHybridConfig", "init_params", "forward_logits",
           "prefill_pages", "decode_step_pages", "ssd_step", "ssd_chunked",
           "ssd_step_inputs", "ssd_state_update", "ssd_prompt_inputs",
           "recurrence_path",
           "GRANITE_HYBRID_SERVING_MODEL"]

# The scan's products are float32 at this precision: it is the recurrence
# to float32 rounding, and a state that two thousand rows of bfloat16
# products built would be a state kept in a lower precision.
SSD_PRECISION = "highest"

FULL, SSM, CONV = "full", "ssm", "conv"
GROUPS = (FULL, SSM, CONV)
MAMBA, ATTENTION = "mamba", "attention"


class GraniteHybridConfig:
    """The published keys under this package's names (defaults are
    granite-4.0-h-small's `config.json`, whole: every expert and the whole
    vocabulary held). `layer_types` None: the published pattern, attention
    at every index 5 mod 10."""

    # what models/_experts.py reads beside the keys: the published rule is
    # its "softmax" rule, renormalised, no bias, no factor
    router_scoring = "softmax"
    n_shared_experts = 1

    def __init__(self, vocab_size=100352, hidden=4096, layers=40, heads=32,
                 kv_heads=8, head_dim=None, layer_types=None,
                 mamba_heads=128, mamba_head_dim=64, mamba_state=128,
                 mamba_groups=1, mamba_conv=4, mamba_expand=2,
                 mamba_chunk=256, moe_intermediate=768,
                 shared_intermediate=1536, n_routed_experts=72,
                 experts_per_tok=10, embedding_multiplier=12.0,
                 residual_multiplier=0.22, attention_multiplier=0.0078125,
                 logits_scaling=16.0, rms_eps=1e-5, rope_theta=10000.0,
                 experts_held=None, vocab_slice=None, state_dtype="float32",
                 max_pos=131072, init_range=0.02,
                 name="granite-4.0-h-small"):
        if layer_types is None:
            layer_types = [ATTENTION if i % 10 == 5 else MAMBA
                           for i in range(layers)]
        layer_types = tuple(layer_types)
        if len(layer_types) != layers \
                or set(layer_types) - {MAMBA, ATTENTION}:
            raise ValueError(f"layer_types names {layers} layers, each "
                             f"{MAMBA!r} or {ATTENTION!r}, not "
                             f"{layer_types!r}")
        if ATTENTION not in layer_types or MAMBA not in layer_types:
            raise ValueError("the primary cache group is the attention "
                             "layers' and the state groups the mamba "
                             "layers': a model without either kind is not "
                             "written")
        if mamba_groups != 1:
            raise ValueError("the mixer is written for ONE group of B and "
                             f"C, not mamba_n_groups {mamba_groups}")
        if mamba_expand * hidden != mamba_heads * mamba_head_dim:
            raise ValueError(
                f"mamba_expand {mamba_expand} x {hidden} is not "
                f"{mamba_heads} heads of {mamba_head_dim}")
        experts_held, vocab_slice = _experts.checked_share(
            experts_held, n_routed_experts, vocab_slice, vocab_size)
        self.vocab_size = vocab_size
        self.hidden = hidden
        self.layers = layers
        self.layer_types = layer_types
        self.mamba_heads = mamba_heads
        self.mamba_head_dim = mamba_head_dim
        self.mamba_state = mamba_state
        self.mamba_conv = mamba_conv
        self.mamba_chunk = mamba_chunk
        self.moe_intermediate = moe_intermediate
        self.shared_intermediate = shared_intermediate
        self.n_routed_experts = n_routed_experts
        self.experts_per_tok = experts_per_tok
        self.embedding_multiplier = embedding_multiplier
        self.residual_multiplier = residual_multiplier
        self.logits_scaling = logits_scaling
        self.rms_eps = rms_eps
        self.experts_held = experts_held
        self.vocab_slice = vocab_slice
        self.state_dtype = state_dtype
        self.max_pos = max_pos
        self.init_range = init_range
        self.name = name
        # the attention layers as models/_grouped.py reads them: FULL layers
        # alone, nothing rotated, the published softmax scale
        n_attention = layer_types.count(ATTENTION)
        self.attention = _grouped.GroupedConfig(
            vocab_size=vocab_size, hidden=hidden, layers=n_attention,
            heads=heads, kv_heads=kv_heads,
            head_dim=head_dim or hidden // heads,
            moe_intermediate=moe_intermediate,
            n_routed_experts=n_routed_experts,
            experts_per_tok=experts_per_tok,
            layer_types=(_grouped.FULL,) * n_attention, rms_eps=rms_eps,
            rope_theta=rope_theta, max_pos=max_pos, init_range=init_range,
            name=name, attention_scale=attention_multiplier)

    @property
    def mamba_inner(self):
        """Channels of x and of the gate z: heads x head channels."""
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_width(self):
        """Channels the convolution runs over: x | B | C."""
        return self.mamba_inner + 2 * self.mamba_state

    @property
    def state_shape(self):
        """A slot's recurrent state of one layer: (heads, P, N)."""
        return (self.mamba_heads, self.mamba_head_dim, self.mamba_state)

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
                    self.layer_types.count(MAMBA), self.mamba_heads,
                    self.mamba_head_dim, self.state_shape, self.state_dtype,
                    self.mamba_conv - 1, self.conv_width, names=(SSM, CONV)))

    def serving_model(self):
        if self.name == GRANITE_HYBRID_SERVING_MODEL.name:
            return GRANITE_HYBRID_SERVING_MODEL
        return _GraniteHybridServingModel(self.name)


def init_params(cfg: GraniteHybridConfig, key, dtype):
    """Seeded random weights on the default device, one jitted maker a
    KIND of layer: normal(0, init_range) matrices (the router's too), unit
    norms, the held experts' matrices alone, the head the embedding; the
    convolution's filters and bias uniform in +-K^-0.5; `a_log = log(1 ..
    H)`, `d = 1` and `dt_bias` the inverse softplus of a log-uniform step
    in [0.001, 0.1] (the published initialiser, recalled: they decide how
    fast a seeded state forgets)."""
    import jax
    import jax.numpy as jnp

    h, att = cfg.hidden, cfg.attention
    H, K, W = cfg.mamba_heads, cfg.mamba_conv, cfg.conv_width
    E, F = _experts.held_experts(cfg)[1], cfg.moe_intermediate
    Fs = cfg.shared_intermediate
    std = cfg.init_range

    def normal(k, shape):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    mixers = {
        MAMBA: {"w_in": (h, 2 * cfg.mamba_inner + 2 * cfg.mamba_state + H),
                "w_out": (cfg.mamba_inner, h)},
        ATTENTION: {"wq": (h, att.heads * att.head_dim),
                    "wk": (h, att.kv_heads * att.head_dim),
                    "wv": (h, att.kv_heads * att.head_dim),
                    "wo": (att.heads * att.head_dim, h)}}
    ffn = {"router": (h, cfg.n_routed_experts), "w_gate": (E, h, F),
           "w_up": (E, h, F), "w_down": (E, F, h), "shared_gate": (h, Fs),
           "shared_up": (h, Fs), "shared_down": (Fs, h)}

    def layer(kind, k):
        shapes = dict(mixers[kind], **ffn)
        ks = jax.random.split(k, len(shapes) + 3)
        lp = {name: normal(kk, shape)
              for (name, shape), kk in zip(shapes.items(), ks)}
        lp.update(norm1=jnp.ones((h,), dtype), norm2=jnp.ones((h,), dtype))
        if kind == MAMBA:
            bound = K ** -0.5
            lp["conv_w"] = jax.random.uniform(
                ks[-3], (K, W), jnp.float32, -bound, bound).astype(dtype)
            lp["conv_b"] = jax.random.uniform(
                ks[-2], (W,), jnp.float32, -bound, bound).astype(dtype)
            dt = jnp.exp(jax.random.uniform(
                ks[-1], (H,), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
            lp["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
            lp["a_log"] = jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32))
            lp["d"] = jnp.ones((H,), jnp.float32)
            lp["gate_norm"] = jnp.ones((cfg.mamba_inner,), dtype)
        return lp

    def top(k):
        return {"wte": normal(k, (cfg.vocab_size, h)),
                "norm_f": jnp.ones((h,), dtype)}

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


# -- the Mamba-2 mixer's pieces ----------------------------------------------------

def recurrence_path(cfg):
    """ "kernel" where the step's read-modify-write of the state is the
    Pallas kernel ops/ssd_step.py (a TPU, the state float32 blocks of
    whole (8, 128) tiles), "xla" elsewhere (the CPU)."""
    if _pages.kernel_beside() and cfg.mamba_state % _pages.LANES == 0 \
            and cfg.mamba_head_dim % 8 == 0 and cfg.state_dtype == "float32":
        return "kernel"
    return "xla"


def ssd_step(S, x, dt, A, B, C):
    """The recurrence, one position: S (..., H, P, N) float32, x (..., H,
    P), dt (..., H), A (H,), B, C (..., N). Returns (S_t, y_t = S_t C_t
    (..., H, P)); the skip term `D x` is the gate's."""
    import jax.numpy as jnp
    a = jnp.exp(dt * A)
    S = a[..., None, None] * S \
        + (dt[..., None] * x)[..., None] * B[..., None, None, :]
    return S, jnp.sum(S * C[..., None, None, :], -1)


def ssd_chunked(x, dt, A, B, C, S0=None, chunk=256):
    """The recurrence over T positions of one sequence in chunks: x (T, H,
    P), dt (T, H), A (H,), B, C (T, N), all float32, S0 (H, P, N) or None
    (zeros). Returns (y (T, H, P) float32 without the skip term, S_T).
    Algebraically `ssd_step` T times. Inside a chunk of Q rows with the
    cumulative log decay c_r = sum_{i<=r} dt_i A: y_r = exp(c_r) S0 C_r +
    sum_{j<=r} exp(c_r - c_j) (C_r . B_j) dt_j x_j, and S_Q = exp(c_Q) S0 +
    sum_j exp(c_Q - c_j) outer(dt_j x_j, B_j). Every exponent is <= 0. A
    row with dt = 0 leaves the state as it was."""
    import jax
    import jax.numpy as jnp
    hi = SSD_PRECISION
    T, H, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, T)
    n = -(-T // Q)
    if n * Q != T:
        pad = n * Q - T
        x = jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
        dt, B, C = (jnp.pad(a, ((0, pad), (0, 0))) for a in (dt, B, C))
    low = jnp.tril(jnp.ones((Q, Q), bool))

    def carry(S, c):
        x, dt, B, C = c                                   # a chunk's rows
        cum = jnp.cumsum(dt * A, 0).T                     # (H, Q)
        dx = (x * dt[..., None]).transpose(1, 0, 2)       # (H, Q, P)
        gram = jnp.einsum("in,jn->ij", C, B, precision=hi)
        decay = jnp.exp(jnp.where(
            low, cum[:, :, None] - cum[:, None, :], -jnp.inf))
        y = jnp.einsum("hij,hjp->hip", gram * decay, dx, precision=hi) \
            + jnp.exp(cum)[..., None] * jnp.einsum(
                "hpn,in->hip", S, C, precision=hi)
        to_end = jnp.exp(cum[:, -1:] - cum)               # (H, Q)
        S = jnp.exp(cum[:, -1])[:, None, None] * S + jnp.einsum(
            "hjp,jn->hpn", dx * to_end[..., None], B, precision=hi)
        return S, y.transpose(1, 0, 2)

    if S0 is None:
        S0 = jnp.zeros((H, P, N), jnp.float32)
    S, y = jax.lax.scan(carry, S0, (
        x.reshape(n, Q, H, P), dt.reshape(n, Q, H), B.reshape(n, Q, N),
        C.reshape(n, Q, N)))
    return y.reshape(n * Q, H, P)[:T], S


def _ssd_project(cfg, lp, u):
    """`ssd/project`: the gate's z (T, HP), the convolution's input x|B|C
    (T, HP + 2N) and dt's pre-activation (T, H)."""
    import jax
    with jax.named_scope("ssd/project"):
        zxbcdt = u @ lp["w_in"]
    inner, wide = cfg.mamba_inner, cfg.mamba_inner + cfg.conv_width
    return zxbcdt[:, :inner], zxbcdt[:, inner:wide], zxbcdt[:, wide:]

def _ssd_activate(cfg, lp, summed, dt_raw):
    """The rest of `ssd/conv` behind the convolution's sum `summed` (T, HP
    + 2N) float32: the bias, SiLU, the split, dt's softplus, all float32:
    x (T, H, P), dt (T, H), B, C (T, N)."""
    import jax
    import jax.numpy as jnp
    T, inner, N = summed.shape[0], cfg.mamba_inner, cfg.mamba_state
    act = jax.nn.silu(summed + lp["conv_b"].astype(jnp.float32))
    x = act[:, :inner].reshape(T, cfg.mamba_heads, cfg.mamba_head_dim)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + lp["dt_bias"])
    return x, dt, act[:, inner:inner + N], act[:, inner + N:]


def _ssd_gate(cfg, lp, y, x, z):
    """`ssd/gate`: the skip term `D x` onto y (T, H, P) float32, the gate
    SiLU(z) FIRST, then the RMS norm over all HP values, `W_out`."""
    import jax
    import jax.numpy as jnp
    T = y.shape[0]
    with jax.named_scope("ssd/gate"):
        y = (y + lp["d"][:, None] * x).reshape(T, -1) \
            * jax.nn.silu(z.astype(jnp.float32))
        y = _decoder.rms(y, lp["gate_norm"], cfg.rms_eps)
        return y.astype(z.dtype) @ lp["w_out"]


def ssd_prompt_inputs(cfg, lp, u, real_len):
    """`ssd/project` and `ssd/conv` of a PROMPT: for one sequence's rows u
    (B, h), `real_len` of them real, the scan's operands x (B, H, P), dt (B,
    H) with 0 at and past `real_len`, B, C (B, N), all float32, the gate's z
    (B, HP) and the history at `real_len` (K - 1, HP + 2N). Returns (x, dt,
    B, C, z, hist). A prefill runs THIS and then `ssd_chunked`; so does the
    scan limit of the cell `granite-h-shortchat-offline`
    (benchmarks/modes/serve-closed-granite-hybrid.py)."""
    import jax
    import jax.numpy as jnp
    z, xbc, dt_raw = _ssd_project(cfg, lp, u)
    with jax.named_scope("ssd/conv"):
        summed, hist = _recurrent.conv_prompt(xbc, lp["conv_w"], real_len)
        x, dt, B, C = _ssd_activate(cfg, lp, summed, dt_raw)
        dt = jnp.where((jnp.arange(u.shape[0]) < real_len)[:, None], dt, 0.0)
    return x, dt, B, C, z, hist


def _ssd_prompt(cfg, lp, u, real_len):
    """A mamba layer's mixer over ONE sequence's rows u (B, h), `real_len`
    of them real, from a zero state: (the mixer's output (B, h), the state
    S at `real_len` (H, P, N) float32, the history at `real_len` (K - 1,
    HP + 2N))."""
    import jax
    import jax.numpy as jnp
    x, dt, B, C, z, hist = ssd_prompt_inputs(cfg, lp, u, real_len)
    with jax.named_scope("ssd/scan"):
        y, S = ssd_chunked(x, dt, -jnp.exp(lp["a_log"]), B, C,
                           chunk=cfg.mamba_chunk)
    return _ssd_gate(cfg, lp, y, x, z), S, hist


def ssd_step_inputs(cfg, lp, u, arenas, lg, conv_ids, done):
    """`ssd/project` and `ssd/conv` of a step: for every slot's row u (S,
    h) the recurrence's operands x (S, H, P), dt (S, H), B, C (S, N), all
    float32, and the gate's z (S, HP), the convolution taken over the
    slot's history, block `conv_ids` (S,) of layer `lg` of its arena, which
    moves one row on (a frozen slot's to scratch). Returns (x, dt, B, C, z,
    arenas)."""
    import jax
    z, xbc, dt_raw = _ssd_project(cfg, lp, u)
    with jax.named_scope("ssd/conv"):
        summed, arenas[CONV] = _recurrent.conv_step(
            arenas[CONV], lg, conv_ids, done, xbc, lp["conv_w"])
        x, dt, B, C = _ssd_activate(cfg, lp, summed, dt_raw)
    return x, dt, B, C, z, arenas


def ssd_state_update(lp, arenas, lg, state_ids, done, x, dt, B, C, path):
    """`ssd/step`: every slot's state, block `state_ids` (S,) of layer
    `lg` of its arena, read, moved one position on and written ONCE (a
    frozen slot's to scratch), by the kernel ops/ssd_step.py or by XLA's
    gather-update-scatter (`path`: `recurrence_path`). Returns (y (S, H, P)
    float32 without the skip term, arenas). The served step runs THIS; so
    does the numeric check of the cell `granite-h-shortchat-offline`, on
    the engine's own blocks
    (benchmarks/modes/serve-closed-granite-hybrid.py)."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope("ssd/step"):
        state = arenas[SSM]
        A = -jnp.exp(lp["a_log"])
        if path == "kernel":
            from ..ops.ssd_step import ssd_step_blocks
            y, arenas[SSM] = ssd_step_blocks(
                state, lg, state_ids, done, x, dt, jnp.exp(dt * A), B, C)
        else:
            S, y = ssd_step(_recurrent.read_blocks(state, lg, state_ids),
                            x, dt, A, B, C)
            arenas[SSM] = _recurrent.write_blocks(state, lg, state_ids,
                                                  done, S)
    return y, arenas


def _zero_counters(cfg):
    import jax.numpy as jnp
    zero = jnp.zeros((), jnp.int32)
    return dict(_experts.zero_counters(cfg), ssd_state_steps=zero,
                ssd_prefill_rows=zero, decode_rows_full=zero)


# -- the block's other pieces ------------------------------------------------------

def _embed(cfg, params, tokens, dtype):
    import jax
    x = _decoder.embed(params, tokens, dtype)
    with jax.named_scope("embed"):
        return x * cfg.embedding_multiplier


def _project(cfg, lp, u):
    """q (T, heads, d), k, v (T, kv_heads, d) of normed tokens u (T, h):
    no bias and NO rotation (`position_embedding_type: "nope"`; this
    module holds no rotation at all)."""
    att, T = cfg.attention, u.shape[0]
    return ((u @ lp["wq"]).reshape(T, att.heads, att.head_dim),
            (u @ lp["wk"]).reshape(T, att.kv_heads, att.head_dim),
            (u @ lp["wv"]).reshape(T, att.kv_heads, att.head_dim))


def _ffn(cfg, lp, x, live, counters):
    """x + rm (moe(u) + shared(u)), u = RMSNorm(x)."""
    import jax
    with jax.named_scope("norm"):
        u = _decoder.rms(x, lp["norm2"], cfg.rms_eps)
    y, counters = _experts.experts(cfg, lp, u, live, counters)
    return x + y * cfg.residual_multiplier, counters


def _head(cfg, params, x):
    """The stage `head`: the final RMS norm and the embedding, transposed,
    over `logits_scaling`; logits float32."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope("head"):
        y = _decoder.rms(x, params["norm_f"], cfg.rms_eps)
        return jnp.einsum("th,vh->tv", y, params["wte"],
                          preferred_element_type=jnp.float32) \
            / cfg.logits_scaling


# -- the whole sequence, no cache (tests; generation never runs it) --------------

def forward_logits(params, cfg, tokens):
    """Logits (T, V) float32 of one sequence tokens (T,): the served math
    without a cache (the chunked scan, masked attention)."""
    import jax.numpy as jnp
    T = tokens.shape[0]
    x = _embed(cfg, params, tokens, _decoder.act_dtype(params))
    counters = _zero_counters(cfg)
    live = jnp.ones((T,), bool)
    for li, lp in enumerate(params["layers"]):
        u = _decoder.rms(x, lp["norm1"], cfg.rms_eps)
        if cfg.kind(li) == MAMBA:
            y = _ssd_prompt(cfg, lp, u, T)[0]
        else:
            q, k, v = _project(cfg, lp, u)
            o = _grouped.attend_rows(cfg.attention, q, k, v, "full", False)
            y = o.reshape(T, -1) @ lp["wo"]
        x = x + y * cfg.residual_multiplier
        x, counters = _ffn(cfg, lp, x, live, counters)
    return _head(cfg, params, x)


# -- prefill into the pages and the state blocks ---------------------------------

def prefill_pages(params, cfg, tokens, pfx_len, real_len, arena, pages):
    """Prefill ONE sequence's COLD prompt tokens (1, B) (`pfx_len` is 0: a
    model with state groups takes no prefix hits): the attention layers'
    rows as whole pages of the primary group's columns, each mamba layer's
    state and history AT `real_len` into the slot's blocks of the state
    groups (written whole, never read). Returns (logits (1, V) float32 of
    position real_len - 1, arena, counters)."""
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
    live = jnp.arange(B) < real_len
    x = _embed(cfg, params, tokens[0], dtype)
    counters = _zero_counters(cfg)
    for li, lp in enumerate(params["layers"]):
        lg = cfg.index_in_group(li)
        with jax.named_scope("norm"):
            u = _decoder.rms(x, lp["norm1"], cfg.rms_eps)
        if cfg.kind(li) == MAMBA:
            y, S, hist = _ssd_prompt(cfg, lp, u, real_len)
            with jax.named_scope("ssd/scan"):
                arenas[SSM] = _recurrent.write_block(arenas[SSM], lg,
                                                     state_id, S)
            with jax.named_scope("ssd/conv"):
                arenas[CONV] = _recurrent.write_block(arenas[CONV], lg,
                                                      conv_id, hist)
        else:
            with jax.named_scope("attn/project"):
                q, k, v = _project(cfg, lp, u)
                kv = jnp.concatenate([k, v], -1).astype(dtype)
                arenas[FULL] = _grouped.write_prompt(
                    arenas[FULL], lg, rows, pfx_len, real_len, kv, "full")
            with jax.named_scope("attn/full"):
                o = _grouped.attend_rows(cfg.attention, q, k, v, "full",
                                         flash, real_len)
            with jax.named_scope("attn/project"):
                y = o.reshape(B, -1) @ lp["wo"]
        x = x + y * cfg.residual_multiplier
        x, counters = _ffn(cfg, lp, x, live, counters)
    counters["ssd_prefill_rows"] = (
        real_len * cfg.layer_types.count(MAMBA)).astype(jnp.int32)
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
    0..ts; a mamba layer reads, updates and writes each live slot's history
    and state block ONCE. A frozen slot's writes reach scratch block 0
    alone. Returns (logits (S, V) float32, arena, counters)."""
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
    x = _embed(cfg, params, tokens, dtype)
    counters = _zero_counters(cfg)
    for li, lp in enumerate(params["layers"]):
        lg = cfg.index_in_group(li)
        with jax.named_scope("norm"):
            u = _decoder.rms(x, lp["norm1"], cfg.rms_eps)
        if cfg.kind(li) == MAMBA:
            xs, dt, B, C, z, arenas = ssd_step_inputs(
                cfg, lp, u, arenas, lg, conv_ids, done)
            y, arenas = ssd_state_update(lp, arenas, lg, state_ids, done,
                                         xs, dt, B, C, recurrence)
            y = _ssd_gate(cfg, lp, y, xs, z)
        else:
            with jax.named_scope("attn/project"):
                q, k, v = _project(cfg, lp, u)
            with jax.named_scope("attn/full"):
                o, arenas[FULL] = _grouped.attend_step(
                    cfg.attention, q, k, v, arenas[FULL], lg, table, ts,
                    done, lo, "full", attention["full"])
            with jax.named_scope("attn/project"):
                y = o.reshape(s_dim, -1).astype(dtype) @ lp["wo"]
        x = x + y.astype(dtype) * cfg.residual_multiplier
        x, counters = _ffn(cfg, lp, x, live, counters)
    n_live = jnp.sum(live).astype(jnp.int32)
    counters["ssd_state_steps"] = n_live * cfg.layer_types.count(MAMBA)
    counters["decode_rows_full"] = (
        jnp.sum(jnp.where(live, ts + 1, 0)).astype(jnp.int32)
        * cfg.layer_types.count(ATTENTION))
    return (_head(cfg, params, x), _recurrent.in_order(GROUPS, arenas),
            counters)


# -- the engine's view of this model ---------------------------------------------

class _GraniteHybridServingModel(_experts.ExpertBlockModel):
    prefill_pages = staticmethod(prefill_pages)
    decode_step_pages = staticmethod(decode_step_pages)

    own_counters = ("ssd_state_steps", "ssd_prefill_rows", "decode_rows_full",
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
        return {"experts_held": {"first": first, "count": count,
                                 "of": cfg.n_routed_experts},
                "vocab_slice": dict(zip(("first", "rows", "of"),
                                        cfg.vocab_slice)),
                "state": {"recurrence_path": recurrence_path(cfg),
                          "prefill_recurrence_path": "xla",
                          "prefill_chunk_rows": cfg.mamba_chunk}}

    def _counters(self, cfg, c, decode):
        import jax.numpy as jnp
        routed = c["router_tokens"] * cfg.experts_per_tok
        held = jnp.sum(c["expert_tokens"]).astype(jnp.int32)
        return super()._counters(cfg, dict(
            c, moe_picks_routed=routed, moe_picks_held=held,
            decode_moe_picks_routed=routed, decode_moe_picks_held=held),
            decode)


GRANITE_HYBRID_SERVING_MODEL = _GraniteHybridServingModel(
    "granite-4.0-h-small")
