"""The published DeepSeek-V3 block on the serving path: Moonlight-16B-A3B
as it stands, and Xing4.0-29B-A4B (`model_type: xing4_0`) by three fields
of the one config.

Multi-head LATENT attention and routed + shared experts, served through
serving.model.ServingModel by the same engine, scheduler, block allocator
and fused chunk loop (serving/decode_loop.py) as the GPT family:

  * the cache holds ONE row a token a layer, `[c | k_rope]` AFTER the
    latent's norm and the key's rotation (kv_lora_rank + qk_rope_head_dim
    values, zero-padded to a multiple of 128 lanes: 576 -> 640), shared by
    all heads: `CacheSpec(layers, heads=1, row_width)`;
  * PREFILL attends in the EXPANDED form (k_nope and v expanded from the
    latents through W_kvb, ordinary causal attention at head widths
    192/192/128: the flash kernel's forward on a TPU for a cold prompt,
    masked XLA attention over the gathered page row after a prefix hit
    and on the CPU);
  * DECODE attends in the ABSORBED form: `q_lat_h = q_nope_h W_UK_h^T`,
    score `q_lat_h . c + q_rope_h . k_rope`, context `sum p c`, then
    `o_h = o_lat_h W_UV_h` (ops/paged_attention.latent_paged_attention
    walks the page table over the latent arena; a gather and two einsums
    where the kernel does not apply);
  * layers past `first_k_dense` route every token to `experts_per_tok` of
    `n_routed_experts` SwiGLU experts (sigmoid scores in float32, the
    picks by score + correction bias, the weights by score alone,
    normalised and scaled) plus one shared SwiGLU. The expert product is
    GROUPED over the rows that were routed (tokens x experts_per_tok of
    them, no capacity, none dropped, never every expert on every token),
    and the routed rows are LAID OUT ONCE: sorted by expert, every
    expert's rows from a whole row tile on, their positions by COUNTING
    (ops/grouped_swiglu.routed_positions: a (token, pick)'s row is its
    group's start plus the earlier picks of the same expert; no sort).
    `moe/dispatch` gathers the rows there (one scatter of tokens x picks
    integers says whose row each is), `moe/experts` computes whole tiles
    of ONE expert, `moe/combine` reads the products back by the same
    positions, pick by pick in the weights' type, sums them in float32
    in pick order, adds the shared experts' term and rounds once
    (`_combine`): ONE sum with two carriers, chosen by `combine_path`
    from the static byte size of the products. XLA's gather and fusion
    for a decode step and a short prompt (below COMBINE_KERNEL_FROM a
    row costs it 8-15 ns, or the whole sum less than a kernel's call);
    from there on, on a TPU, the kernel ops/routed_combine: the expert
    kernel stores a row's words TOGETHER (`grouped_swiglu(...,
    packed=True)`: two column halves a 32-bit word, the same bfloat16
    roundings) and the combine fetches every row that is someone's by a
    DMA of its own, a token tile at a time, the next tile's rows in
    flight while this one is summed; a pick past the buffer (a dead
    token's, one held elsewhere) is never fetched and adds exactly 0.
    The in-graph counter `combine_kernel_passes` counts the passes that
    took the kernel. On a TPU the product is ONE kernel a layer,
    ops/grouped_swiglu: gate, up, `silu(g) * u` and down per expert, the
    weights read where they lie, an expert with no row never fetched;
    the row tile is the layout's, chosen from the static row count (16
    rows for a decode step's 192, 256 for a prompt's thousands: PERF.md,
    PRs 28 and 37). Elsewhere (the CPU) it is one `jax.lax.ragged_dot`
    per weight over the same layout; `expert_product_path` says which,
    the in-graph counter `moe_kernel_passes` counts the layers that ran
    the kernel and `moe_rows_computed` the rows it computed (its visits'
    whole tiles: `sum(expert_tokens)` over it is the share that were
    someone's).

What a config may change in the block (defaults are Moonlight's, whose
program they leave as it was, to the bit):
  * `q_lora_rank`: the query through a low-rank pair with a norm
    between, `q = RMSNorm(h W_qa; q_norm) W_qb`;
  * `rope_scaling`: YaRN's dict (`rope_frequencies`: each rotary
    frequency blended between itself and itself over `factor`; the
    softmax scale times mscale^2, `attention_scale`);
  * `hc_mult` n > 1: the residual `x + f(norm(x))` of BOTH sublayers
    becomes a manifold-constrained hyper-connection over n streams
    (`_residual`): the state a layer hands on is (rows, n, h); a
    sublayer reads `u = H_pre X`, and `X' = H_res X + outer(H_post, y)`
    with H_pre, H_post from sigmoids and H_res doubly stochastic by
    `hc_sinkhorn_iters` Sinkhorn rounds of `exp(clamp(.))`, all from the
    token's own streams (`hc_coefficients`) and all in float32 whatever
    the weights' type. X_0 is the embedding repeated n times; the final
    norm reads the streams' sum. Scopes `hc/coeff`, `hc/pre`, `hc/post`;
    in-graph counters `hc_passes`, `hc_rowsum_dev_ppm`. The choice is a
    Python branch on the config: at n = 1 none of it is traced.

Parameters (`x @ W`, W is (in, out); no bias anywhere but the mixers'):
  wte (V, h), head (h, V), norm_f (h,), layers[i]:
    norm1, norm2 (h,); wq (h, heads*(nope+rope)), or with `q_lora_rank` r
    wqa (h, r), q_norm (r,), wqb (r, heads*(nope+rope)); wkva (h,
    rank+rope); kv_norm (rank,); wkvb (rank, heads*(nope+v)), a head's
    [k_nope | v]; wo (heads*v, h); then a dense layer's gate, up (h, I),
    down (I, h), or an expert layer's router (h, E), router_bias (E,)
    float32, w_gate, w_up (E, h, F), w_down (E, F, h), shared_gate,
    shared_up (h, Fs), shared_down (Fs, h); with `hc_mult` n > 1 also
    hc_attn and hc_ffn, one mixer a sublayer: hc_norm (n*h,), phi (n*h,
    2n + n*n), b_pre, b_post (n,), b_res (n, n) and the scalar gates
    a_pre, a_post, a_res, the last six float32.

Still refused. Engine features: none of int8 weights or cache, adapters,
speculation, a mesh plan or chunked prefill is implemented (a 16k-row
bucket's workspace is what a chunked latent prefill would cut; the mixer
under a mesh is unwritten); the engine refuses each at construction from
`features` below. Of the architecture: a multi-token-prediction module
(how it joins n streams is not public: benchmarks/lib/xing.py refuses
`num_nextn_predict_layers` != 0), grouped routing (`n_group` > 1), a
rope scaling that is not YaRN.
"""

from __future__ import annotations

import math

import numpy as np

from ..serving.model import CacheSpec, ServingModel
from .gpt_decode import _gather_pages, _write_pages

__all__ = ["MoonlightConfig", "init_params", "forward_logits",
           "prefill_pages", "decode_step_pages",
           "decode_attention_path", "absorbed_attention", "route",
           "held_experts", "grouped_experts", "expert_product_path", "rope",
           "rope_frequencies", "attention_scale", "hc_coefficients",
           "MOONLIGHT_SERVING_MODEL"]

_LANES = 128


class MoonlightConfig:
    """The published keys under this package's names (defaults are
    Moonlight-16B-A3B's `config.json`; Xing4.0-29B-A4B sets
    `q_lora_rank`, `rope_scaling`, `hc_mult` and its `name`)."""

    def __init__(self, vocab_size=163840, hidden=2048, layers=27, heads=16,
                 kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, intermediate=11264,
                 moe_intermediate=1408, n_routed_experts=64,
                 n_shared_experts=2, experts_per_tok=6, first_k_dense=1,
                 routed_scaling_factor=2.446, rms_eps=1e-5,
                 rope_theta=50000.0, max_pos=8192, init_range=0.02,
                 q_lora_rank=None, rope_scaling=None, hc_mult=1,
                 hc_sinkhorn_iters=20, hc_eps=1e-6, hc_res_clamp=30.0,
                 name="Moonlight-16B-A3B"):
        self.vocab_size = vocab_size
        self.hidden = hidden
        self.layers = layers
        self.heads = heads
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.intermediate = intermediate
        self.moe_intermediate = moe_intermediate
        self.n_routed_experts = n_routed_experts
        self.n_shared_experts = n_shared_experts
        self.experts_per_tok = experts_per_tok
        self.first_k_dense = first_k_dense
        self.routed_scaling_factor = routed_scaling_factor
        self.rms_eps = rms_eps
        self.rope_theta = rope_theta
        self.max_pos = max_pos
        self.init_range = init_range
        # the query through a low-rank pair and its norm (None: one matrix)
        self.q_lora_rank = q_lora_rank
        # None (plain RoPE) or the published YaRN dict: factor,
        # original_max_position_embeddings, beta_fast, beta_slow, mscale,
        # mscale_all_dim
        if rope_scaling is not None and rope_scaling.get("type") != "yarn":
            raise ValueError("rope_scaling is None or a YaRN dict, not "
                             f"{rope_scaling!r}")
        self.rope_scaling = rope_scaling
        # residual streams: 1 is `x + f(norm(x))`; n > 1 the
        # manifold-constrained hyper-connections over n streams
        if hc_mult < 1:
            raise ValueError(f"hc_mult is at least 1, not {hc_mult}")
        self.hc_mult = hc_mult
        self.hc_sinkhorn_iters = hc_sinkhorn_iters
        self.hc_eps = hc_eps
        self.hc_res_clamp = hc_res_clamp
        self.name = name

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def row_values(self):
        """Values a token leaves in a layer's cache: latent + rope key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def row_width(self):
        """The row as stored: `row_values` up to a multiple of 128 lanes
        (a minor dimension that is not one is padded in HBM anyway and
        cannot be sliced by a DMA: PERF.md, PR 26)."""
        return -(-self.row_values // _LANES) * _LANES

    def serving_model(self):
        """The one serving model of this block, under this config's
        name (`engine.stats()["model"]`)."""
        if self.name == MOONLIGHT_SERVING_MODEL.name:
            return MOONLIGHT_SERVING_MODEL
        return _MoonlightServingModel(self.name)


def init_params(cfg: MoonlightConfig, key, dtype):
    """Seeded random weights on the default device: normal(0, init_range)
    matrices, unit norms, a small non-zero router correction bias (a
    trained model's is not zero, and zero would leave it unexercised).
    One jitted maker per KIND of layer, called once per layer: three
    small programs whatever the depth, and never more than one layer's
    generator bits alive beside the weights (the 64 experts of a layer
    are 1.1 GB in bfloat16; all layers' in one call would hold the
    stacks and their slices together)."""
    import jax
    import jax.numpy as jnp

    h, n = cfg.hidden, cfg.heads
    E, F = cfg.n_routed_experts, cfg.moe_intermediate
    Fs = cfg.n_shared_experts * F
    std = cfg.init_range
    m, r = cfg.hc_mult, cfg.q_lora_rank

    def normal(k, shape):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    shapes = {"wkva": (h, cfg.row_values),
              "wkvb": (cfg.kv_lora_rank,
                       n * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
              "wo": (n * cfg.v_head_dim, h)}
    if r is None:
        shapes = dict(wq=(h, n * cfg.qk_head_dim), **shapes)
    else:
        shapes = dict(wqa=(h, r), wqb=(r, n * cfg.qk_head_dim), **shapes)
    dense = dict(shapes, gate=(h, cfg.intermediate),
                 up=(h, cfg.intermediate), down=(cfg.intermediate, h))
    moe = dict(shapes, router=(h, E), w_gate=(E, h, F), w_up=(E, h, F),
               w_down=(E, F, h), shared_gate=(h, Fs), shared_up=(h, Fs),
               shared_down=(Fs, h))

    def mixer(k):
        """One sublayer's mixer. `phi` as every matrix; the gates `a_*`
        at HC_GATE and a diagonal HC_RES_DIAG in `b_res`, so that the
        coefficients depend on the token and H_res leans to the identity
        without being it (see HC_GATE); small seeded biases, so that
        none is zero and unexercised."""
        ks = jax.random.split(k, 4)
        bias = lambda kk, shape: 0.1 * jax.random.normal(kk, shape,
                                                         jnp.float32)
        gate = jnp.float32(HC_GATE)
        return {"hc_norm": jnp.ones((m * h,), dtype),
                "phi": normal(ks[0], (m * h, 2 * m + m * m)),
                "b_pre": bias(ks[1], (m,)), "b_post": bias(ks[2], (m,)),
                "b_res": bias(ks[3], (m, m))
                + HC_RES_DIAG * jnp.eye(m, dtype=jnp.float32),
                "a_pre": gate, "a_post": gate, "a_res": gate}

    def layer(shapes, k):
        ks = jax.random.split(k, len(shapes) + 1)
        lp = {name: normal(kk, shape)
              for (name, shape), kk in zip(shapes.items(), ks)}
        lp.update(norm1=jnp.ones((h,), dtype), norm2=jnp.ones((h,), dtype),
                  kv_norm=jnp.ones((cfg.kv_lora_rank,), dtype))
        if r is not None:
            lp["q_norm"] = jnp.ones((r,), dtype)
        if "router" in shapes:
            lp["router_bias"] = 0.01 * jax.random.normal(ks[-1], (E,),
                                                         jnp.float32)
        if m > 1:
            # keys of their own: the other weights are what they were
            lp["hc_attn"] = mixer(jax.random.fold_in(k, 1))
            lp["hc_ffn"] = mixer(jax.random.fold_in(k, 2))
        return lp

    def top(k):
        k1, k2 = jax.random.split(k)
        return {"wte": normal(k1, (cfg.vocab_size, h)),
                "head": normal(k2, (h, cfg.vocab_size)),
                "norm_f": jnp.ones((h,), dtype)}

    make = {False: jax.jit(lambda k: layer(dense, k)),
            True: jax.jit(lambda k: layer(moe, k))}
    keys = jax.random.split(key, cfg.layers + 1)
    params = jax.jit(top)(keys[-1])
    params["layers"] = [make[i >= cfg.first_k_dense](keys[i])
                        for i in range(cfg.layers)]
    return params


# -- the block's pieces -------------------------------------------------------

def _rms(x, g, eps):
    """RMS norm, statistics in float32, the result in x's type."""
    import jax
    import jax.numpy as jnp
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (x32 * inv * g.astype(jnp.float32)).astype(x.dtype)


def yarn_mscale(factor, mscale):
    """YaRN's attention-magnitude correction for a context stretched
    `factor` times."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_frequencies(d, theta, scaling=None):
    """(inv_freq (d/2,) float32, what cos and sin are scaled by) of a
    rotary width d. Plain RoPE: theta^(-2i/d) and 1. YaRN (`scaling`,
    the published dict): each frequency blended between itself
    (extrapolation) and itself over `factor` (interpolation) by a linear
    ramp over the dimensions between the one that turns `beta_fast`
    times in the original context and the one that turns `beta_slow`
    times; cos and sin scaled by mscale(factor, mscale) over
    mscale(factor, mscale_all_dim)."""
    import jax.numpy as jnp
    extra = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if scaling is None:
        return extra, 1.0
    factor = scaling["factor"]
    original = scaling["original_max_position_embeddings"]

    def turns_dim(turns):
        return (d * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(turns_dim(scaling["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    keep = 1.0 - ramp                       # 1: extrapolate, 0: interpolate
    inv = extra / factor * (1 - keep) + extra * keep
    return inv, (yarn_mscale(factor, scaling.get("mscale", 1))
                 / yarn_mscale(factor, scaling.get("mscale_all_dim", 0)))


def attention_scale(cfg):
    """1 / sqrt(nope + rope), times YaRN's mscale(factor,
    mscale_all_dim) squared where the positions are stretched."""
    scale = 1.0 / math.sqrt(cfg.qk_head_dim)
    sc = cfg.rope_scaling
    if sc is not None and sc.get("mscale_all_dim", 0):
        scale *= yarn_mscale(sc["factor"], sc["mscale_all_dim"]) ** 2
    return scale


def rope(x, pos, theta, scaling=None, interleaved=True):
    """Rotary position on the last axis of x at integer positions `pos`
    (broadcast against x's leading axes), in the PUBLISHED element
    order: the interleaved pairs (x0, x1), (x2, x3), ... are first
    permuted to halves (x0, x2, ..., x1, x3, ...), then `x cos +
    rotate_half(x) sin`, at `rope_frequencies(d, theta, scaling)`.
    `interleaved=False`: a model published with its pairs already in
    halves (models/mellum) is not permuted. Float32 inside, x's type
    out."""
    import jax.numpy as jnp
    d = x.shape[-1]
    x32 = x.astype(jnp.float32)
    if interleaved:
        x32 = jnp.concatenate([x32[..., 0::2], x32[..., 1::2]], -1)
    inv, mscale = rope_frequencies(d, theta, scaling)
    ang = pos.astype(jnp.float32)[..., None] * inv
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    rot = jnp.concatenate([-x32[..., d // 2:], x32[..., :d // 2]], -1)
    return (x32 * cos + rot * sin).astype(x.dtype)


def _swiglu_hidden(x, gate, up):
    import jax
    g = x @ gate
    return jax.nn.silu(g) * (x @ up)


def _swiglu(x, gate, up, down):
    return _swiglu_hidden(x, gate, up) @ down


# -- the residual path ---------------------------------------------------------

# Seeded values of a mixer's gates and of `b_res`'s diagonal (init_params).
# The mHC paper starts the gates small, which would leave the dynamic
# term without effect, H_res the same matrix at every token and, from
# X_0's equal rows, the n streams one: nothing a test or a cell could see.
# With `phi` normal(0, 0.02) over n x 3584 unit-RMS values a z has
# standard deviation about 2.4; at a gate of 0.5 the sigmoids' arguments
# spread by 1.2 and H_res's entries (diagonal leaning, not the identity)
# by what benchmarks/configs/xing4.0-29b-a4b.json `assumed` records.
HC_GATE = 0.5
HC_RES_DIAG = 2.0


def _add_all(parts):
    """The sum of a few equal-shaped arrays as plain additions: n is 4,
    and additions fuse with what is around them where a reduction over
    an axis of 4 would be a kernel of its own."""
    total = parts[0]
    for part in parts[1:]:
        total = total + part
    return total


def _three_bfloat16(w):
    """[hi | mid | lo]: w (.., k) float32 as three bfloat16 pieces side
    by side (.., 3k) whose sum is w to 2^-24 of it."""
    import jax.numpy as jnp
    pieces, rest = [], w
    for _ in range(3):
        piece = rest.astype(jnp.bfloat16)
        pieces.append(piece)
        rest = rest - piece.astype(jnp.float32)
    return jnp.concatenate(pieces, -1)


def hc_coefficients(cfg, hp, X):
    """A sublayer's mixing coefficients from its input streams X (T, n,
    C), all in float32 whatever X's type: H_pre (n, T) in (0, 1), H_post
    (n, T) in (0, 2) and H_res (n, n, T), row j column i at [j, i],
    made doubly stochastic by `hc_sinkhorn_iters` rounds of column then
    row normalisation of exp(clamp(.)). The token axis is LAST, inside
    and in what comes back, so that the rounds are additions and
    products of whole vectors of tokens and a reader takes a
    coefficient as ONE vector (T,), `h_res[j, i]`: a vector has one
    layout, so no reader's choice of layout reaches back into the
    rounds. Handed back token-first, (T, n, n), they took the layout of
    whatever read them next, and behind a Pallas call's output the
    compiler put the 4 streams in the lanes: 32 MB a round out of VMEM
    for 1 MB in it, `hc/coeff` 5.5 ms a sublayer of 16k tokens for 1.2
    (PERF.md, PR 39)."""
    import jax
    import jax.numpy as jnp
    T, n, C = X.shape
    f32, eps = jnp.float32, cfg.hc_eps
    x = X.reshape(T, n * C)
    x32 = x.astype(f32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    # RMSNorm(vec X; hc_norm) . phi with the norm's weight folded into
    # phi's rows and the row's scale applied after the product (24
    # values a token, not n x C)
    phi = hp["hc_norm"].astype(f32)[:, None] * hp["phi"].astype(f32)
    if x.dtype == jnp.bfloat16:
        # the float32 product of bfloat16 streams WITHOUT a float32 copy
        # of them (940 MB at 16k rows, written and read back in every
        # sublayer): phi in three bfloat16 pieces that sum to it, the
        # three products exact in float32 and summed there; 72 columns
        # cost the MXU what 24 do
        z = jnp.dot(x, _three_bfloat16(phi), preferred_element_type=f32)
        z = _add_all(jnp.split(z, 3, axis=-1)[::-1])
    else:
        z = jnp.dot(x32, phi, precision=jax.lax.Precision.HIGHEST)
    z = (z * inv).T                                           # (2n + n^2, T)
    h_pre = jax.nn.sigmoid(hp["a_pre"] * z[:n] + hp["b_pre"][:, None])
    h_post = 2.0 * jax.nn.sigmoid(hp["a_post"] * z[n:2 * n]
                                  + hp["b_post"][:, None])
    m = hp["a_res"] * z[2 * n:].reshape(n, n, T) + hp["b_res"][:, :, None]
    m = jnp.exp(jnp.clip(m, -cfg.hc_res_clamp, cfg.hc_res_clamp))
    for _ in range(cfg.hc_sinkhorn_iters):
        cols = _add_all([m[j] for j in range(n)])             # (n, T)
        m = m / (cols + eps)[None]
        rows = _add_all([m[:, i] for i in range(n)])          # (n, T)
        m = m / (rows + eps)[:, None]
    return h_pre, h_post, m


def _residual(cfg, hp, x, f, live, counters):
    """ONE sublayer around f (attention or feed-forward, its own norm
    first): `x + f(x)` at `hc_mult` 1, where x is (T, h); the
    manifold-constrained hyper-connection at n streams, where x is (T,
    n, h): u = H_pre X, y = f(u), X' = H_res X + outer(H_post, y), the
    coefficients from `hc_coefficients(hp)`. f(u, counters) returns
    (y, counters, aux). The choice is the config's, made in Python: at
    `hc_mult` 1 nothing of the mixer is traced. Returns (x', counters,
    aux)."""
    import jax
    import jax.numpy as jnp
    if cfg.hc_mult == 1:
        y, counters, aux = f(x, counters)
        return x + y, counters, aux
    n, f32 = cfg.hc_mult, jnp.float32
    with jax.named_scope("hc/coeff"):
        h_pre, h_post, h_res = hc_coefficients(cfg, hp, x)
        # how far a row of H_res is from summing to one, at the worst
        # live token, in parts per million
        rows = _add_all([h_res[:, i] for i in range(n)])          # (n, T)
        dev = jnp.max(jnp.where(live, jnp.abs(rows - 1.0), 0.0))
    with jax.named_scope("hc/pre"):
        u = _add_all([h_pre[i][:, None] * x[:, i].astype(f32)
                      for i in range(n)]).astype(x.dtype)
    y, counters, aux = f(u, counters)
    with jax.named_scope("hc/post"):
        # the streams are read again AS THEY LIE: behind the barrier
        # their widening to float32 is this scope's own and fuses into
        # the sums, where shared with `hc/pre` it would be a float32
        # copy of the streams (940 MB at 16k rows) kept across f
        x, y = jax.lax.optimization_barrier((x, y))
        y32 = y.astype(f32)
        out = [_add_all([h_res[j, i][:, None] * x[:, i].astype(f32)
                         for i in range(n)]) + h_post[j][:, None] * y32
               for j in range(n)]
        x = jnp.stack(out, 1).astype(x.dtype)
    passes = jnp.any(live).astype(jnp.int32)
    counters = dict(
        counters, hc_passes=counters["hc_passes"] + passes,
        hc_rowsum_dev_ppm=counters["hc_rowsum_dev_ppm"]
        + jnp.round(dev * 1e6).astype(jnp.int32))
    return x, counters, aux


def _streams_in(cfg, x):
    """The residual state a layer is handed: x (T, h) itself, or its n
    copies (T, n, h)."""
    import jax.numpy as jnp
    if cfg.hc_mult == 1:
        return x
    return jnp.broadcast_to(x[:, None, :], (x.shape[0], cfg.hc_mult,
                                            x.shape[1]))


def _streams_out(cfg, x):
    """What the final norm is handed: x, or the sum of its n streams."""
    import jax.numpy as jnp
    if cfg.hc_mult == 1:
        return x
    return x.astype(jnp.float32).sum(-2).astype(x.dtype)


def _project(cfg, lp, x, pos):
    """The attention's projections of tokens x (T, h) at positions pos
    (T,): q_nope (T, n, nope), q_rope (T, n, rope) rotated, and the cache
    row's two parts, c (T, rank) normed and k_rope (T, rope) rotated.
    The query is one matrix, or (`q_lora_rank`) a low-rank pair with a
    norm between."""
    T = x.shape[0]
    n, nope = cfg.heads, cfg.qk_nope_head_dim
    theta, scaling = cfg.rope_theta, cfg.rope_scaling
    if cfg.q_lora_rank is None:
        q = x @ lp["wq"]
    else:
        q = _rms(x @ lp["wqa"], lp["q_norm"], cfg.rms_eps) @ lp["wqb"]
    q = q.reshape(T, n, cfg.qk_head_dim)
    q_nope = q[..., :nope]
    q_rope = rope(q[..., nope:], pos[:, None], theta, scaling)
    kva = x @ lp["wkva"]
    c = _rms(kva[:, :cfg.kv_lora_rank], lp["kv_norm"], cfg.rms_eps)
    k_rope = rope(kva[:, cfg.kv_lora_rank:], pos, theta, scaling)
    return q_nope, q_rope, c, k_rope


def _cache_rows(cfg, c, k_rope):
    """[c | k_rope | 0] (T, row_width): the row as the arena stores it."""
    import jax.numpy as jnp
    pad = cfg.row_width - cfg.row_values
    parts = [c, k_rope]
    if pad:
        parts.append(jnp.zeros((c.shape[0], pad), c.dtype))
    return jnp.concatenate(parts, -1)


def _wkvb_heads(cfg, lp):
    """(W_UK, W_UV): (rank, n, nope) and (rank, n, v), the key and the
    value half of W_kvb by head."""
    w = lp["wkvb"].reshape(cfg.kv_lora_rank, cfg.heads,
                           cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def _expand(cfg, lp, c, k_rope):
    """Keys (T, n, nope + rope) and values (T, n, v) from cache rows."""
    import jax.numpy as jnp
    w_uk, w_uv = _wkvb_heads(cfg, lp)
    k_nope = jnp.einsum("tc,cnd->tnd", c, w_uk)
    v = jnp.einsum("tc,cnd->tnd", c, w_uv)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, None, :],
                                  k_nope.shape[:2] + k_rope.shape[-1:])], -1)
    return k, v


def _masked_attention(q, k, v, mask, scale, q_block=512):
    """softmax(q k^T scale) v under `mask` (Tq, Tk), float32 scores and
    statistics, by blocks of query rows so that the score matrix of a
    long prompt never exists whole. q (Tq, n, d), k (Tk, n, d), v (Tk,
    n, dv) -> (Tq, n, dv)."""
    import jax
    import jax.numpy as jnp

    def block(args):
        qb, mb = args
        s = jnp.einsum("qnd,knd->nqk", qb, k,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(mb[None], s, -1e30)
        p = jnp.exp(s - jnp.max(s, -1, keepdims=True))
        p = (p / p.sum(-1, keepdims=True)).astype(v.dtype)
        return jnp.einsum("nqk,knd->qnd", p, v)

    tq = q.shape[0]
    if tq <= q_block or tq % q_block:
        return block((q, mask))
    nb = tq // q_block
    out = jax.lax.map(block, (q.reshape(nb, q_block, *q.shape[1:]),
                              mask.reshape(nb, q_block, mask.shape[1])))
    return out.reshape(tq, *out.shape[2:])


def route(cfg, lp, x):
    """The router. x (T, h) -> (picks (T, k) int32, weights (T, k)
    float32), by the config's scoring rule (`cfg.router_scoring`; a
    config without the field is "sigmoid"). "sigmoid": scores are
    sigmoid(x W_g) in float32; the k largest of
    score + correction bias are picked (one group, so no group stage; a
    layer without `router_bias` has no bias: the scores themselves are
    ranked); the weights are the scores WITHOUT the bias at the picks,
    over their sum + 1e-20, times routed_scaling_factor (a config
    without the field: 1, no factor). "softmax": scores are
    softmax(x W_g) over the experts in float32, the k largest are picked
    and their scores divided by their sum (no bias, no factor). The
    router is as wide as the MODEL has experts, whichever of them this
    chip holds (`held_experts`)."""
    import jax
    import jax.numpy as jnp
    logits = jnp.dot(x.astype(jnp.float32), lp["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if getattr(cfg, "router_scoring", "sigmoid") == "softmax":
        w, picks = jax.lax.top_k(jax.nn.softmax(logits, -1),
                                 cfg.experts_per_tok)
        return picks.astype(jnp.int32), w / w.sum(-1, keepdims=True)
    scores = jax.nn.sigmoid(logits)
    if "router_bias" in lp:
        _, picks = jax.lax.top_k(
            scores + lp["router_bias"].astype(jnp.float32),
            cfg.experts_per_tok)
        w = jnp.take_along_axis(scores, picks, -1)
    else:
        w, picks = jax.lax.top_k(scores, cfg.experts_per_tok)
    w = w / (w.sum(-1, keepdims=True) + 1e-20)
    factor = getattr(cfg, "routed_scaling_factor", 1.0)
    if factor != 1.0:
        w = w * factor
    return picks.astype(jnp.int32), w


def held_experts(cfg):
    """(first, count): the routed experts this chip holds, ids first ..
    first + count - 1 of `cfg.n_routed_experts` (`cfg.experts_held`; a
    config without the field, or None, holds them all). The chip's share
    of an expert-parallel deployment: the router scores every expert of
    the model, the layer lays out and computes the picks that fall on
    its own, and what the experts held elsewhere would add is left out
    (no code stands in for the other chips or their exchange)."""
    held = getattr(cfg, "experts_held", None)
    return (0, cfg.n_routed_experts) if held is None else tuple(held)


def expert_product_path(lp):
    """ "grouped_swiglu_kernel" on a TPU for lane-aligned widths;
    "ragged_dot" elsewhere (the CPU)."""
    import jax
    _, h, F = lp["w_gate"].shape
    if h % _LANES == 0 and F % _LANES == 0 \
            and jax.default_backend() == "tpu":
        return "grouped_swiglu_kernel"
    return "ragged_dot"


def grouped_experts(lp, xs, group_sizes, tile, packed=False):
    """The grouped SwiGLU over the routed rows alone: xs (R, h) in the
    layout of ops/grouped_swiglu.routed_positions (sorted by expert,
    every group from a whole tile of `tile` rows on), group_sizes (E,)
    how many rows each expert has. A row between a group's end and its
    tile's is computed for nobody; the tiles past the last group's are
    not to be read. On a TPU one kernel (ops/grouped_swiglu; `packed`,
    the kernel's alone: its rows as `combine_path`'s kernel reads them);
    elsewhere three ragged products over the groups rounded up to the
    tile."""
    import jax
    if expert_product_path(lp) == "grouped_swiglu_kernel":
        from ..ops.grouped_swiglu import grouped_swiglu
        return grouped_swiglu(xs, lp["w_gate"], lp["w_up"], lp["w_down"],
                              group_sizes, tile, packed)
    whole = -(-group_sizes // tile) * tile
    g = jax.lax.ragged_dot(xs, lp["w_gate"], whole)
    u = jax.lax.ragged_dot(xs, lp["w_up"], whole)
    return jax.lax.ragged_dot(jax.nn.silu(g) * u, lp["w_down"], whole)


# From this many bytes of routed products on, a prompt's combine is the
# kernel ops/routed_combine (a row a DMA: 10-20 ns a (token, pick)
# whatever the row's width); below it XLA's gather reads a row in 8-15 ns
# (Mellum's 512 and 1,024 buckets: 36 and 72 MiB of products) or the whole
# sum costs less than a Pallas call (a decode step: 4-8 MiB, 1-8 us), and
# from it on 34-80 ns (command-a's 4,096 bucket, 96 MiB, is the smallest
# that is slow: PERF.md, PR 39).
COMBINE_KERNEL_FROM = 84 << 20


def combine_path(lp, x, rows):
    """ "row_dma_kernel" where the expert product is the kernel, the rows
    are bfloat16 of whole 256 lanes and `rows` of them (static) make
    COMBINE_KERNEL_FROM bytes; "gather" elsewhere (the CPU, a decode
    step, a short prompt)."""
    import jax.numpy as jnp
    h = x.shape[1]
    if expert_product_path(lp) == "grouped_swiglu_kernel" \
            and x.dtype == jnp.bfloat16 and h % (2 * _LANES) == 0 \
            and rows * h * x.dtype.itemsize >= COMBINE_KERNEL_FROM:
        return "row_dma_kernel"
    return "gather"


# A layer that holds `count` of E experts gets T * k * count / E picks on
# average and T * k at the worst. Its routed buffer is sized for
# HELD_SLACK times the average (a SECOND STATIC SIZE beside the worst
# case's), so that dispatch's gather, the kernel's grid and the buffer
# combine reads out of follow the picks that are held (XLA's combine
# still makes T * k row reads, a clipped one for a pick held elsewhere;
# the kernel's fetches the held ones alone); a pass
# whose held picks do not fit there (their groups, each rounded up to
# the tile) takes the other branch of a `lax.cond`, the same code over
# the tokens in E / (count * HELD_SLACK) parts, each of which fits
# whatever its routing: no pick is ever dropped. Below HELD_SPLIT_FROM
# picks (a decode step) the worst case is a few hundred rows and the one
# buffer holds it.
HELD_SLACK = 2
HELD_SPLIT_FROM = 4096


def _lay_out(lp, x, picks, live, groups, tile, slots, average, packed):
    """Dispatch and the experts' product over a buffer for `slots` picks
    (static): picks (T, k) as `routed_positions` takes them, `live` (T,)
    by token or (T, k) by pick, at most `slots` of them live; `average`
    (static) how many are expected (T * k where every expert is held),
    which says whether dispatch places or gathers. Returns (ys, the
    buffer's rows through their experts, `packed` (static) for the
    combine kernel; pos (T, k); group_sizes (groups,))."""
    import jax
    import jax.numpy as jnp
    from ..ops.grouped_swiglu import padded_rows, routed_positions
    T, k = picks.shape
    with jax.named_scope("moe/dispatch"):
        # a pick that is not live has no position: in no group, never
        # moved, never computed
        pos, group_sizes = routed_positions(picks, live, groups, tile)
        at = pos.reshape(-1)
        token = jnp.arange(T * k, dtype=jnp.int32) // k
        rows = padded_rows(slots, groups, tile)
        if 4 * average <= rows:
            # a step's few rows in a buffer that is mostly the experts'
            # round-ups: the rows are PLACED (a gather fetches every row
            # of the buffer, ~15 ns a row whoever's it is: PERF.md, PR 37)
            xs = jnp.zeros((rows, x.shape[1]), x.dtype).at[at].set(
                x[token], mode="drop", unique_indices=True)
        else:
            # whose row each row of the buffer is (nobody's: token 0's,
            # for nobody): the scatter moves T * k integers, the gather
            # the rows
            source = jnp.zeros((rows,), jnp.int32).at[at].set(
                token, mode="drop", unique_indices=True)
            xs = x[source]
    with jax.named_scope("moe/experts"):
        ys = grouped_experts(lp, xs, group_sizes, tile, packed)
    return ys, pos, group_sizes


def _weighted_sum(ys, pos, w, live):
    """XLA's sum of `_combine`: pick by pick, (k, T, h) in
    the weights' type (token-major it would be re-laid for k = 4 and 6),
    then ONE multiply-and-sum over the picks in float32, in pick order;
    a dead pick's `pos` is past the buffer and reads whatever its last
    row holds (a dead token's sum is zeroed here, a dead pick of a live
    token has weight 0: `_moe`). Returns (T, h) float32."""
    import jax.numpy as jnp
    T, k = pos.shape
    back = ys.at[pos.T.reshape(-1)].get(mode="clip").reshape(k, T, -1)
    y = back[0].astype(jnp.float32) * w[:, 0, None]
    for j in range(1, k):
        y = y + back[j].astype(jnp.float32) * w[:, j, None]
    return jnp.where(live[:, None], y, 0)


def _combine(ys, pos, w, live, shared, scale, dtype, by_dma):
    """`moe/combine`'s whole sum: every token's routed products times
    their weights, summed in float32 in pick order, a dead token's sum
    0; plus `shared` (T, h) in float32 (None: no shared expert), times
    `scale` where that is not None; ONE rounding to `dtype`. `by_dma`
    (static, `combine_path`'s) says how `_lay_out` left `ys` and who
    reads it: the buffer's rows (R, h), gathered by XLA
    (`_weighted_sum`), or the kernel's packed rows, fetched by the
    kernel ops/routed_combine, a DMA a row that is someone's (a dead
    token has no position), the shared term and the rounding inside
    it."""
    if by_dma:
        from ..ops.routed_combine import routed_combine
        return routed_combine(ys, pos, w, shared,
                              1.0 if scale is None else scale, dtype)
    return _sum_end(_weighted_sum(ys, pos, w, live), shared, scale, dtype)


def _sum_end(y, shared, scale, dtype):
    """The end of XLA's sum: y (T, h) float32 plus `shared` in float32
    (None: none), times `scale` where that is not None, ONE rounding."""
    import jax.numpy as jnp
    if shared is not None:
        shared = shared.astype(jnp.float32)
        if scale is not None:
            shared = shared * scale
        y = y + shared
    return y.astype(dtype)


def _moe(cfg, lp, x, live):
    """The expert layer's feed-forward on tokens x (T, h), for every
    config that names `n_routed_experts`, `experts_per_tok`,
    `n_shared_experts` (0: no shared expert, none traced) and optionally
    `router_scoring` (see `route`), `experts_held` (see `held_experts`)
    and `shared_expert_combination` ("sum", the default, or "average":
    the shared experts, stored as ONE SwiGLU n times as wide, over their
    count): this block's, models/mellum's and models/command_a's.
    `live` (T,)
    bool: rows that are real (a prefill's padding and a frozen slot's
    ride-along are not: they get no expert and do not count). The routed
    rows are laid out ONCE, by counting (`routed_positions`): the
    dispatch gathers them there, the product computes whole tiles of one
    expert, the weighted sum reads them back by the same positions
    (`_combine`: XLA's gather, or from COMBINE_KERNEL_FROM bytes of
    products on a kernel's row DMAs; one sum, two carriers).
    Returns (y (T, h), counters)."""
    import jax
    import jax.numpy as jnp
    from ..ops.grouped_swiglu import padded_rows, row_tile_for
    T, k, E = x.shape[0], cfg.experts_per_tok, cfg.n_routed_experts
    first, held = held_experts(cfg)
    tile = row_tile_for(T * k, E)
    with jax.named_scope("moe/router"):
        picks, w = route(cfg, lp, x)
    mine, slots, average, parts = live, T * k, T * k, 1
    if held < E:
        picks = picks - first
        mine = live[:, None] & (picks >= 0) & (picks < held)
        # a pick of an expert held elsewhere: its weight divided the sum
        # and multiplies nothing here
        w = jnp.where(mine, w, 0)
        average = -(-T * k * held // E)
        parts = max(1, E // (held * HELD_SLACK))
        if T * k < HELD_SPLIT_FROM or T % parts:
            parts = 1
    if parts > 1:
        slots = T * k // parts
    by_dma = combine_path(
        lp, x, padded_rows(slots, held, tile)) == "row_dma_kernel"
    if parts == 1:
        ys, pos, group_sizes = _lay_out(lp, x, picks, mine, held, tile,
                                        slots, average, by_dma)
    else:
        def routed(x, picks, mine, w, live):
            ys, pos, sizes = _lay_out(lp, x, picks, mine, held, tile, slots,
                                      average, by_dma)
            # the picks' sum alone, in float32: the shared term and the
            # rounding come behind the `lax.cond`, where XLA's sum has them
            with jax.named_scope("moe/combine"):
                return _combine(ys, pos, w, live, None, None, jnp.float32,
                                by_dma), sizes

        def in_parts(*whole):
            # the barrier keeps a part's sum out of the fusion that stacks
            # the parts: fused, XLA wants the whole stack in the combine
            # kernel's scoped VMEM (34 MB of it at 4,096 tokens)
            def one(part):
                done = routed(*part)
                return jax.lax.optimization_barrier(done) if by_dma else done

            ys, sizes = jax.lax.map(one, tuple(
                a.reshape(parts, T // parts, *a.shape[1:]) for a in whole))
            return ys.reshape(T, -1), jnp.sum(sizes, 0)

        sizes = jnp.sum(mine[:, :, None] & (
            picks[:, :, None] == jnp.arange(held, dtype=jnp.int32)),
            (0, 1), dtype=jnp.int32)
        fits = jnp.sum(-(-sizes // tile)) * tile <= \
            padded_rows(slots, held, tile)
        y, group_sizes = jax.lax.cond(fits, routed, in_parts,
                                      x, picks, mine, w, live)
    shared, scale = None, None
    if cfg.n_shared_experts:
        with jax.named_scope("moe/shared"):
            hidden = _swiglu_hidden(x, lp["shared_gate"], lp["shared_up"])
            if by_dma and parts == 1:
                # the shared experts' last product BEHIND the routed
                # kernel, where XLA's own order has it when its fusion
                # reads it: free of the kernel's output the scheduler
                # finished them first and kept their (T, h) beside the
                # routed rows and their products, the layer's peak (80
                # MiB more at Xing's 16k bucket)
                hidden, ys = jax.lax.optimization_barrier((hidden, ys))
            shared = hidden @ lp["shared_down"]
        if getattr(cfg, "shared_expert_combination", "sum") == "average":
            scale = 1.0 / cfg.n_shared_experts
    with jax.named_scope("moe/combine"):
        if parts == 1:
            y = _combine(ys, pos, w, live, shared, scale, x.dtype, by_dma)
        else:
            y = _sum_end(y, shared, scale, x.dtype)
    passes = jnp.any(live).astype(jnp.int32)
    zero = jnp.zeros_like(passes)
    kernel = expert_product_path(lp) == "grouped_swiglu_kernel"
    counters = {"expert_tokens": group_sizes,
                "router_tokens": jnp.sum(live).astype(jnp.int32),
                "experts_touched": jnp.sum(group_sizes > 0).astype(jnp.int32),
                "moe_passes": passes,
                "kernel_passes": passes if kernel else zero,
                # rows the kernel computed: its visits' whole tiles
                "rows_computed": jnp.sum(-(-group_sizes // tile)) * tile
                if kernel else zero,
                "combine_kernel_passes": passes if by_dma else zero}
    return y, counters


def _ffn(cfg, lp, x, live, counters):
    """norm2 + the layer's feed-forward (dense or routed), with the
    counters of a routed layer added to `counters`: the second
    sublayer's f of `_residual`."""
    import jax
    h = _rms(x, lp["norm2"], cfg.rms_eps)
    if "router" not in lp:
        with jax.named_scope("ffn/dense"):
            return _swiglu(h, lp["gate"], lp["up"], lp["down"]), counters, None
    y, c = _moe(cfg, lp, h, live)
    return y, dict(counters, **{name: counters[name] + c[name]
                                for name in c}), None


def _ffn_sublayer(cfg, lp, x, live, counters):
    """The layer's second sublayer around the residual state x."""
    x, counters, _ = _residual(
        cfg, lp.get("hc_ffn"), x,
        lambda u, counters: _ffn(cfg, lp, u, live, counters), live, counters)
    return x, counters


def _zero_counters(cfg):
    import jax.numpy as jnp
    zero = jnp.zeros((), jnp.int32)
    counters = {"expert_tokens": jnp.zeros((held_experts(cfg)[1],),
                                           jnp.int32),
                "router_tokens": zero, "experts_touched": zero,
                "moe_passes": zero, "kernel_passes": zero,
                "rows_computed": zero, "combine_kernel_passes": zero}
    if cfg.hc_mult > 1:
        counters.update(hc_passes=zero, hc_rowsum_dev_ppm=zero)
    return counters


def _head(cfg, params, x):
    import jax
    import jax.numpy as jnp
    with jax.named_scope("head"):
        y = _rms(_streams_out(cfg, x), params["norm_f"], cfg.rms_eps)
        return jnp.dot(y, params["head"],
                       preferred_element_type=jnp.float32)


def _act_dtype(params):
    import jax.numpy as jnp
    return jnp.bfloat16 if params["wte"].dtype == jnp.bfloat16 \
        else jnp.float32


# -- the whole sequence, no cache (tests; generation never runs it) ------------

def forward_logits(params, cfg, tokens):
    """Logits (T, V) float32 of one sequence tokens (T,), expanded
    attention, the grouped expert product: the served math without a
    cache."""
    import jax.numpy as jnp
    T = tokens.shape[0]
    pos = jnp.arange(T)
    x = _streams_in(cfg, params["wte"][tokens].astype(_act_dtype(params)))
    mask = pos[None, :] <= pos[:, None]
    scale = attention_scale(cfg)
    counters = _zero_counters(cfg)
    live = jnp.ones((T,), bool)
    for lp in params["layers"]:
        def attend(u, counters, lp=lp):
            h = _rms(u, lp["norm1"], cfg.rms_eps)
            q_nope, q_rope, c, k_rope = _project(cfg, lp, h, pos)
            k, v = _expand(cfg, lp, c, k_rope)
            q = jnp.concatenate([q_nope, q_rope], -1)
            o = _masked_attention(q, k, v, mask, scale)
            return o.reshape(T, -1) @ lp["wo"], counters, None

        x, counters, _ = _residual(cfg, lp.get("hc_attn"), x, attend, live,
                                   counters)
        x, counters = _ffn_sublayer(cfg, lp, x, live, counters)
    return _head(cfg, params, x)


# -- prefill into the pages: expanded attention --------------------------------

def prefill_pages(params, cfg, tokens, pfx_len, real_len, arena, pages):
    """Prefill ONE sequence's prompt suffix tokens (1, B) (right-padded
    to its bucket; real_len real) at positions pfx_len.., whose first
    pfx_len positions are already cached (a prefix hit; 0 for a cold
    prompt), into the pages of its page row `pages` (P,). Writes the
    suffix's cache rows as whole pages and attends in the expanded form:
    a cold prompt over its own rows, causally; after a prefix hit over
    the whole gathered page row, masked by position. Returns (logits (1,
    V) float32 of position pfx_len + real_len - 1, arena, counters)."""
    import jax
    import jax.numpy as jnp
    from ..ops.flash_attention import flash_causal_rows

    B = tokens.shape[1]
    bs = arena.shape[4]
    L = pages.shape[0] * bs
    dtype = arena.dtype
    scale = attention_scale(cfg)
    on_tpu = jax.default_backend() == "tpu"
    j = jnp.arange(B)
    pos = pfx_len + j
    live = j < real_len
    x = _streams_in(cfg, params["wte"][tokens[0]].astype(dtype))
    counters = _zero_counters(cfg)
    for li, lp in enumerate(params["layers"]):
        def attend(u, counters, arena=arena, li=li, lp=lp):
            with jax.named_scope("mla/project"):
                h = _rms(u, lp["norm1"], cfg.rms_eps)
                q_nope, q_rope, c, k_rope = _project(cfg, lp, h, pos)
                q = jnp.concatenate([q_nope, q_rope], -1)
                rows = _cache_rows(cfg, c, k_rope)
                arena = _write_pages(arena, li, pages, pfx_len, real_len,
                                     rows[:, None, :])

            def cold(arena):
                k, v = _expand(cfg, lp, c, k_rope)
                if on_tpu and B % 128 == 0:
                    return flash_causal_rows(q, k, v, scale, length=real_len)
                return _masked_attention(q, k, v, j[None, :] <= j[:, None],
                                         scale)

            def warm(arena):
                cached = _gather_pages(arena, li, pages)[0]       # (L, W)
                k, v = _expand(
                    cfg, lp, cached[:, :cfg.kv_lora_rank],
                    cached[:, cfg.kv_lora_rank:cfg.row_values])
                return _masked_attention(
                    q, k, v, jnp.arange(L)[None, :] <= pos[:, None], scale)

            with jax.named_scope("mla/attend"):
                o = jax.lax.cond(pfx_len == 0, cold, warm, arena)
            with jax.named_scope("mla/project"):
                return o.reshape(B, -1) @ lp["wo"], counters, arena

        x, counters, arena = _residual(cfg, lp.get("hc_attn"), x, attend,
                                       live, counters)
        x, counters = _ffn_sublayer(cfg, lp, x, live, counters)
    last = x[real_len - 1][None]
    return _head(cfg, params, last), arena, counters


# -- decode through the pages: absorbed attention ------------------------------

def decode_attention_path(arena, arena_constraint=None):
    """ "latent_paged_kernel" on a TPU over the bare arena with a
    lane-aligned row; "gather" elsewhere (the CPU)."""
    import jax
    if (not isinstance(arena, tuple) and arena_constraint is None
            and arena.shape[-1] % _LANES == 0
            and jax.default_backend() == "tpu"):
        return "latent_paged_kernel"
    return "gather"


def absorbed_attention(q_ext, rows, mask):
    """The gather form of the absorbed step: q_ext (S, n, W) scaled,
    rows (S, L, W) each slot's cached rows, mask (S, L). Returns the
    context (S, n, W) in the rows' space."""
    import jax.numpy as jnp
    s = jnp.einsum("snw,slw->snl", q_ext, rows,
                   preferred_element_type=jnp.float32)
    s = jnp.where(mask[:, None, :], s, -1e30)
    p = jnp.exp(s - jnp.max(s, -1, keepdims=True))
    p = (p / p.sum(-1, keepdims=True)).astype(rows.dtype)
    return jnp.einsum("snl,slw->snw", p, rows)


def decode_step_pages(params, cfg, tokens, arena, pt, ts, done=None,
                      attention=None):
    """One decode step of every slot: tokens, ts (S,), pt (S, P). Writes
    each live slot's cache row at position ts and attends over 0..ts in
    the absorbed form. A frozen slot (`done`) writes to the scratch
    block (the gather) or nowhere (the kernel) and its logits are the
    caller's to discard. Returns (logits (S, V) float32, arena,
    counters)."""
    import jax
    import jax.numpy as jnp

    s_dim, P = pt.shape
    bs = arena.shape[4]
    dtype = arena.dtype
    rank = cfg.kv_lora_rank
    scale = attention_scale(cfg)
    if attention is None:
        attention = decode_attention_path(arena)
    if attention == "latent_paged_kernel":
        from ..ops.paged_attention import latent_paged_attention
    live = jnp.ones((s_dim,), bool) if done is None else ~done
    x = _streams_in(cfg, params["wte"][tokens].astype(dtype))
    counters = _zero_counters(cfg)
    pad = cfg.row_width - cfg.row_values
    for li, lp in enumerate(params["layers"]):
        def attend(u, counters, arena=arena, li=li, lp=lp):
            with jax.named_scope("mla/project"):
                h = _rms(u, lp["norm1"], cfg.rms_eps)
                q_nope, q_rope, c, k_rope = _project(cfg, lp, h, ts)
                row = _cache_rows(cfg, c, k_rope)
            with jax.named_scope("mla/absorb"):
                w_uk, w_uv = _wkvb_heads(cfg, lp)
                q_lat = jnp.einsum("snd,cnd->snc", q_nope, w_uk)
                parts = [q_lat, q_rope]
                if pad:
                    parts.append(jnp.zeros(q_rope.shape[:2] + (pad,), dtype))
                q_ext = (jnp.concatenate(parts, -1).astype(jnp.float32)
                         * scale).astype(dtype)
            with jax.named_scope("mla/attend"):
                if attention == "latent_paged_kernel":
                    o_ext, arena = latent_paged_attention(
                        q_ext, row, arena, li, pt, ts, done)
                else:
                    wblk = pt[jnp.arange(s_dim), ts // bs]
                    if done is not None:
                        wblk = jnp.where(done, 0, wblk)
                    arena = arena.at[li, 0, wblk, 0, ts % bs].set(row)
                    cached = _gather_pages(arena, li, pt)[:, 0]   # (S, L, W)
                    o_ext = absorbed_attention(
                        q_ext, cached,
                        jnp.arange(P * bs)[None, :] <= ts[:, None])
            with jax.named_scope("mla/absorb"):
                o = jnp.einsum("snc,cnd->snd", o_ext[..., :rank], w_uv)
            with jax.named_scope("mla/project"):
                return o.reshape(s_dim, -1) @ lp["wo"], counters, arena

        x, counters, arena = _residual(cfg, lp.get("hc_attn"), x, attend,
                                       live, counters)
        x, counters = _ffn_sublayer(cfg, lp, x, live, counters)
    return _head(cfg, params, x), arena, counters


# -- the engine's view of this model ------------------------------------------

class _MoonlightServingModel(ServingModel):
    """The latent-attention + expert block as the engine sees it; one
    class for every config of it, named by the config."""
    features = frozenset()

    def __init__(self, name):
        self.name = name

    def max_positions(self, cfg):
        return cfg.max_pos

    def cache_spec(self, cfg):
        return CacheSpec(cfg.layers, 1, cfg.row_width)

    def activation_dtype(self, params):
        return _act_dtype(params)

    def decode_attention_path(self, arena, arena_constraint=None):
        return decode_attention_path(arena, arena_constraint)

    def counter_names(self, cfg):
        # expert_tokens[e]: rows routed to expert e, and router_tokens:
        # tokens routed (each layer counts), by both programs since
        # start; the decode_* three by the decode step alone: tokens
        # routed, experts that had a row, and passes of an expert layer
        # with a live slot (what a step's expert bytes are counted from);
        # moe_kernel_passes: passes of an expert layer, a prefill's six
        # and a decode step's, whose product was the grouped kernel (0
        # where `ragged_dot` ran: every backend but the TPU), and
        # moe_rows_computed: the rows those passes computed (visits x row
        # tile), so sum(expert_tokens) / moe_rows_computed is the share of
        # the kernel's rows that were someone's (0 rows without it);
        # moe_combine_kernel_passes: those of them whose combine was the
        # kernel ops/routed_combine (`combine_path`: a prompt of
        # COMBINE_KERNEL_FROM bytes of products; no decode step). With
        # residual streams (`hc_mult` > 1) also hc_passes: sublayers
        # mixed, by both programs, and hc_rowsum_dev_ppm: the sum over
        # those of the largest |row sum of H_res - 1| at a live token,
        # in parts per million (their ratio is a sublayer's mean)
        names = {"expert_tokens": (cfg.n_routed_experts,),
                 "router_tokens": (), "decode_router_tokens": (),
                 "decode_experts_touched": (), "decode_moe_passes": (),
                 "moe_kernel_passes": (), "moe_rows_computed": (),
                 "moe_combine_kernel_passes": ()}
        if cfg.hc_mult > 1:
            names.update(hc_passes=(), hc_rowsum_dev_ppm=())
        return names

    @staticmethod
    def _counters(c, decode):
        """The block's counters under the engine's names; the decode_*
        three count the decode step's alone."""
        import jax.numpy as jnp
        zero = jnp.zeros((), jnp.int32)
        out = {"expert_tokens": c["expert_tokens"],
               "router_tokens": c["router_tokens"],
               "decode_router_tokens": c["router_tokens"] if decode else zero,
               "decode_experts_touched":
                   c["experts_touched"] if decode else zero,
               "decode_moe_passes": c["moe_passes"] if decode else zero,
               "moe_kernel_passes": c["kernel_passes"],
               "moe_rows_computed": c["rows_computed"],
               "moe_combine_kernel_passes": c["combine_kernel_passes"]}
        out.update({name: c[name] for name in c if name.startswith("hc_")})
        return out

    def prefill(self, params, cfg, tokens, pfx_len, real_len, arena, pages,
                adapters=None, adapter_id=None):
        logits, arena, c = prefill_pages(params, cfg, tokens, pfx_len,
                                         real_len, arena, pages)
        return logits, arena, self._counters(c, decode=False)

    def decode_step(self, params, cfg, tokens, arena, pt, ts, done, *,
                    adapters=None, adapter_ids=None, arena_constraint=None):
        logits, arena, c = decode_step_pages(
            params, cfg, tokens, arena, pt, ts, done,
            attention=decode_attention_path(arena, arena_constraint))
        return logits, arena, self._counters(c, decode=True)


MOONLIGHT_SERVING_MODEL = _MoonlightServingModel("Moonlight-16B-A3B")
