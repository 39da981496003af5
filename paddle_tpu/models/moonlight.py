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
    normalised and scaled) plus one shared SwiGLU: the shared expert layer,
    models/_experts.py, which this file configures and does not contain.

This file is the residual mixer and the block's programs. Shared,
imported and not copied: the latent attention itself (projections, the
cache row, the expanded prefill and the absorbed step, its path verdict)
from models/_latent.py, which a hybrid model's latent layers run too;
`rms`, `rope` (YaRN), `masked_attention`, `head` from models/_decoder.py;
`ffn`, the counters and the serving class's half from models/_experts.py;
the page reads and writes, the kernel rule and the cold-or-warm switch
from serving/pages.py.

What a config may change in the block (defaults are Moonlight's, whose
program they leave as it was, to the bit):
  * `q_lora_rank`: the query through a low-rank pair with a norm
    between, `q = RMSNorm(h W_qa; q_norm) W_qb`;
  * `mla_use_nope` (the published key of a model whose latent layers
    carry no positions; False here): the rotation is an argument of the
    latent attention, `_latent.project`: on, the 64 "rope" values of q
    and k are rotated; off, they are projected, cached and scored as
    they are;
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

from ..serving import pages as _pages
from ..serving.model import CacheSpec
from . import _decoder, _experts, _latent

__all__ = ["MoonlightConfig", "init_params", "forward_logits",
           "prefill_pages", "decode_step_pages", "hc_coefficients",
           "MOONLIGHT_SERVING_MODEL"]


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
                 mla_use_nope=False, name="Moonlight-16B-A3B"):
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
        # True: the latent attention carries no positions (nothing rotated)
        self.mla_use_nope = bool(mla_use_nope)
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
        return -(-self.row_values // _pages.LANES) * _pages.LANES

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


def _head(cfg, params, x):
    """The shared head over what the last layer hands on: x, or the sum of
    its n streams (in the head's scope, float32)."""
    import jax
    import jax.numpy as jnp
    if cfg.hc_mult > 1:
        with jax.named_scope("head"):
            x = x.astype(jnp.float32).sum(-2).astype(x.dtype)
    return _decoder.head(cfg, params, x)


def _ffn_sublayer(cfg, lp, x, live, counters):
    """The layer's second sublayer around the residual state x."""
    x, counters, _ = _residual(
        cfg, lp.get("hc_ffn"), x,
        lambda u, counters: _experts.ffn(cfg, lp, u, live, counters), live,
        counters)
    return x, counters


def _zero_counters(cfg):
    """The expert layer's counters and, with residual streams, the
    mixer's two."""
    import jax.numpy as jnp
    counters = _experts.zero_counters(cfg)
    if cfg.hc_mult > 1:
        zero = jnp.zeros((), jnp.int32)
        counters.update(hc_passes=zero, hc_rowsum_dev_ppm=zero)
    return counters


# -- the whole sequence, no cache (tests; generation never runs it) ------------

def forward_logits(params, cfg, tokens):
    """Logits (T, V) float32 of one sequence tokens (T,), expanded
    attention, the grouped expert product: the served math without a
    cache."""
    import jax.numpy as jnp
    T = tokens.shape[0]
    pos = jnp.arange(T)
    x = _streams_in(cfg, params["wte"][tokens].astype(
        _decoder.act_dtype(params)))
    mask = pos[None, :] <= pos[:, None]
    scale = _latent.attention_scale(cfg)
    counters = _zero_counters(cfg)
    live = jnp.ones((T,), bool)
    for lp in params["layers"]:
        def attend(u, counters, lp=lp):
            h = _decoder.rms(u, lp["norm1"], cfg.rms_eps)
            q_nope, q_rope, c, k_rope = _latent.project(cfg, lp, h, pos)
            k, v = _latent.expand(cfg, lp, c, k_rope)
            q = jnp.concatenate([q_nope, q_rope], -1)
            o = _decoder.masked_attention(q, k, v, mask, scale)
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
    import jax.numpy as jnp

    B = tokens.shape[1]
    dtype = arena.dtype
    flash = _pages.kernel_beside(bucket=B)
    j = jnp.arange(B)
    pos = pfx_len + j
    live = j < real_len
    x = _streams_in(cfg, params["wte"][tokens[0]].astype(dtype))
    counters = _zero_counters(cfg)
    for li, lp in enumerate(params["layers"]):
        def attend(u, counters, arena=arena, li=li, lp=lp):
            y, arena = _latent.prefill_attend(
                cfg, lp, u, j, pos, arena, li, pages, pfx_len, real_len,
                flash)
            return y, counters, arena

        x, counters, arena = _residual(cfg, lp.get("hc_attn"), x, attend,
                                       live, counters)
        x, counters = _ffn_sublayer(cfg, lp, x, live, counters)
    last = x[real_len - 1][None]
    return _head(cfg, params, last), arena, counters


# -- decode through the pages: absorbed attention ------------------------------

def decode_step_pages(params, cfg, tokens, arena, pt, ts, done=None,
                      attention=None):
    """One decode step of every slot: tokens, ts (S,), pt (S, P). Writes
    each live slot's cache row at position ts and attends over 0..ts in
    the absorbed form. A frozen slot (`done`) writes to the scratch
    block (the gather) or nowhere (the kernel) and its logits are the
    caller's to discard. Returns (logits (S, V) float32, arena,
    counters)."""
    import jax.numpy as jnp

    s_dim = pt.shape[0]
    dtype = arena.dtype
    if attention is None:
        attention = _latent.decode_attention_path(arena)
    live = jnp.ones((s_dim,), bool) if done is None else ~done
    x = _streams_in(cfg, params["wte"][tokens].astype(dtype))
    counters = _zero_counters(cfg)
    for li, lp in enumerate(params["layers"]):
        def attend(u, counters, arena=arena, li=li, lp=lp):
            y, arena = _latent.step_attend(cfg, lp, u, ts, arena, li, pt,
                                           done, attention)
            return y, counters, arena

        x, counters, arena = _residual(cfg, lp.get("hc_attn"), x, attend,
                                       live, counters)
        x, counters = _ffn_sublayer(cfg, lp, x, live, counters)
    return _head(cfg, params, x), arena, counters


# -- the engine's view of this model ------------------------------------------

class _MoonlightServingModel(_experts.ExpertBlockModel):
    """The latent-attention + expert block as the engine sees it; one
    class for every config of it, named by the config."""
    prefill_pages = staticmethod(prefill_pages)
    decode_step_pages = staticmethod(decode_step_pages)

    def cache_spec(self, cfg):
        return CacheSpec(cfg.layers, 1, cfg.row_width)

    def decode_attention_path(self, arena, arena_constraint=None):
        return _latent.decode_attention_path(arena, arena_constraint)

    def counter_names(self, cfg):
        # with residual streams also hc_passes: sublayers mixed, and
        # hc_rowsum_dev_ppm: the sum over those of the largest |row sum of
        # H_res - 1| at a live token, in parts per million
        names = super().counter_names(cfg)
        if cfg.hc_mult > 1:
            names.update(hc_passes=(), hc_rowsum_dev_ppm=())
        return names


MOONLIGHT_SERVING_MODEL = _MoonlightServingModel("Moonlight-16B-A3B")
