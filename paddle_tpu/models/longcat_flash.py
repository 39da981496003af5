"""LongCat-Flash's language model (`LongCat-Flash-Omni`, `-Chat`: the
same text stack) on the serving path: a DOUBLE layer of two latent
attentions and two dense feed-forwards, with ONE expert layer on a
SHORTCUT round the second half, whose router scores the routed experts
and, behind them, identity experts that cost nothing.

Served through serving.model.ServingModel by the same engine, scheduler,
page table and fused chunk loop as every other model. One layer `l` on
the stream x (every norm an RMSNorm with its own weight):

    a0 = x  + MLA[l,0](norm_in[l,0](x))          cache layer 2l
    u0 = norm_post[l,0](a0)
    s  = MoE[l](u0)                               the shortcut: not added yet
    b0 = a0 + FFN[l,0](u0)                        dense SwiGLU
    a1 = b0 + MLA[l,1](norm_in[l,1](b0))         cache layer 2l + 1
    b1 = a1 + FFN[l,1](norm_post[l,1](a1))
    x' = b1 + s                                   it lands at the layer's end

  * TWO attentions a layer, so the cache's layers are not the model's:
    `cache_spec` is ONE latent group of `2 * layers` layers and the
    programs address layer `2l + i`. The attention is models/_latent.py's
    (Moonlight's: a cached row `[c | k_rope]`, the expanded prefill, the
    absorbed step) with a low-rank query and the two published scales,
    `mla_scale_q_lora` and `mla_scale_kv_lora`: q times sqrt(hidden /
    q_lora_rank), both its parts, before rotation; the normed latent c
    times sqrt(hidden / kv_lora_rank), so the cached row holds the scaled
    latent; the shared rope key is not scaled (`_latent.project`).
  * The branches are NOT a chain: `s` reads u0 alone and is consumed two
    sub-blocks later. The programs write the dataflow as published and add
    nothing that orders it: where the expert layer runs beside the dense
    half is the compiler's to choose.
  * The expert layer is models/_experts.py's with the third routing rule
    (`route`): softmax over `n_routed_experts + zero_expert_num` outputs in
    float32; the `experts_per_tok` largest of score + `router_bias` are
    picked (the bias RANKS and does not weigh); the weights are the scores
    at the picks times `routed_scaling_factor`, NOT renormalised. A pick
    below `n_routed_experts` is a SwiGLU expert (`experts_held = (first,
    count)`: those this chip holds; a pick held elsewhere adds nothing
    here); a pick from `n_routed_experts` on is an IDENTITY expert: its
    weight times u0 (`moe/identity`), no row laid out, no tile computed.
    No shared expert, no leading dense layer.
  * `vocab_size` is the rows of the embedding and of the untied head HELD
    here; `vocab_slice` (first, rows, of) names them in the published
    vocabulary.

Parameters (`x @ W`, W is (in, out); no bias but the router's): wte (V,
h), head (h, V), norm_f (h,), layers[l]: "attn": two of {norm1 (h,), wqa
(h, r), q_norm (r,), wqb (r, n (nope + rope)), wkva (h, rank + rope),
kv_norm (rank,), wkvb (rank, n (nope + v)), wo (n v, h)}; "ffn": two of
{norm2 (h,), gate, up (h, I), down (I, h)}; "moe": {router (h, E + Z),
router_bias (E + Z,) float32, w_gate, w_up (held, h, F), w_down (held, F,
h)}.

Named scopes: `embed`, `norm` (the two post-attention norms),
`mla/project|absorb|attend`, `ffn/dense`, `moe/router|dispatch|experts|
identity|combine`, `head`. In-graph counters beside the expert layer's
(`_experts.counter_names`: with identity experts also
`moe_identity_picks`, `moe_expert_picks`, `moe_held_picks`,
`moe_real_picks_hist`): `mla_decode_rows` (live positions x cache layers
a step) and command-a's `moe_picks_routed` (live tokens x
`experts_per_tok`, over the router's WHOLE width) / `moe_picks_held`.

Refused by the engine (`serving.model.require_features`: `features` is
empty): int8 weights or cache, adapters, speculation, a mesh plan and
chunked prefill, none of which this block has written. Host swap,
migration and prefix hits are the engine's own over the block axis of
the one group and are served as Moonlight's are. Not built, stubbed or
named anywhere: the expert-parallel exchange, the other chips, and the
Omni model's audio and vision encoders and codec decoder.
"""

from __future__ import annotations

import math

from ..serving import pages as _pages
from ..serving.model import CacheSpec
from . import _decoder, _experts, _latent

__all__ = ["LongcatFlashConfig", "init_params", "forward_logits",
           "prefill_pages", "decode_step_pages",
           "LONGCAT_FLASH_SERVING_MODEL"]


class LongcatFlashConfig:
    """The published keys under this package's names (defaults are
    LongCat-Flash-Omni's `config.json`, whole: every expert and the whole
    vocabulary held) and what the published text leaves open (`init_range`,
    `router_bias_std`: benchmarks/configs/longcat-flash-omni.json
    `assumed`)."""

    # what models/_latent.py and models/_experts.py read beside the keys
    rope_scaling = None
    mla_use_nope = False
    n_shared_experts = 0
    router_scoring = "softmax"
    router_renormalize = False

    def __init__(self, vocab_size=131072, hidden=6144, layers=28, heads=64,
                 q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, intermediate=12288,
                 moe_intermediate=2048, n_routed_experts=512,
                 zero_expert_num=256, experts_per_tok=12,
                 routed_scaling_factor=6.0, rms_eps=1e-5, rope_theta=1e7,
                 mla_scale_q_lora=True, mla_scale_kv_lora=True,
                 experts_held=None, vocab_slice=None, max_pos=131072,
                 init_range=0.02, router_bias_std=1e-4,
                 name="LongCat-Flash-Omni"):
        if experts_held is not None:
            first, count = experts_held
            if not (0 <= first and 0 < count
                    and first + count <= n_routed_experts):
                raise ValueError(f"experts_held {experts_held!r} are not "
                                 f"experts of {n_routed_experts}")
            experts_held = (int(first), int(count))
        if vocab_slice is None:
            vocab_slice = (0, vocab_size, vocab_size)
        if vocab_slice[1] != vocab_size \
                or sum(vocab_slice[:2]) > vocab_slice[2]:
            raise ValueError(f"vocab_slice {vocab_slice!r} (first, rows, of) "
                             f"does not name {vocab_size} rows of a "
                             "vocabulary")
        if experts_per_tok > n_routed_experts + zero_expert_num:
            raise ValueError(f"{experts_per_tok} picks of "
                             f"{n_routed_experts + zero_expert_num} outputs")
        self.vocab_size = vocab_size
        self.hidden = hidden
        self.layers = layers
        self.heads = heads
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.intermediate = intermediate
        self.moe_intermediate = moe_intermediate
        self.n_routed_experts = n_routed_experts
        self.zero_expert_num = zero_expert_num
        self.experts_per_tok = experts_per_tok
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.rms_eps = rms_eps
        self.rope_theta = rope_theta
        # the published switches, and what models/_latent.py reads of them
        self.mla_scale_q_lora = bool(mla_scale_q_lora)
        self.mla_scale_kv_lora = bool(mla_scale_kv_lora)
        self.mla_q_scale = math.sqrt(hidden / q_lora_rank) \
            if mla_scale_q_lora else 1.0
        self.mla_kv_scale = math.sqrt(hidden / kv_lora_rank) \
            if mla_scale_kv_lora else 1.0
        self.experts_held = experts_held
        self.vocab_slice = tuple(int(n) for n in vocab_slice)
        self.max_pos = max_pos
        # ASSUMED (not keys of the published config)
        self.init_range = init_range
        self.router_bias_std = router_bias_std
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
    def cache_layers(self):
        """Layers of the latent group: two attentions a model layer."""
        return 2 * self.layers

    def serving_model(self):
        if self.name == LONGCAT_FLASH_SERVING_MODEL.name:
            return LONGCAT_FLASH_SERVING_MODEL
        return _LongcatFlashServingModel(self.name)


def init_params(cfg: LongcatFlashConfig, key, dtype):
    """Seeded random weights on the default device, ONE jitted maker for
    the one kind of layer (called once a layer) and one for the top:
    normal(0, init_range) matrices, unit norms, the held experts'
    matrices alone. The router's correction bias is normal(0,
    `router_bias_std`) in float32: softmax over 768 outputs gives scores
    near 1/768, so a bias of the sigmoid models' 0.01 would choose the same
    12 outputs for every token; 1e-4 ranks without deciding."""
    import jax
    import jax.numpy as jnp

    h, n, r = cfg.hidden, cfg.heads, cfg.q_lora_rank
    W = _experts.router_width(cfg)
    E, F = _experts.held_experts(cfg)[1], cfg.moe_intermediate
    std = cfg.init_range

    def normal(k, shape):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    attn = {"wqa": (h, r), "wqb": (r, n * cfg.qk_head_dim),
            "wkva": (h, cfg.row_values),
            "wkvb": (cfg.kv_lora_rank,
                     n * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "wo": (n * cfg.v_head_dim, h)}
    ffn = {"gate": (h, cfg.intermediate), "up": (h, cfg.intermediate),
           "down": (cfg.intermediate, h)}
    moe = {"router": (h, W), "w_gate": (E, h, F), "w_up": (E, h, F),
           "w_down": (E, F, h)}

    def part(shapes, k, **ones):
        ks = jax.random.split(k, len(shapes))
        made = {name: normal(kk, shape)
                for (name, shape), kk in zip(shapes.items(), ks)}
        made.update({name: jnp.ones((width,), dtype)
                     for name, width in ones.items()})
        return made

    def layer(k):
        ks = jax.random.split(k, 6)
        lp = {"attn": tuple(part(attn, kk, norm1=h, q_norm=r,
                                 kv_norm=cfg.kv_lora_rank)
                            for kk in ks[:2]),
              "ffn": tuple(part(ffn, kk, norm2=h) for kk in ks[2:4]),
              "moe": part(moe, ks[4])}
        lp["moe"]["router_bias"] = cfg.router_bias_std * jax.random.normal(
            ks[5], (W,), jnp.float32)
        return lp

    def top(k):
        k1, k2 = jax.random.split(k)
        return {"wte": normal(k1, (cfg.vocab_size, h)),
                "head": normal(k2, (h, cfg.vocab_size)),
                "norm_f": jnp.ones((h,), dtype)}

    make = jax.jit(layer)
    keys = jax.random.split(key, cfg.layers + 1)
    params = jax.jit(top)(keys[-1])
    params["layers"] = [make(keys[li]) for li in range(cfg.layers)]
    return params


# -- the double layer ---------------------------------------------------------

def _double_layer(cfg, lp, x, arena, live, counters, attend):
    """One layer on the stream x (T, h) as published: `attend(i, u, arena)`
    -> (y, arena) is the i-th latent attention sublayer (its own norm
    first) of rows u over the cache `arena` (None: no cache). Returns (x',
    arena, counters). The shortcut `s` is computed from u0 and added last;
    nothing here says when."""
    import jax

    def post_norm(i, a):
        with jax.named_scope("norm"):
            return _decoder.rms(a, lp["ffn"][i]["norm2"], cfg.rms_eps)

    def dense(i, u):
        f = lp["ffn"][i]
        with jax.named_scope("ffn/dense"):
            return _experts.swiglu(u, f["gate"], f["up"], f["down"])

    y, arena = attend(0, x, arena)
    a0 = x + y
    u0 = post_norm(0, a0)
    s, counters = _experts.experts(cfg, lp["moe"], u0, live, counters)
    b0 = a0 + dense(0, u0)
    y, arena = attend(1, b0, arena)
    a1 = b0 + y
    b1 = a1 + dense(1, post_norm(1, a1))
    return b1 + s, arena, counters


def _zero_counters(cfg):
    import jax.numpy as jnp
    return dict(_experts.zero_counters(cfg),
                mla_decode_rows=jnp.zeros((), jnp.int32))


# -- the whole sequence, no cache (tests; generation never runs it) -----------

def forward_logits(params, cfg, tokens):
    """Logits (T, V) float32 of one sequence tokens (T,): the served math
    without a cache (the expanded latent attention, the grouped experts)."""
    import jax.numpy as jnp
    T = tokens.shape[0]
    pos = jnp.arange(T)
    x = _decoder.embed(params, tokens, _decoder.act_dtype(params))
    mask = pos[None, :] <= pos[:, None]
    scale = _latent.attention_scale(cfg)
    counters = _zero_counters(cfg)
    live = jnp.ones((T,), bool)
    for lp in params["layers"]:
        def attend(i, u, arena, lp=lp):
            ap = lp["attn"][i]
            h = _decoder.rms(u, ap["norm1"], cfg.rms_eps)
            q_nope, q_rope, c, k_rope = _latent.project(cfg, ap, h, pos)
            k, v = _latent.expand(cfg, ap, c, k_rope)
            q = jnp.concatenate([q_nope, q_rope], -1)
            o = _decoder.masked_attention(q, k, v, mask, scale)
            return o.reshape(T, -1) @ ap["wo"], arena

        x, _, counters = _double_layer(cfg, lp, x, None, live, counters,
                                       attend)
    return _decoder.head(cfg, params, x)


# -- prefill into the pages: expanded attention -------------------------------

def prefill_pages(params, cfg, tokens, pfx_len, real_len, arena, pages):
    """Prefill ONE sequence's prompt suffix tokens (1, B) (right-padded to
    its bucket; real_len real) at positions pfx_len.., whose first pfx_len
    positions are already cached (a prefix hit; 0 for a cold prompt), into
    the pages of its page row `pages` (P,): attention i of layer l writes
    cache layer 2l + i. Returns (logits (1, V) float32 of position pfx_len
    + real_len - 1, arena, counters)."""
    import jax.numpy as jnp

    B = tokens.shape[1]
    flash = _pages.kernel_beside(bucket=B)
    j = jnp.arange(B)
    pos = pfx_len + j
    live = j < real_len
    x = _decoder.embed(params, tokens[0], arena.dtype)
    counters = _zero_counters(cfg)
    for li, lp in enumerate(params["layers"]):
        def attend(i, u, arena, li=li, lp=lp):
            return _latent.prefill_attend(
                cfg, lp["attn"][i], u, j, pos, arena, 2 * li + i, pages,
                pfx_len, real_len, flash)

        x, arena, counters = _double_layer(cfg, lp, x, arena, live, counters,
                                           attend)
    last = x[real_len - 1][None]
    return _decoder.head(cfg, params, last), arena, counters


# -- decode through the pages: absorbed attention -----------------------------

def decode_step_pages(params, cfg, tokens, arena, pt, ts, done=None,
                      attention=None):
    """One decode step of every slot: tokens, ts (S,), pt (S, P). Each of a
    layer's two attentions writes every live slot's row at position ts into
    ITS cache layer (2l, 2l + 1) and attends over 0..ts in the absorbed
    form. A frozen slot (`done`) writes to the scratch block (the gather)
    or nowhere (the kernel). Returns (logits (S, V) float32, arena,
    counters)."""
    import jax.numpy as jnp

    s_dim = pt.shape[0]
    if attention is None:
        attention = _latent.decode_attention_path(arena)
    live = jnp.ones((s_dim,), bool) if done is None else ~done
    x = _decoder.embed(params, tokens, arena.dtype)
    counters = _zero_counters(cfg)
    for li, lp in enumerate(params["layers"]):
        def attend(i, u, arena, li=li, lp=lp):
            return _latent.step_attend(
                cfg, lp["attn"][i], u, ts, arena, 2 * li + i, pt, done,
                attention)

        x, arena, counters = _double_layer(cfg, lp, x, arena, live, counters,
                                           attend)
    counters["mla_decode_rows"] = (
        jnp.sum(jnp.where(live, ts + 1, 0)).astype(jnp.int32)
        * cfg.cache_layers)
    return _decoder.head(cfg, params, x), arena, counters


# -- the engine's view of this model ------------------------------------------

class _LongcatFlashServingModel(_experts.ExpertBlockModel):
    prefill_pages = staticmethod(prefill_pages)
    decode_step_pages = staticmethod(decode_step_pages)

    # the picks as command-a counts them: routed = live tokens x
    # experts_per_tok a layer over the router's whole width (identity
    # picks among them), held = those whose expert is held here
    own_counters = ("mla_decode_rows", "moe_picks_routed", "moe_picks_held",
                    "decode_moe_picks_routed", "decode_moe_picks_held")

    def cache_spec(self, cfg):
        return CacheSpec(cfg.cache_layers, 1, cfg.row_width)

    def decode_attention_path(self, arena, arena_constraint=None):
        return _latent.decode_attention_path(arena, arena_constraint)

    def prefill_attention_path(self, arena, bucket, arena_constraint=None):
        return "flash" if _pages.kernel_beside(bucket=bucket) else "gather"

    def describe(self, cfg):
        first, count = _experts.held_experts(cfg)
        return {"experts_held": {"first": first, "count": count,
                                 "of": cfg.n_routed_experts},
                "identity_experts": cfg.zero_expert_num,
                "router_width": _experts.router_width(cfg),
                "cache_layers": cfg.cache_layers,
                "vocab_slice": dict(zip(("first", "rows", "of"),
                                        cfg.vocab_slice))}

    def _counters(self, cfg, c, decode):
        import jax.numpy as jnp
        routed = c["router_tokens"] * cfg.experts_per_tok
        held = jnp.sum(c["expert_tokens"]).astype(jnp.int32)
        return super()._counters(cfg, dict(
            c, moe_picks_routed=routed, moe_picks_held=held,
            decode_moe_picks_routed=routed, decode_moe_picks_held=held),
            decode)


LONGCAT_FLASH_SERVING_MODEL = _LongcatFlashServingModel("LongCat-Flash-Omni")
