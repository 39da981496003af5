"""command-a-plus-05-2026 (`model_type: cohere2_moe`) on the serving path:
a PARALLEL block (one LayerNorm feeds attention and the experts side by
side), grouped-query attention whose full layers carry no positions, and
sigmoid-routed experts of which this chip may hold a SHARE.

Served through serving.model.ServingModel by the same engine, scheduler,
cache manager and fused chunk loop as every other model:

  * a layer is `u = LN(x); x' = x + Attn(u) + FFN(u)`: ONE norm a layer,
    Cohere's LayerNorm (the mean removed, divided by sqrt(var + eps), a
    learned scale, no bias), statistics in float32; a final LN before
    the head; the head is the embedding, transposed
    (`tie_word_embeddings`), times `logit_scale`;
  * 128 query heads over 8 KV heads, no bias, no q/k norm, softmax scale
    head_dim^-0.5; a "sliding_attention" layer (window 4096) rotates q
    and k on all `head_dim` values in INTERLEAVED pairs (`rope_gptj`), a
    "full_attention" layer has NO positions at all. The two kinds are
    the two cache groups of models/_grouped.py, prefilled through the
    banded / full flash forward and decoded through the grouped paged
    kernel, by that module's pieces;
  * every layer is sparse: the shared expert layer (models/_experts.py)
    with sigmoid scoring, no correction bias, no scaling factor, the
    picks' scores over their sum; the shared experts combined by their
    MEAN (`shared_expert_combination = "average"`); `experts_held =
    (first, count)`: the routed experts this chip holds of
    `n_routed_experts` (None: all; `_experts.held_experts`);
  * `vocab_size` is the rows of the embedding HELD here; `vocab_slice`
    (first, rows, of) names them in the published vocabulary. Token ids
    are the slice's own (0 .. rows - 1): the traffic draws them there,
    and the logits and the sampling are over the slice.

Shared, not copied: models/_grouped.py's cache groups, prefill and decode
attention, path verdicts and serving class; models/_experts.py's layer and
counters; models/_decoder.py's `rope` and `embed`; serving/pages.py's page
and ring writes.

Parameters: `_grouped.init_params`' tree (ONE `norm` a layer, the held
experts' matrices alone, no `head`).

Named scopes: `embed`, `norm`, `attn/project`, `attn/window`,
`attn/full`, `moe/router`, `moe/dispatch`, `moe/experts`, `moe/shared`,
`moe/combine`, `head`. In-graph counters beside the expert layer's and
the cache groups' `decode_rows_*`: `moe_picks_routed` (live tokens x
experts_per_tok, every layer) and `moe_picks_held` (those that fell on
an expert held here), by both programs, and `decode_moe_picks_routed`,
`decode_moe_picks_held` by the decode step alone.

Not built, because the published config has no key for it: the vision
tower; the `prefix_dense_*` layers (`first_k_dense_replace` is 0).
Refused by the engine from `features`: int8 weights or cache, adapters,
speculation, a mesh plan, chunked prefill; and, having two cache groups,
host swap and migration.
"""

from __future__ import annotations

from . import _decoder, _experts, _grouped

__all__ = ["CommandAConfig", "init_params", "forward_logits",
           "prefill_pages", "decode_step_pages", "COMMAND_A_SERVING_MODEL"]


class CommandAConfig(_grouped.GroupedConfig):
    """The published keys under this package's names (defaults are
    command-a-plus-05-2026's `config.json`, whole: every expert and the
    whole vocabulary held). Of what models/_experts.py reads it states
    the shared experts' MEAN; the scoring, the factor and the held range
    are that module's defaults unless `experts_held` is given."""

    shared_expert_combination = "average"

    def __init__(self, vocab_size=262144, hidden=4096, layers=32, heads=128,
                 kv_heads=8, head_dim=128, moe_intermediate=4096,
                 n_routed_experts=128, n_shared_experts=4, experts_per_tok=8,
                 experts_held=None, vocab_slice=None, layer_types=None,
                 sliding_window=4096, layer_norm_eps=1e-5, rope_theta=50000.0,
                 logit_scale=1.0, max_pos=200000, init_range=0.02,
                 name="command-a-plus-05-2026"):
        super().__init__(
            vocab_size=vocab_size, hidden=hidden, layers=layers, heads=heads,
            kv_heads=kv_heads, head_dim=head_dim,
            moe_intermediate=moe_intermediate,
            n_routed_experts=n_routed_experts,
            experts_per_tok=experts_per_tok, layer_types=layer_types,
            sliding_window=sliding_window, rms_eps=None,
            rope_theta=rope_theta, max_pos=max_pos, init_range=init_range,
            name=name)
        if experts_held is not None:
            first, count = experts_held
            if not (0 <= first and 0 < count
                    and first + count <= n_routed_experts):
                raise ValueError(f"experts_held {experts_held!r} are not "
                                 f"experts of {n_routed_experts}")
            experts_held = (int(first), int(count))
        if vocab_slice is None:
            vocab_slice = (0, vocab_size, vocab_size)
        if vocab_slice[1] != vocab_size or sum(vocab_slice[:2]) > vocab_slice[2]:
            raise ValueError(f"vocab_slice {vocab_slice!r} (first, rows, of) "
                             f"does not name {vocab_size} rows of a "
                             "vocabulary")
        self.n_shared_experts = n_shared_experts
        self.experts_held = experts_held
        self.vocab_slice = tuple(int(n) for n in vocab_slice)
        self.layer_norm_eps = layer_norm_eps
        self.logit_scale = logit_scale

    def serving_model(self):
        if self.name == COMMAND_A_SERVING_MODEL.name:
            return COMMAND_A_SERVING_MODEL
        return _CommandAServingModel(self.name)


def init_params(cfg: CommandAConfig, key, dtype):
    """`_grouped.init_params`' seeded weights: the held experts' matrices
    alone, ONE norm a layer, the head the embedding."""
    return _grouped.init_params(cfg, key, dtype, norms=("norm",), tied=True)


# -- the block's pieces ----------------------------------------------------------

def _layer_norm(x, g, eps):
    """Cohere's LayerNorm: the mean removed, over sqrt(var + eps), times
    a learned scale, no bias; statistics in float32, the result in x's
    type."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope("norm"):
        x32 = x.astype(jnp.float32)
        c = x32 - jnp.mean(x32, -1, keepdims=True)
        inv = jax.lax.rsqrt(jnp.mean(c * c, -1, keepdims=True) + eps)
        return (c * inv * g.astype(jnp.float32)).astype(x.dtype)


def _project(cfg, lp, u, pos, kind):
    """The projections of normed tokens u (T, h) at positions pos (T,):
    q (T, heads, d), k, v (T, kv_heads, d). A window layer rotates q and
    k on all d values, interleaved pairs (`rope` lays the pairs out in
    halves, q and k alike, which no score can tell); a full layer has NO
    positions."""
    T, d = u.shape[0], cfg.head_dim
    q = (u @ lp["wq"]).reshape(T, cfg.heads, d)
    k = (u @ lp["wk"]).reshape(T, cfg.kv_heads, d)
    v = (u @ lp["wv"]).reshape(T, cfg.kv_heads, d)
    if kind == "window":
        q = _decoder.rope(q, pos[:, None], cfg.rope_theta)
        k = _decoder.rope(k, pos[:, None], cfg.rope_theta)
    return q, k, v


def _head(cfg, params, x):
    import jax
    import jax.numpy as jnp
    y = _layer_norm(x, params["norm_f"], cfg.layer_norm_eps)
    with jax.named_scope("head"):
        logits = jnp.einsum("th,vh->tv", y, params["wte"],
                            preferred_element_type=jnp.float32)
        return logits if cfg.logit_scale == 1.0 else logits * cfg.logit_scale


# -- the whole sequence, no cache (tests; generation never runs it) --------------

def forward_logits(params, cfg, tokens):
    """Logits (T, V) float32 of one sequence tokens (T,): the served math
    without a cache."""
    import jax.numpy as jnp
    T = tokens.shape[0]
    pos = jnp.arange(T)
    x = _decoder.embed(params, tokens, _decoder.act_dtype(params))
    counters = _experts.zero_counters(cfg)
    live = jnp.ones((T,), bool)
    for li, lp in enumerate(params["layers"]):
        kind = cfg.kind(li)
        u = _layer_norm(x, lp["norm"], cfg.layer_norm_eps)
        q, k, v = _project(cfg, lp, u, pos, kind)
        o = _grouped.attend_rows(cfg, q, k, v, kind, False)
        y, counters = _experts.experts(cfg, lp, u, live, counters)
        x = x + o.reshape(T, -1) @ lp["wo"] + y
    return _head(cfg, params, x)


# -- prefill into the pages --------------------------------------------------------

def prefill_pages(params, cfg, tokens, pfx_len, real_len, arena, pages):
    """Prefill ONE sequence's COLD prompt tokens (1, B) into its page row
    `pages` (the full group's columns whole pages from 0, the window
    group's ring the pages it will hold; `pfx_len` is 0). Returns (logits
    (1, V) float32 of position real_len - 1, arena, counters)."""
    import jax
    import jax.numpy as jnp

    arenas = _grouped.arenas(arena)
    B = tokens.shape[1]
    bs = arenas["full"].shape[4]
    dtype = arenas["full"].dtype
    rows_of = _grouped.tables(cfg, pages, bs)
    flash = _grouped.prefill_attention_path(arena, B) == "flash"
    j = jnp.arange(B)
    pos = pfx_len + j
    live = j < real_len
    x = _decoder.embed(params, tokens[0], dtype)
    counters = _experts.zero_counters(cfg)
    for li, lp in enumerate(params["layers"]):
        kind, lg = cfg.kind(li), cfg.index_in_group(li)
        u = _layer_norm(x, lp["norm"], cfg.layer_norm_eps)
        with jax.named_scope("attn/project"):
            q, k, v = _project(cfg, lp, u, pos, kind)
            kv = jnp.concatenate([k, v], -1).astype(dtype)
            arenas[kind] = _grouped.write_prompt(
                arenas[kind], lg, rows_of[kind], pfx_len, real_len, kv, kind)
        with jax.named_scope("attn/" + kind):
            o = _grouped.attend_rows(cfg, q, k, v, kind, flash, real_len)
        with jax.named_scope("attn/project"):
            a = o.reshape(B, -1) @ lp["wo"]
        y, counters = _experts.experts(cfg, lp, u, live, counters)
        x = x + a + y
    last = x[real_len - 1][None]
    return _head(cfg, params, last), _grouped.arena_out(arenas), counters


# -- decode through the pages ------------------------------------------------------

def decode_step_pages(params, cfg, tokens, arena, pt, ts, done=None,
                      attention=None):
    """One decode step of every slot: tokens, ts (S,), pt (S, P + R); a
    full layer attends 0..ts, a window layer max(0, ts - window + 1)..ts.
    Returns (logits (S, V) float32, arena, counters)."""
    import jax
    import jax.numpy as jnp

    arenas = _grouped.arenas(arena)
    s_dim = pt.shape[0]
    bs = arenas["full"].shape[4]
    dtype = arenas["full"].dtype
    tables = _grouped.tables(cfg, pt, bs)
    if attention is None:
        attention = _grouped.decode_attention_path(arena)
    live = jnp.ones((s_dim,), bool) if done is None else ~done
    lo = {"full": jnp.zeros_like(ts),
          "window": jnp.maximum(ts - cfg.sliding_window + 1, 0)}
    x = _decoder.embed(params, tokens, dtype)
    counters = _experts.zero_counters(cfg)
    for li, lp in enumerate(params["layers"]):
        kind, lg = cfg.kind(li), cfg.index_in_group(li)
        u = _layer_norm(x, lp["norm"], cfg.layer_norm_eps)
        with jax.named_scope("attn/project"):
            q, k, v = _project(cfg, lp, u, ts, kind)
        with jax.named_scope("attn/" + kind):
            o, arenas[kind] = _grouped.attend_step(
                cfg, q, k, v, arenas[kind], lg, tables[kind], ts, done,
                lo[kind], kind, attention[kind])
        with jax.named_scope("attn/project"):
            a = o.reshape(s_dim, -1).astype(dtype) @ lp["wo"]
        y, counters = _experts.experts(cfg, lp, u, live, counters)
        x = x + a + y
    for kind, name in (("full", _grouped.FULL), ("window", _grouped.WINDOW)):
        counters["decode_rows_" + kind] = (
            jnp.sum(jnp.where(live, ts - lo[kind] + 1, 0)).astype(jnp.int32)
            * cfg.layer_types.count(name))
    return _head(cfg, params, x), _grouped.arena_out(arenas), counters


# -- the engine's view of this model ---------------------------------------------

class _CommandAServingModel(_grouped.GroupedBlockModel):
    prefill_pages = staticmethod(prefill_pages)
    decode_step_pages = staticmethod(decode_step_pages)

    # the picks: routed = live tokens x experts_per_tok a layer, held =
    # those whose expert is held here; `decode_*` the step's alone
    own_counters = ("moe_picks_routed", "moe_picks_held",
                    "decode_moe_picks_routed", "decode_moe_picks_held")

    def describe(self, cfg):
        first, count = _experts.held_experts(cfg)
        return {"experts_held": {"first": first, "count": count,
                                 "of": cfg.n_routed_experts},
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


COMMAND_A_SERVING_MODEL = _CommandAServingModel("command-a-plus-05-2026")
