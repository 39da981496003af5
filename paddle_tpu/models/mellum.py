"""Mellum2-12B-A2.5B-Instruct (`model_type: mellum`) on the serving path:
grouped-query attention whose layers are of TWO kinds, and softmax-routed
experts.

Pre-norm decoder, RMSNorm, no bias, untied embedding and head. Served
through serving.model.ServingModel by the same engine, scheduler, cache
manager and fused chunk loop as every other model. What is this model's:

  * 32 query heads over 4 KV heads; three "sliding_attention" layers
    (window 1024, plain rotary positions) to a "full_attention" one (YaRN
    frequencies with cos and sin times the attention factor:
    `rope_scaling`), q and k rotated on all d values, pairs in halves as
    published: TWO CACHE GROUPS, prefilled through the banded / full flash
    forward and decoded through the grouped paged kernel, by
    models/_grouped.py's pieces (there is no warm prefill and no chunked
    one);
  * every layer is sparse: the shared expert layer (models/_experts.py)
    under this config's scoring rule, `router_scoring = "softmax"` with the
    picks' weights over their sum, and no shared expert.

Shared, imported and not copied: the cache groups, the prefill and decode
attention, the weights' maker and the serving class from
models/_grouped.py; `ffn` (norm2, the expert layer, the counters) from
models/_experts.py; `rms`, `rope`, `head` from models/_decoder.py; the page
writers from serving/pages.py.

Parameters: `_grouped.init_params`' tree (norm1, norm2 a layer, an untied
head). Named scopes: `attn/project`, `attn/window`, `attn/full`,
`moe/router`, `moe/dispatch`, `moe/experts`, `moe/combine`, `head`.
In-graph counters beside the expert layer's: `decode_rows_full`,
`decode_rows_window`, the rows a decode step had to attend, summed over
live slots and that kind's layers.

Not built, because the published config has no key for it: a
multi-token-prediction head; a per-head q/k norm is not THIS model's
(models/sdar has one). Refused by the engine from `features`: int8 weights
or cache, adapters, speculation, a mesh plan, chunked prefill; and, having
two cache groups, host swap and migration.
"""

from __future__ import annotations

from . import _decoder, _experts, _grouped

__all__ = ["MellumConfig", "init_params", "forward_logits", "prefill_pages",
           "decode_step_pages", "MELLUM_SERVING_MODEL"]


class MellumConfig(_grouped.GroupedConfig):
    """The published keys under this package's names (defaults are
    Mellum2-12B-A2.5B-Instruct's `config.json`): three window layers to a
    full one, YaRN on the full layers' positions (cos and sin times
    mscale(factor, 1): the published `attention_factor`; a window
    layer's are plain), softmax routing."""

    n_shared_experts = 0
    router_scoring = "softmax"

    def __init__(self, vocab_size=98304, hidden=2304, layers=28, heads=32,
                 kv_heads=4, head_dim=128, moe_intermediate=896,
                 n_routed_experts=64, experts_per_tok=8, layer_types=None,
                 sliding_window=1024, rms_eps=1e-6, rope_theta=500000.0,
                 rope_scaling=None, max_pos=131072, init_range=0.02,
                 name="Mellum2-12B-A2.5B-Instruct"):
        super().__init__(
            vocab_size=vocab_size, hidden=hidden, layers=layers, heads=heads,
            kv_heads=kv_heads, head_dim=head_dim,
            moe_intermediate=moe_intermediate,
            n_routed_experts=n_routed_experts,
            experts_per_tok=experts_per_tok, layer_types=layer_types,
            sliding_window=sliding_window, rms_eps=rms_eps,
            rope_theta=rope_theta, rope_scaling=rope_scaling,
            max_pos=max_pos, init_range=init_range, name=name)

    def serving_model(self):
        if self.name == MELLUM_SERVING_MODEL.name:
            return MELLUM_SERVING_MODEL
        return _MellumServingModel(self.name)


init_params = _grouped.init_params    # norm1, norm2; an untied head


def _project(cfg, lp, x, pos, kind):
    """norm1 and the projections of tokens x (T, h) at positions pos (T,):
    q (T, heads, d), k, v (T, kv_heads, d), q and k rotated on all d
    values, pairs in halves as published; a full layer's frequencies are
    YaRN's."""
    T, d = x.shape[0], cfg.head_dim
    h = _decoder.rms(x, lp["norm1"], cfg.rms_eps)
    scaling = cfg.rope_scaling if kind == "full" else None
    q = (h @ lp["wq"]).reshape(T, cfg.heads, d)
    k = (h @ lp["wk"]).reshape(T, cfg.kv_heads, d)
    v = (h @ lp["wv"]).reshape(T, cfg.kv_heads, d)
    q = _decoder.rope(q, pos[:, None], cfg.rope_theta, scaling,
                      interleaved=False)
    k = _decoder.rope(k, pos[:, None], cfg.rope_theta, scaling,
                      interleaved=False)
    return q, k, v


# -- the whole sequence, no cache (tests; generation never runs it) --------------

def forward_logits(params, cfg, tokens):
    """Logits (T, V) float32 of one sequence tokens (T,): the served math
    without a cache."""
    import jax.numpy as jnp
    T = tokens.shape[0]
    pos = jnp.arange(T)
    x = params["wte"][tokens].astype(_decoder.act_dtype(params))
    counters = _experts.zero_counters(cfg)
    live = jnp.ones((T,), bool)
    for li, lp in enumerate(params["layers"]):
        kind = cfg.kind(li)
        q, k, v = _project(cfg, lp, x, pos, kind)
        o = _grouped.attend_rows(cfg, q, k, v, kind, False)
        x = x + o.reshape(T, -1) @ lp["wo"]
        y, counters, _ = _experts.ffn(cfg, lp, x, live, counters)
        x = x + y
    return _decoder.head(cfg, params, x)


# -- prefill into the pages --------------------------------------------------------

def prefill_pages(params, cfg, tokens, pfx_len, real_len, arena, pages):
    """Prefill ONE sequence's COLD prompt tokens (1, B) (right-padded to
    its bucket; real_len real) into its page row `pages`: the full
    group's columns whole pages from 0, the window group's ring the pages
    it will hold. `pfx_len` is 0 (a model with a window group is mapped
    without prefix hits: serving/kv_cache.py). Returns (logits (1, V)
    float32 of position real_len - 1, arena, counters)."""
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
    x = params["wte"][tokens[0]].astype(dtype)
    counters = _experts.zero_counters(cfg)
    for li, lp in enumerate(params["layers"]):
        kind, lg = cfg.kind(li), cfg.index_in_group(li)
        with jax.named_scope("attn/project"):
            q, k, v = _project(cfg, lp, x, pos, kind)
            kv = jnp.concatenate([k, v], -1).astype(dtype)
            arenas[kind] = _grouped.write_prompt(
                arenas[kind], lg, rows_of[kind], pfx_len, real_len, kv, kind)
        with jax.named_scope("attn/" + kind):
            o = _grouped.attend_rows(cfg, q, k, v, kind, flash, real_len)
        with jax.named_scope("attn/project"):
            x = x + o.reshape(B, -1) @ lp["wo"]
        y, counters, _ = _experts.ffn(cfg, lp, x, live, counters)
        x = x + y
    last = x[real_len - 1][None]
    return _decoder.head(cfg, params, last), _grouped.arena_out(arenas), \
        counters


# -- decode through the pages ------------------------------------------------------

def decode_step_pages(params, cfg, tokens, arena, pt, ts, done=None,
                      attention=None):
    """One decode step of every slot: tokens, ts (S,), pt (S, P + R).
    Writes each live slot's K|V row at position ts in both groups (a
    window layer's into ring entry (ts // block_size) % R) and attends:
    a full layer over 0..ts, a window layer over
    max(0, ts - window + 1)..ts. A frozen slot (`done`) writes to the
    scratch block (the gather) or nowhere (the kernel). Returns (logits
    (S, V) float32, arena, counters)."""
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
    x = params["wte"][tokens].astype(dtype)
    counters = _experts.zero_counters(cfg)
    rows_attended = {kind: jnp.sum(jnp.where(live, ts - lo[kind] + 1, 0))
                     .astype(jnp.int32) for kind in lo}
    for li, lp in enumerate(params["layers"]):
        kind, lg = cfg.kind(li), cfg.index_in_group(li)
        with jax.named_scope("attn/project"):
            q, k, v = _project(cfg, lp, x, ts, kind)
        with jax.named_scope("attn/" + kind):
            o, arenas[kind] = _grouped.attend_step(
                cfg, q, k, v, arenas[kind], lg, tables[kind], ts, done,
                lo[kind], kind, attention[kind])
        with jax.named_scope("attn/project"):
            x = x + o.reshape(s_dim, -1).astype(dtype) @ lp["wo"]
        y, counters, _ = _experts.ffn(cfg, lp, x, live, counters)
        x = x + y
    n_kind = {"full": cfg.layer_types.count(_grouped.FULL),
              "window": cfg.layer_types.count(_grouped.WINDOW)}
    counters = dict(counters, **{
        "decode_rows_" + kind: rows_attended[kind] * n_kind[kind]
        for kind in lo})
    return _decoder.head(cfg, params, x), _grouped.arena_out(arenas), counters


# -- the engine's view of this model ---------------------------------------------

class _MellumServingModel(_grouped.GroupedBlockModel):
    prefill_pages = staticmethod(prefill_pages)
    decode_step_pages = staticmethod(decode_step_pages)


MELLUM_SERVING_MODEL = _MellumServingModel("Mellum2-12B-A2.5B-Instruct")
