"""Mellum2-12B-A2.5B-Instruct (`model_type: mellum`) on the serving path:
grouped-query attention whose layers are of TWO kinds, and softmax-routed
experts.

Pre-norm decoder, RMSNorm, no bias, untied embedding and head. Served
through serving.model.ServingModel by the same engine, scheduler, cache
manager and fused chunk loop as the GPT family and the latent block:

  * `heads` query heads share `kv_heads` KV heads (32 over 4: query head i
    reads KV head i // 8); the cache row of a token in a layer is a KV
    head's K and V side by side, `kv_heads` of `2 * head_dim`;
  * `layer_types[l]` is "sliding_attention" (position i attends j with
    i - sliding_window < j <= i, plain rotary positions) or
    "full_attention" (causal over everything, YaRN frequencies with cos
    and sin times the attention factor: `rope_scaling`). The two kinds are
    two CACHE GROUPS (`cache_spec`: "full", the primary, and "window"): a
    full layer keeps every row, a window layer a ring of
    ceil(window / block_size) + 1 blocks a slot, position p in ring entry
    (p // block_size) % ring (serving/model.py). A prefill writes the
    window group only the rows its ring will hold;
  * PREFILL attends a cold prompt over its own rows: the flash forward
    with shared KV heads and, in a window layer, a band
    (ops/flash_attention.flash_causal_rows) on a TPU for buckets of whole
    128-row tiles, masked XLA attention elsewhere. There is no warm
    prefill: a model with a window group takes no prefix hits and chunked
    prefill is not implemented;
  * DECODE walks each group's pages with ONE kernel
    (ops/paged_attention.paged_attention: the group's 8 queries are the
    rows of two matrix products a KV head, a window layer's walk starts at
    the page that holds ts - window + 1); a gather and two einsums where
    the kernel does not apply (the CPU);
  * every layer is sparse: `models/moonlight`'s expert layer (`_moe`:
    dispatch, `grouped_experts`, combine, counters) under this config's
    scoring rule, `router_scoring = "softmax"` with the picks' weights over
    their sum, and no shared expert (`n_shared_experts = 0`).

Shared with models/moonlight, not copied: `_rms`, `rope` /
`rope_frequencies` (YaRN), `_swiglu`'s experts through `_ffn` / `_moe` /
`route` / `grouped_experts`, `_head`, `_masked_attention`, the counters.

Parameters (`x @ W`, W is (in, out)): wte (V, h), head (h, V), norm_f
(h,), layers[i]: norm1, norm2 (h,); wq (h, heads*d), wk, wv (h,
kv_heads*d), wo (heads*d, h); router (h, E), w_gate, w_up (E, h, F),
w_down (E, F, h).

Named scopes: `attn/project`, `attn/window`, `attn/full`, `moe/router`,
`moe/dispatch`, `moe/experts`, `moe/combine`, `head`. In-graph counters
beside the expert layer's: `decode_rows_full`, `decode_rows_window`, the
rows a decode step had to attend, summed over live slots and that kind's
layers.

Not built, because the published config has no key for it: a
multi-token-prediction head; a per-head q/k norm is not THIS model's
(models/sdar, which shares this config, these weights and the cache group
and brings its own projections, has one). Refused by the engine from
`features`: int8 weights or cache, adapters, speculation, a mesh plan,
chunked prefill; and, having two cache groups, host swap and migration.
"""

from __future__ import annotations

import math

from ..serving.model import CacheSpec, ServingModel, group_columns
from .gpt_decode import _gather_pages, _write_pages
from .moonlight import (_MoonlightServingModel, _act_dtype, _ffn, _head,
                        _masked_attention, _rms, _zero_counters, rope)

__all__ = ["MellumConfig", "init_params", "forward_logits", "prefill_pages",
           "decode_step_pages", "decode_attention_path",
           "prefill_attention_path", "MELLUM_SERVING_MODEL"]

_LANES = 128
FULL, WINDOW = "full_attention", "sliding_attention"


class MellumConfig:
    """The published keys under this package's names (defaults are
    Mellum2-12B-A2.5B-Instruct's `config.json`)."""

    # what models/moonlight's shared pieces read of a config
    n_shared_experts = 0
    router_scoring = "softmax"
    hc_mult = 1

    def __init__(self, vocab_size=98304, hidden=2304, layers=28, heads=32,
                 kv_heads=4, head_dim=128, moe_intermediate=896,
                 n_routed_experts=64, experts_per_tok=8, layer_types=None,
                 sliding_window=1024, rms_eps=1e-6, rope_theta=500000.0,
                 rope_scaling=None, max_pos=131072, init_range=0.02,
                 name="Mellum2-12B-A2.5B-Instruct"):
        if heads % kv_heads:
            raise ValueError(f"{heads} query heads do not share {kv_heads} "
                             "KV heads evenly")
        if layer_types is None:
            layer_types = [FULL if i % 4 == 3 else WINDOW
                           for i in range(layers)]
        layer_types = tuple(layer_types)
        if len(layer_types) != layers or set(layer_types) - {FULL, WINDOW}:
            raise ValueError(f"layer_types names {layers} layers, each "
                             f"{FULL!r} or {WINDOW!r}, not {layer_types!r}")
        if FULL not in layer_types:
            raise ValueError("the primary cache group is the full layers': "
                             "a model of window layers alone is not written")
        if rope_scaling is not None and rope_scaling.get("type") != "yarn":
            raise ValueError("rope_scaling (the full layers') is None or a "
                             f"YaRN dict, not {rope_scaling!r}")
        self.vocab_size = vocab_size
        self.hidden = hidden
        self.layers = layers
        self.heads = heads
        self.kv_heads = kv_heads
        self.head_dim = head_dim
        self.moe_intermediate = moe_intermediate
        self.n_routed_experts = n_routed_experts
        self.experts_per_tok = experts_per_tok
        self.layer_types = layer_types
        self.sliding_window = sliding_window
        self.rms_eps = rms_eps
        self.rope_theta = rope_theta
        # the FULL layers' positions: None or YaRN's dict as
        # models/moonlight.rope_frequencies reads it (cos and sin times
        # mscale(factor, 1): the published `attention_factor`); a window
        # layer's are plain
        self.rope_scaling = rope_scaling
        self.max_pos = max_pos
        self.init_range = init_range
        self.name = name

    @property
    def group(self):
        """Query heads a KV head."""
        return self.heads // self.kv_heads

    def kind(self, layer):
        return "full" if self.layer_types[layer] == FULL else "window"

    def index_in_group(self, layer):
        """The layer's plane in its cache group's arena."""
        return sum(t == self.layer_types[layer]
                   for t in self.layer_types[:layer])

    def serving_model(self):
        if self.name == MELLUM_SERVING_MODEL.name:
            return MELLUM_SERVING_MODEL
        return _MellumServingModel(self.name)


def init_params(cfg: MellumConfig, key, dtype):
    """Seeded random weights on the default device: normal(0, init_range)
    matrices (the router's too), unit norms. One jitted maker called once
    a layer, as models/moonlight.init_params."""
    import jax
    import jax.numpy as jnp

    h, d = cfg.hidden, cfg.head_dim
    E, F = cfg.n_routed_experts, cfg.moe_intermediate
    std = cfg.init_range
    shapes = {"wq": (h, cfg.heads * d), "wk": (h, cfg.kv_heads * d),
              "wv": (h, cfg.kv_heads * d), "wo": (cfg.heads * d, h),
              "router": (h, E), "w_gate": (E, h, F), "w_up": (E, h, F),
              "w_down": (E, F, h)}

    def normal(k, shape):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    def layer(k):
        ks = jax.random.split(k, len(shapes))
        lp = {name: normal(kk, shape)
              for (name, shape), kk in zip(shapes.items(), ks)}
        lp.update(norm1=jnp.ones((h,), dtype), norm2=jnp.ones((h,), dtype))
        return lp

    def top(k):
        k1, k2 = jax.random.split(k)
        return {"wte": normal(k1, (cfg.vocab_size, h)),
                "head": normal(k2, (h, cfg.vocab_size)),
                "norm_f": jnp.ones((h,), dtype)}

    make = jax.jit(layer)
    keys = jax.random.split(key, cfg.layers + 1)
    params = jax.jit(top)(keys[-1])
    params["layers"] = [make(keys[i]) for i in range(cfg.layers)]
    return params


# -- the attention's pieces ------------------------------------------------------

def _project(cfg, lp, x, pos, kind):
    """norm1 and the projections of tokens x (T, h) at positions pos (T,):
    q (T, heads, d), k, v (T, kv_heads, d), q and k rotated on all d
    values, pairs in halves as published; a full layer's frequencies are
    YaRN's."""
    T, d = x.shape[0], cfg.head_dim
    h = _rms(x, lp["norm1"], cfg.rms_eps)
    scaling = cfg.rope_scaling if kind == "full" else None
    q = (h @ lp["wq"]).reshape(T, cfg.heads, d)
    k = (h @ lp["wk"]).reshape(T, cfg.kv_heads, d)
    v = (h @ lp["wv"]).reshape(T, cfg.kv_heads, d)
    q = rope(q, pos[:, None], cfg.rope_theta, scaling, interleaved=False)
    k = rope(k, pos[:, None], cfg.rope_theta, scaling, interleaved=False)
    return q, k, v


def _attend_rows(cfg, q, k, v, kind, flash, real_len=None):
    """Causal attention of one sequence over its own rows (a window layer:
    the last `sliding_window` of them), q (T, heads, d), k, v (T, kv_heads,
    d) -> (T, heads, d). `real_len`: the rows that are not padding, which
    the flash forward neither visits nor returns (zeros)."""
    import jax.numpy as jnp
    scale = 1.0 / math.sqrt(cfg.head_dim)
    window = cfg.sliding_window if kind == "window" else None
    if flash:
        from ..ops.flash_attention import flash_causal_rows
        return flash_causal_rows(q, k, v, scale, window=window,
                                 length=real_len)
    i = jnp.arange(q.shape[0])
    mask = i[None, :] <= i[:, None]
    if window is not None:
        mask = mask & (i[:, None] - i[None, :] < window)
    return _masked_attention(q, jnp.repeat(k, cfg.group, 1),
                             jnp.repeat(v, cfg.group, 1), mask, scale)


def _specs(cfg):
    """The cache groups: the full layers' (the primary) and, where the
    config has window layers, theirs."""
    n_full = cfg.layer_types.count(FULL)
    full = CacheSpec(n_full, cfg.kv_heads, 2 * cfg.head_dim, None, "full")
    if n_full == cfg.layers:
        return (full,)
    return (full, CacheSpec(cfg.layers - n_full, cfg.kv_heads,
                            2 * cfg.head_dim, cfg.sliding_window, "window"))


def _tables(cfg, table, block_size):
    """{kind: its columns of a page row (P + R,) or a page table (S,
    P + R)}, as serving.model.cache_groups laid them out."""
    specs = _specs(cfg)
    return {spec.name: table[..., cols] for spec, cols in zip(
        specs, group_columns(specs, table.shape[-1], block_size))}


def _arenas(cfg, arena):
    """{kind: its arena} from what the engine threads: the tuple of the
    groups' arenas, or the one arena of a config without window layers."""
    if isinstance(arena, tuple):
        return {"full": arena[0], "window": arena[1]}
    return {"full": arena}


def _arena_out(arenas):
    return (arenas["full"], arenas["window"]) if "window" in arenas \
        else arenas["full"]


# -- the whole sequence, no cache (tests; generation never runs it) --------------

def forward_logits(params, cfg, tokens):
    """Logits (T, V) float32 of one sequence tokens (T,): the served math
    without a cache."""
    import jax.numpy as jnp
    T = tokens.shape[0]
    pos = jnp.arange(T)
    x = params["wte"][tokens].astype(_act_dtype(params))
    counters = _zero_counters(cfg)
    live = jnp.ones((T,), bool)
    for li, lp in enumerate(params["layers"]):
        kind = cfg.kind(li)
        q, k, v = _project(cfg, lp, x, pos, kind)
        o = _attend_rows(cfg, q, k, v, kind, flash=False)
        x = x + o.reshape(T, -1) @ lp["wo"]
        y, counters, _ = _ffn(cfg, lp, x, live, counters)
        x = x + y
    return _head(cfg, params, x)


# -- prefill into the pages --------------------------------------------------------

def _write_ring(leaf, li, ring, real_len, rows):
    """Put rows (B, heads, w), the positions 0 .. real_len - 1 of ONE
    sequence, into a window group's `leaf` as whole pages, and only the
    pages the ring will hold: the last `len(ring)` that hold a real row,
    page t into block ring[t % len(ring)]; a page with no real row goes to
    scratch block 0."""
    import jax
    import jax.numpy as jnp
    bs, w = leaf.shape[4], leaf.shape[5]
    B, heads = rows.shape[0], rows.shape[1]
    R = ring.shape[0]
    n_t = -(-B // bs)
    if n_t * bs != B:
        rows = jnp.pad(rows, ((0, n_t * bs - B), (0, 0), (0, 0)))
    tiles = rows.reshape(n_t, bs, heads, w).transpose(0, 2, 1, 3)
    n_w = min(n_t, R)
    t0 = jnp.clip((real_len - 1) // bs - n_w + 1, 0, n_t - n_w)
    tiles = jax.lax.dynamic_slice_in_dim(tiles, t0, n_w, 0)
    t = t0 + jnp.arange(n_w)
    ids = jnp.where(t * bs < real_len, ring[t % R], 0)
    return leaf.at[li, 0, ids].set(tiles)


def prefill_attention_path(arena, bucket, arena_constraint=None):
    """ "flash" on a TPU for a bucket of whole 128-row tiles over bare
    arenas with lane-aligned rows (both kinds of layer: the band is the
    same kernel); "gather" elsewhere (the CPU)."""
    import jax
    first = arena[0] if isinstance(arena, tuple) else arena
    if (arena_constraint is None and bucket % _LANES == 0
            and first.shape[-1] % _LANES == 0
            and jax.default_backend() == "tpu"):
        return "flash"
    return "gather"


def prefill_pages(params, cfg, tokens, pfx_len, real_len, arena, pages):
    """Prefill ONE sequence's COLD prompt tokens (1, B) (right-padded to
    its bucket; real_len real) into its page row `pages`: the full
    group's columns whole pages from 0, the window group's ring the pages
    it will hold. `pfx_len` is 0 (a model with a window group is mapped
    without prefix hits: serving/kv_cache.py). Returns (logits (1, V)
    float32 of position real_len - 1, arena, counters)."""
    import jax
    import jax.numpy as jnp

    arenas = _arenas(cfg, arena)
    B = tokens.shape[1]
    bs = arenas["full"].shape[4]
    dtype = arenas["full"].dtype
    rows_of = _tables(cfg, pages, bs)
    flash = prefill_attention_path(arena, B) == "flash"
    j = jnp.arange(B)
    pos = pfx_len + j
    live = j < real_len
    x = params["wte"][tokens[0]].astype(dtype)
    counters = _zero_counters(cfg)
    for li, lp in enumerate(params["layers"]):
        kind, lg = cfg.kind(li), cfg.index_in_group(li)
        with jax.named_scope("attn/project"):
            q, k, v = _project(cfg, lp, x, pos, kind)
            kv = jnp.concatenate([k, v], -1).astype(dtype)
            if kind == "full":
                arenas[kind] = _write_pages(arenas[kind], lg, rows_of[kind],
                                            pfx_len, real_len, kv)
            else:
                arenas[kind] = _write_ring(arenas[kind], lg, rows_of[kind],
                                           real_len, kv)
        with jax.named_scope("attn/" + kind):
            o = _attend_rows(cfg, q, k, v, kind, flash, real_len)
        with jax.named_scope("attn/project"):
            x = x + o.reshape(B, -1) @ lp["wo"]
        y, counters, _ = _ffn(cfg, lp, x, live, counters)
        x = x + y
    last = x[real_len - 1][None]
    return _head(cfg, params, last), _arena_out(arenas), counters


# -- decode through the pages ------------------------------------------------------

def decode_attention_path(arena, arena_constraint=None):
    """{cache group: "paged_kernel" on a TPU over a bare arena with a
    lane-aligned K|V row, "gather" elsewhere (the CPU)}."""
    import jax
    arenas = arena if isinstance(arena, tuple) else (arena,)
    names = ("full", "window")
    return {name: "paged_kernel"
            if (arena_constraint is None and a.shape[-1] % _LANES == 0
                and jax.default_backend() == "tpu") else "gather"
            for name, a in zip(names, arenas)}


def _gather_attend(cfg, q, rows, keep):
    """The gather form of a decode step's attention: q (S, heads, d), rows
    (S, kv_heads, L, 2d) each slot's gathered K|V rows, keep (S, L) which
    of them the slot attends. Returns (S, heads, d)."""
    import jax.numpy as jnp
    S, d = q.shape[0], cfg.head_dim
    qg = q.reshape(S, cfg.kv_heads, cfg.group, d)
    s = jnp.einsum("skgd,skld->skgl", qg, rows[..., :d],
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    s = jnp.where(keep[:, None, None, :], s, -1e30)
    p = jnp.exp(s - jnp.max(s, -1, keepdims=True))
    p = (p / p.sum(-1, keepdims=True)).astype(rows.dtype)
    return jnp.einsum("skgl,skld->skgd", p, rows[..., d:]).reshape(S, -1, d)


def _attend_step(cfg, q, k, v, arena, lg, table, ts, done, lo, kind, path):
    """One layer's attention of a decode step, with its write: q (S,
    heads, d), k, v (S, kv_heads, d) of position ts (S,), `arena` the
    layer's group's, `lg` its plane there, `table` (S, pages) the
    group's columns of the page table (a window group's a ring), `lo`
    (S,) the first position attended. `path`: "paged_kernel" or
    "gather". Returns (o (S, heads, d), the arena)."""
    import jax.numpy as jnp
    s_dim, pages = table.shape
    bs, dtype = arena.shape[4], arena.dtype
    if path == "paged_kernel":
        from ..ops.paged_attention import paged_attention
        return paged_attention(q, k, v, arena, lg, table, ts, done,
                               lo=None if kind == "full" else lo)
    page = ts // bs
    wblk = table[jnp.arange(s_dim), page % pages]
    if done is not None:
        wblk = jnp.where(done, 0, wblk)
    a = arena.at[lg, 0, wblk, :, ts % bs].set(
        jnp.concatenate([k, v], -1).astype(dtype))
    rows = _gather_pages(a, lg, table)             # (S, kv, pages*bs, 2d)
    # entry c of the table holds the one page t in (page - pages, page]
    # with t % pages == c (a full row: c itself)
    c = jnp.arange(pages)[None, :]
    t = page[:, None] - (page[:, None] - c) % pages
    at = (t[:, :, None] * bs + jnp.arange(bs)).reshape(s_dim, -1)
    keep = (at >= lo[:, None]) & (at <= ts[:, None])
    return _gather_attend(cfg, q, rows, keep), a


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

    arenas = _arenas(cfg, arena)
    s_dim = pt.shape[0]
    bs = arenas["full"].shape[4]
    dtype = arenas["full"].dtype
    tables = _tables(cfg, pt, bs)
    if attention is None:
        attention = decode_attention_path(arena)
    live = jnp.ones((s_dim,), bool) if done is None else ~done
    lo = {"full": jnp.zeros_like(ts),
          "window": jnp.maximum(ts - cfg.sliding_window + 1, 0)}
    x = params["wte"][tokens].astype(dtype)
    counters = _zero_counters(cfg)
    rows_attended = {kind: jnp.sum(jnp.where(live, ts - lo[kind] + 1, 0))
                     .astype(jnp.int32) for kind in lo}
    for li, lp in enumerate(params["layers"]):
        kind, lg = cfg.kind(li), cfg.index_in_group(li)
        with jax.named_scope("attn/project"):
            q, k, v = _project(cfg, lp, x, ts, kind)
        with jax.named_scope("attn/" + kind):
            o, arenas[kind] = _attend_step(
                cfg, q, k, v, arenas[kind], lg, tables[kind], ts, done,
                lo[kind], kind, attention[kind])
        with jax.named_scope("attn/project"):
            x = x + o.reshape(s_dim, -1).astype(dtype) @ lp["wo"]
        y, counters, _ = _ffn(cfg, lp, x, live, counters)
        x = x + y
    n_kind = {"full": cfg.layer_types.count(FULL),
              "window": cfg.layer_types.count(WINDOW)}
    counters = dict(counters, **{
        "decode_rows_" + kind: rows_attended[kind] * n_kind[kind]
        for kind in lo})
    return _head(cfg, params, x), _arena_out(arenas), counters


# -- the engine's view of this model ---------------------------------------------

class _MellumServingModel(ServingModel):
    features = frozenset()

    def __init__(self, name):
        self.name = name

    def max_positions(self, cfg):
        return cfg.max_pos

    def cache_spec(self, cfg):
        specs = _specs(cfg)
        return specs if len(specs) > 1 else specs[0]

    def activation_dtype(self, params):
        return _act_dtype(params)

    def decode_attention_path(self, arena, arena_constraint=None):
        return decode_attention_path(arena, arena_constraint)

    def prefill_attention_path(self, arena, bucket, arena_constraint=None):
        return prefill_attention_path(arena, bucket, arena_constraint)

    def counter_names(self, cfg):
        # the expert layer's as the latent block's (moonlight.py), and the
        # rows a decode step had to attend, by kind of layer, summed over
        # live slots and that kind's layers
        return {"expert_tokens": (cfg.n_routed_experts,),
                "router_tokens": (), "decode_router_tokens": (),
                "decode_experts_touched": (), "decode_moe_passes": (),
                "moe_kernel_passes": (), "moe_rows_computed": (),
                "moe_combine_kernel_passes": (), "decode_rows_full": (),
                "decode_rows_window": ()}

    @staticmethod
    def _counters(c, decode):
        import jax.numpy as jnp
        zero = jnp.zeros((), jnp.int32)
        out = _MoonlightServingModel._counters(c, decode)
        out.update({name: c.get(name, zero)
                    for name in ("decode_rows_full", "decode_rows_window")})
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


MELLUM_SERVING_MODEL = _MellumServingModel("Mellum2-12B-A2.5B-Instruct")
