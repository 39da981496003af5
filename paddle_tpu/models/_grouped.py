"""Grouped-query attention over CACHE GROUPS: what Mellum, command-a,
SDAR, granite-4.0-h-small and Qwen3-Next share (models/mellum.py,
command_a.py, sdar.py, granite_hybrid.py, qwen3_next.py; the cells
mellum-mixedlen-offline, command-a-reason-offline, sdar-blockgen-offline,
granite-h-shortchat-offline, qwen3-next-longmix-offline).

  * `heads` query heads share `kv_heads` KV heads (query head i reads KV
    head i // group); the cache row of a token in a layer is a KV head's K
    and V side by side, `kv_heads` of `2 * head_dim`;
  * `layer_types[l]` is "sliding_attention" (position i attends j with
    i - sliding_window < j <= i) or "full_attention" (causal over
    everything): two CACHE GROUPS (`specs`: "full", the primary, and
    "window", a ring of ceil(window / block_size) + 1 blocks a slot:
    serving/model.py). A config of full layers alone has the one group;
  * PREFILL attends a cold prompt over its own rows (`attend_rows`: the
    flash forward with shared KV heads and the layer's mask rule on a TPU
    for buckets of whole 128-row tiles; masked XLA attention elsewhere).
    There is no warm prefill: a window group takes no prefix hits;
  * DECODE walks each group's pages with ONE kernel (`attend_step`:
    ops/paged_attention.paged_attention, a window layer's walk from the
    page that holds ts - window + 1); a gather and two einsums where the
    kernel does not apply (the CPU).

What a block brings itself: its norms, its projections and positions
(`_project`, written once a leaf: they differ in norm, in rotation (none,
whole, YaRN, the first quarter of a head) and in what else the query
projection carries (Qwen3-Next's output gate)),
its residual (sequential or parallel), its programs.

Imports no model and, at module level, no jax.
"""

from __future__ import annotations

import math

from ..serving import pages as _pages
from ..serving.model import CacheSpec, group_columns
from . import _decoder, _experts

__all__ = ["FULL", "WINDOW", "GroupedConfig", "init_params", "attend_rows",
           "specs", "tables", "arenas", "arena_out", "write_prompt",
           "attend_step", "prefill_attention_path", "decode_attention_path",
           "GroupedBlockModel"]

FULL, WINDOW = "full_attention", "sliding_attention"


class GroupedConfig:
    """What this module reads of a config, checked: the widths, the kinds
    of layer (`layer_types` None: three window layers to a full one, as
    Mellum and command-a publish theirs) and the window. A model's config
    subclasses it with the published defaults and its own fields."""

    def __init__(self, *, vocab_size, hidden, layers, heads, kv_heads,
                 head_dim, moe_intermediate, n_routed_experts,
                 experts_per_tok, rms_eps, rope_theta, max_pos, init_range,
                 name, layer_types=None, sliding_window=None,
                 rope_scaling=None, attention_scale=None):
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
        # _decoder.rope_frequencies reads it
        self.rope_scaling = rope_scaling
        # the softmax scale where a model publishes one (None:
        # head_dim^-0.5)
        self.attention_scale = attention_scale
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


def init_params(cfg, key, dtype, norms=("norm1", "norm2"), tied=False):
    """Seeded random weights on the default device: normal(0, init_range)
    matrices (the router's too), unit norms. layers[i]: `norms` (h,) each;
    wq (h, heads*d), wk, wv (h, kv_heads*d), wo (heads*d, h); router (h, E),
    as wide as the model has experts; w_gate, w_up (held, h, F), w_down
    (held, F, h), the `_experts.held_experts(cfg)` alone; with shared
    experts shared_gate, shared_up (h, n*F), shared_down (n*F, h). On top
    wte (V, h), norm_f (h,) and, unless the head is `tied` to the
    embedding, head (h, V). One jitted maker called once a layer: never
    more than one layer's generator bits alive beside the weights."""
    import jax
    import jax.numpy as jnp

    h, d, F = cfg.hidden, cfg.head_dim, cfg.moe_intermediate
    held, Fs = _experts.held_experts(cfg)[1], cfg.n_shared_experts * F
    std = cfg.init_range
    shapes = {"wq": (h, cfg.heads * d), "wk": (h, cfg.kv_heads * d),
              "wv": (h, cfg.kv_heads * d), "wo": (cfg.heads * d, h),
              "router": (h, cfg.n_routed_experts), "w_gate": (held, h, F),
              "w_up": (held, h, F), "w_down": (held, F, h)}
    if Fs:
        shapes.update(shared_gate=(h, Fs), shared_up=(h, Fs),
                      shared_down=(Fs, h))

    def normal(k, shape):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    def layer(k):
        ks = jax.random.split(k, len(shapes))
        lp = {name: normal(kk, shape)
              for (name, shape), kk in zip(shapes.items(), ks)}
        lp.update({name: jnp.ones((h,), dtype) for name in norms})
        return lp

    def top(k):
        k1, k2 = (k, None) if tied else jax.random.split(k)
        out = {"wte": normal(k1, (cfg.vocab_size, h)),
               "norm_f": jnp.ones((h,), dtype)}
        return out if tied else dict(out, head=normal(k2, (h, cfg.vocab_size)))

    make = jax.jit(layer)
    keys = jax.random.split(key, cfg.layers + 1)
    params = jax.jit(top)(keys[-1])
    params["layers"] = [make(keys[i]) for i in range(cfg.layers)]
    return params


# -- a prompt over its own rows ---------------------------------------------------

def attend_rows(cfg, q, k, v, kind, flash, real_len=None, block=None):
    """Attention of one sequence over its own rows, q (T, heads, d), k, v
    (T, kv_heads, d) -> (T, heads, d), under the layer's mask rule:
    causal; in a layer of `kind` "window" the last `sliding_window` rows
    alone; with `block` (a power of two) BLOCK-causal, row i attending
    j <= i | (block - 1). `real_len`: the rows that are not padding, which
    the flash forward neither visits nor returns (zeros)."""
    import jax.numpy as jnp
    scale = 1.0 / math.sqrt(cfg.head_dim) if cfg.attention_scale is None \
        else cfg.attention_scale
    window = cfg.sliding_window if kind == "window" else None
    if flash:
        from ..ops.flash_attention import flash_causal_rows
        return flash_causal_rows(q, k, v, scale, window=window,
                                 length=real_len, block=block)
    i = jnp.arange(q.shape[0])
    if block is None:
        mask = i[None, :] <= i[:, None]
    else:
        mask = i[None, :] <= (i[:, None] | (block - 1))
    if window is not None:
        mask = mask & (i[:, None] - i[None, :] < window)
    return _decoder.masked_attention(q, jnp.repeat(k, cfg.group, 1),
                                     jnp.repeat(v, cfg.group, 1), mask, scale)


# -- the cache groups ---------------------------------------------------------------

def specs(cfg):
    """The cache groups: the full layers' (the primary) and, where the
    config has window layers, theirs."""
    n_full = cfg.layer_types.count(FULL)
    full = CacheSpec(n_full, cfg.kv_heads, 2 * cfg.head_dim, None, "full")
    if n_full == cfg.layers:
        return (full,)
    return (full, CacheSpec(cfg.layers - n_full, cfg.kv_heads,
                            2 * cfg.head_dim, cfg.sliding_window, "window"))


def tables(cfg, table, block_size):
    """{kind: its columns of a page row (P + R,) or a page table (S,
    P + R)}, as serving.model.cache_groups laid them out."""
    groups = specs(cfg)
    return {spec.name: table[..., cols] for spec, cols in zip(
        groups, group_columns(groups, table.shape[-1], block_size))}


def arenas(arena):
    """{kind: its arena} from what the engine threads: the tuple of the
    groups' arenas, or the one arena of a config without window layers."""
    if isinstance(arena, tuple):
        return {"full": arena[0], "window": arena[1]}
    return {"full": arena}


def arena_out(by_kind):
    return (by_kind["full"], by_kind["window"]) if "window" in by_kind \
        else by_kind["full"]


def write_prompt(arena, lg, table, pfx_len, real_len, rows, kind):
    """A prompt's rows (B, kv_heads, 2d) into plane `lg` of its group's
    arena: whole pages from `pfx_len` on in the full group, the pages the
    ring will hold in the window group."""
    if kind == "full":
        return _pages.write_pages(arena, lg, table, pfx_len, real_len, rows)
    return _pages.write_ring(arena, lg, table, real_len, rows)


def prefill_attention_path(arena, bucket, arena_constraint=None):
    """ "flash" on a TPU for a bucket of whole 128-row tiles over bare
    arenas with lane-aligned rows (every kind of layer: the band and the
    block-causal mask are the same kernel); "gather" elsewhere (the
    CPU)."""
    return "flash" if _pages.kernel_beside(
        arenas(arena)["full"], arena_constraint, bucket) else "gather"


def decode_attention_path(arena, arena_constraint=None):
    """{cache group: "paged_kernel" on a TPU over a bare arena with a
    lane-aligned K|V row, "gather" elsewhere (the CPU)}."""
    return {kind: "paged_kernel" if _pages.kernel_beside(a, arena_constraint)
            else "gather" for kind, a in arenas(arena).items()}


# -- a decode step through the pages ----------------------------------------------

def _gather_attend(cfg, q, rows, keep):
    """The gather form of a decode step's attention: q (S, heads, d), rows
    (S, kv_heads, L, 2d) each slot's gathered K|V rows, keep (S, L) which
    of them the slot attends. Returns (S, heads, d)."""
    import jax.numpy as jnp
    S, d = q.shape[0], cfg.head_dim
    qg = q.reshape(S, cfg.kv_heads, cfg.group, d)
    s = jnp.einsum("skgd,skld->skgl", qg, rows[..., :d],
                   preferred_element_type=jnp.float32)
    s = s / math.sqrt(d) if cfg.attention_scale is None \
        else s * cfg.attention_scale
    s = jnp.where(keep[:, None, None, :], s, -1e30)
    p = jnp.exp(s - jnp.max(s, -1, keepdims=True))
    p = (p / p.sum(-1, keepdims=True)).astype(rows.dtype)
    return jnp.einsum("skgl,skld->skgd", p, rows[..., d:]).reshape(S, -1, d)


def attend_step(cfg, q, k, v, arena, lg, table, ts, done, lo, kind, path):
    """One layer's attention of a decode step, with its write: q (S,
    heads, d), k, v (S, kv_heads, d) of position ts (S,), `arena` the
    layer's group's, `lg` its plane there, `table` (S, pages) the
    group's columns of the page table (a window group's a ring), `lo`
    (S,) the first position attended. `path`: "paged_kernel" or
    "gather". Returns (o (S, heads, d), the arena)."""
    import jax.numpy as jnp
    s_dim, n_pages = table.shape
    bs, dtype = arena.shape[4], arena.dtype
    if path == "paged_kernel":
        from ..ops.paged_attention import paged_attention
        if cfg.attention_scale is not None:
            # the kernel scales by head_dim^-0.5: the rest of a published
            # scale rides on q
            q = (q.astype(jnp.float32) * (cfg.attention_scale * math.sqrt(
                cfg.head_dim))).astype(q.dtype)
        return paged_attention(q, k, v, arena, lg, table, ts, done,
                               lo=None if kind == "full" else lo)
    page = ts // bs
    wblk = table[jnp.arange(s_dim), page % n_pages]
    if done is not None:
        wblk = jnp.where(done, 0, wblk)
    a = arena.at[lg, 0, wblk, :, ts % bs].set(
        jnp.concatenate([k, v], -1).astype(dtype))
    rows = _pages.gather_pages(a, lg, table)        # (S, kv, pages*bs, 2d)
    # entry c of the table holds the one page t in (page - pages, page]
    # with t % pages == c (a full row: c itself)
    c = jnp.arange(n_pages)[None, :]
    t = page[:, None] - (page[:, None] - c) % n_pages
    at = (t[:, :, None] * bs + jnp.arange(bs)).reshape(s_dim, -1)
    keep = (at >= lo[:, None]) & (at <= ts[:, None])
    return _gather_attend(cfg, q, rows, keep), a


# -- the engine's view of such a block ---------------------------------------------

class GroupedBlockModel(_experts.ExpertBlockModel):
    """The serving class of a block whose cache is these groups: their
    specs, the two verdicts by group, and beside the expert layer's
    counters the rows a decode step had to attend, by cache group, summed
    over live slots and that group's layers (`decode_rows_<group>`)."""

    def cache_spec(self, cfg):
        groups = specs(cfg)
        return groups if len(groups) > 1 else groups[0]

    def decode_attention_path(self, arena, arena_constraint=None):
        return decode_attention_path(arena, arena_constraint)

    def prefill_attention_path(self, arena, bucket, arena_constraint=None):
        return prefill_attention_path(arena, bucket, arena_constraint)

    def counter_names(self, cfg):
        return dict(super().counter_names(cfg), **{
            "decode_rows_" + spec.name: () for spec in specs(cfg)})
