"""Multi-head LATENT attention over one cached row a token: what the
DeepSeek-V3 block (models/moonlight.py: Moonlight-16B-A3B, Xing4.0-29B-A4B)
and the latent layers of a hybrid model (models/kimi_linear.py) share.

  * the cache holds ONE row a token a layer, `[c | k_rope]` after the
    latent's norm (kv_lora_rank + qk_rope_head_dim values, zero-padded to
    a multiple of 128 lanes: 576 -> 640), shared by all heads;
  * PREFILL attends in the EXPANDED form (`expand`: k_nope and v from the
    latents through W_kvb, causal attention at head widths 192/192/128:
    the flash forward on a TPU for a cold prompt, masked XLA attention over
    the gathered page row after a prefix hit and on the CPU);
  * DECODE attends in the ABSORBED form: `q_lat_h = q_nope_h W_UK_h^T`,
    score `q_lat_h . c + q_rope_h . k_rope`, context `sum p c`, then `o_h =
    o_lat_h W_UV_h` (ops/paged_attention.latent_paged_attention walks the
    page table over the latent arena; `absorbed_attention`'s gather and
    two einsums where the kernel does not apply).

What this module reads of a config: `heads`, `kv_lora_rank`,
`qk_nope_head_dim`, `qk_rope_head_dim`, `qk_head_dim`, `v_head_dim`,
`row_values`, `row_width`, `rms_eps`, `rope_theta`, `rope_scaling`,
`q_lora_rank` (None: the query is one matrix), `mla_q_scale` and
`mla_kv_scale` (1.0 where a config does not say: what the query, both its
parts before rotation, and the normed latent c are multiplied by; a model
published with `mla_scale_q_lora` / `mla_scale_kv_lora` sets them to
sqrt(hidden / q_lora_rank) and sqrt(hidden / kv_lora_rank); the shared
rope key is never scaled, and the cached row holds the SCALED latent) and
`mla_use_nope` (False where a config does not say): the published key of a
model whose latent layers carry NO positions. The 64 "rope" values of
the query and of the
key are then projected, cached and scored UNROTATED: the row, the kernels
and every width stay what they are, and with the key off the programs
trace what they traced (tests/test_served_programs.py).

Scopes: `mla/project`, `mla/absorb`, `mla/attend`. Imports no model and,
at module level, no jax.
"""

from __future__ import annotations

import math

from ..serving import pages as _pages
from . import _decoder

__all__ = ["attention_scale", "project", "cache_rows", "wkvb_heads",
           "expand", "decode_attention_path", "absorbed_attention",
           "prefill_attend", "step_attend"]


def attention_scale(cfg):
    """1 / sqrt(nope + rope), times YaRN's mscale(factor,
    mscale_all_dim) squared where the positions are stretched."""
    scale = 1.0 / math.sqrt(cfg.qk_head_dim)
    sc = cfg.rope_scaling
    if sc is not None and sc.get("mscale_all_dim", 0):
        scale *= _decoder.yarn_mscale(sc["factor"],
                                      sc["mscale_all_dim"]) ** 2
    return scale


def project(cfg, lp, x, pos):
    """The attention's projections of tokens x (T, h) at positions pos
    (T,): q_nope (T, n, nope), q_rope (T, n, rope) rotated, and the cache
    row's two parts, c (T, rank) normed and k_rope (T, rope) rotated.
    The query is one matrix, or (`q_lora_rank`) a low-rank pair with a
    norm between. Under `mla_use_nope` nothing is rotated. `mla_q_scale`
    and `mla_kv_scale` multiply q and c: folded into the float32 weight of
    the norm each stands behind (one rounding, not two), or applied to q
    where the query is one matrix."""
    T = x.shape[0]
    n, nope = cfg.heads, cfg.qk_nope_head_dim
    theta, scaling = cfg.rope_theta, cfg.rope_scaling
    q_scale = getattr(cfg, "mla_q_scale", 1.0)
    if cfg.q_lora_rank is None:
        q = x @ lp["wq"]
        if q_scale != 1.0:
            q = q * q_scale
    else:
        q = _decoder.rms(x @ lp["wqa"], _scaled(lp["q_norm"], q_scale),
                         cfg.rms_eps) @ lp["wqb"]
    q = q.reshape(T, n, cfg.qk_head_dim)
    rotate = not getattr(cfg, "mla_use_nope", False)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    if rotate:
        q_rope = _decoder.rope(q_rope, pos[:, None], theta, scaling)
    kva = x @ lp["wkva"]
    c = _decoder.rms(kva[:, :cfg.kv_lora_rank],
                     _scaled(lp["kv_norm"], getattr(cfg, "mla_kv_scale", 1.0)),
                     cfg.rms_eps)
    k_rope = kva[:, cfg.kv_lora_rank:]
    if rotate:
        k_rope = _decoder.rope(k_rope, pos, theta, scaling)
    return q_nope, q_rope, c, k_rope


def _scaled(g, scale):
    """A norm's weight times a published scale, in float32 (`rms` works
    there); the weight itself where the scale is 1."""
    import jax.numpy as jnp
    return g if scale == 1.0 else g.astype(jnp.float32) * scale


def cache_rows(cfg, c, k_rope):
    """[c | k_rope | 0] (T, row_width): the row as the arena stores it."""
    import jax.numpy as jnp
    pad = cfg.row_width - cfg.row_values
    parts = [c, k_rope]
    if pad:
        parts.append(jnp.zeros((c.shape[0], pad), c.dtype))
    return jnp.concatenate(parts, -1)


def wkvb_heads(cfg, lp):
    """(W_UK, W_UV): (rank, n, nope) and (rank, n, v), the key and the
    value half of W_kvb by head."""
    w = lp["wkvb"].reshape(cfg.kv_lora_rank, cfg.heads,
                           cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def expand(cfg, lp, c, k_rope):
    """Keys (T, n, nope + rope) and values (T, n, v) from cache rows."""
    import jax.numpy as jnp
    w_uk, w_uv = wkvb_heads(cfg, lp)
    k_nope = jnp.einsum("tc,cnd->tnd", c, w_uk)
    v = jnp.einsum("tc,cnd->tnd", c, w_uv)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, None, :],
                                  k_nope.shape[:2] + k_rope.shape[-1:])], -1)
    return k, v


def decode_attention_path(arena, arena_constraint=None):
    """ "latent_paged_kernel" on a TPU over the bare arena with a
    lane-aligned row; "gather" elsewhere (the CPU)."""
    return "latent_paged_kernel" \
        if _pages.kernel_beside(arena, arena_constraint) else "gather"


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


def prefill_attend(cfg, lp, u, j, pos, arena, li, pages, pfx_len, real_len,
                   flash, cold_only=False):
    """A prefill's attention sublayer (its norm `norm1` first) over the
    rows u (B, h) of ONE sequence, row j = 0..B-1 at position pos = pfx_len
    + j (both handed in: a program makes them once, not once a layer),
    `real_len` of them real: writes the rows' cache rows into layer `li`
    of `arena` as whole pages of `pages` (P,) and attends in the expanded
    form, a cold prompt over its own rows (`flash`: through the flash
    forward), after a prefix hit over the whole gathered page row.
    `cold_only` (static): the caller never has a hit (a model with
    several cache groups) and no warm branch is traced. Returns (the
    sublayer's output (B, h), arena)."""
    import jax
    import jax.numpy as jnp
    B = u.shape[0]
    L = pages.shape[0] * arena.shape[4]
    scale = attention_scale(cfg)
    with jax.named_scope("mla/project"):
        h = _decoder.rms(u, lp["norm1"], cfg.rms_eps)
        q_nope, q_rope, c, k_rope = project(cfg, lp, h, pos)
        q = jnp.concatenate([q_nope, q_rope], -1)
        rows = cache_rows(cfg, c, k_rope)
        arena = _pages.write_pages(arena, li, pages, pfx_len,
                                   real_len, rows[:, None, :])

    def cold(arena):
        k, v = expand(cfg, lp, c, k_rope)
        if flash:
            from ..ops.flash_attention import flash_causal_rows
            return flash_causal_rows(q, k, v, scale, length=real_len)
        return _decoder.masked_attention(
            q, k, v, j[None, :] <= j[:, None], scale)

    def warm(arena):
        cached = _pages.gather_pages(arena, li, pages)[0]  # (L, W)
        k, v = expand(
            cfg, lp, cached[:, :cfg.kv_lora_rank],
            cached[:, cfg.kv_lora_rank:cfg.row_values])
        return _decoder.masked_attention(
            q, k, v, jnp.arange(L)[None, :] <= pos[:, None], scale,
            _warm_query_rows(cfg))

    with jax.named_scope("mla/attend"):
        o = cold(arena) if cold_only else \
            _pages.cold_or_warm(pfx_len, cold, warm, arena)
    with jax.named_scope("mla/project"):
        return o.reshape(B, -1) @ lp["wo"], arena


def _warm_query_rows(cfg):
    """Query rows a block of the warm prefill's masked attention: 512 up to
    32 heads; beyond, as many as keep a block's float32 scores of ALL heads
    over the page row what 32 heads make of 512 (64 heads: 256; at 512 the
    scores of a 5,120-row page row were 671 MB a block, and the 4,096-row
    prefill did not fit beside LongCat-Flash's weights and arena)."""
    return 512 if cfg.heads <= 32 else max(128, 512 * 32 // cfg.heads)


def step_attend(cfg, lp, u, ts, arena, li, pt, done, attention):
    """A decode step's attention sublayer (its norm `norm1` first) over
    one row a slot u (S, h) at positions ts (S,): writes each live slot's
    cache row into layer `li` of `arena` through the page table pt (S, P)
    and attends over 0..ts in the absorbed form, by `attention`
    (`decode_attention_path`'s verdict). A frozen slot (`done`) writes to
    the scratch block (the gather) or nowhere (the kernel). Returns (the
    sublayer's output (S, h), arena)."""
    import jax
    import jax.numpy as jnp
    s_dim, P = pt.shape
    bs = arena.shape[4]
    dtype = arena.dtype
    rank = cfg.kv_lora_rank
    scale = attention_scale(cfg)
    pad = cfg.row_width - cfg.row_values
    with jax.named_scope("mla/project"):
        h = _decoder.rms(u, lp["norm1"], cfg.rms_eps)
        q_nope, q_rope, c, k_rope = project(cfg, lp, h, ts)
        row = cache_rows(cfg, c, k_rope)
    with jax.named_scope("mla/absorb"):
        w_uk, w_uv = wkvb_heads(cfg, lp)
        q_lat = jnp.einsum("snd,cnd->snc", q_nope, w_uk)
        parts = [q_lat, q_rope]
        if pad:
            parts.append(jnp.zeros(q_rope.shape[:2] + (pad,), dtype))
        q_ext = (jnp.concatenate(parts, -1).astype(jnp.float32)
                 * scale).astype(dtype)
    with jax.named_scope("mla/attend"):
        if attention == "latent_paged_kernel":
            from ..ops.paged_attention import latent_paged_attention
            o_ext, arena = latent_paged_attention(
                q_ext, row, arena, li, pt, ts, done)
        else:
            wblk = pt[jnp.arange(s_dim), ts // bs]
            if done is not None:
                wblk = jnp.where(done, 0, wblk)
            arena = arena.at[li, 0, wblk, 0, ts % bs].set(row)
            cached = _pages.gather_pages(arena, li, pt)[:, 0]
            o_ext = absorbed_attention(
                q_ext, cached,
                jnp.arange(P * bs)[None, :] <= ts[:, None])
    with jax.named_scope("mla/absorb"):
        o = jnp.einsum("snc,cnd->snd", o_ext[..., :rank], w_uv)
    with jax.named_scope("mla/project"):
        return o.reshape(s_dim, -1) @ lp["wo"], arena
