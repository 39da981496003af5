"""The block arena's readers and writers, and the one rule for where a
Mosaic kernel may sit beside it.

`CacheSpec.arena_shape` (serving/model.py) defines the layout these index:
`(layers, 1, num_blocks, heads, block_size, row_width)`, block 0 the
scratch block. A served model's programs read and write its pages through
these; the engine side (kv_cache.py, scheduler.py) allocates and maps them
and never looks inside. Imports no model and, at module level, no jax.
"""

from __future__ import annotations

__all__ = ["LANES", "gather_pages", "write_pages", "write_ring",
           "kernel_beside", "cold_or_warm"]

LANES = 128


def gather_pages(leaf, li, pages):
    """Assemble one sequence's K|V matrix from layer `li` of a block
    arena leaf (data or scale plane).

    leaf: (layers, 1, num_blocks, heads, block_size, w).
    pages: (..., P) int32 page table (one row per sequence). Returns
    (..., heads, P*block_size, w): the blocks in logical order, so row
    t of the result is the K|V of absolute position t wherever block
    t // block_size happens to live in the arena. Whole pages are
    indexed straight out of the leaf (no `leaf[li, 0]` plane is sliced
    out first). Entries past a sequence's allocated tail point at the
    scratch block; the causal mask keeps attention from ever reading
    those rows."""
    g = leaf[li, 0, pages]                # (..., P, heads, bs, w)
    g = g.swapaxes(-4, -3)                # (..., heads, P, bs, w)
    return g.reshape(*g.shape[:-3], g.shape[-3] * g.shape[-2],
                     g.shape[-1])


def write_pages(leaf, li, pages, start, real_len, rows):
    """Put rows (B, heads, w), the positions start .. start+real_len-1
    of ONE sequence, into `leaf` (data or scale plane) as WHOLE PAGES:
    the touched pages are read, the real rows merged in by position, and
    the pages scattered back, each a (heads, block_size, w) piece that
    is contiguous in the arena's own layout. (A scatter of single rows
    makes XLA want the arena with heads next to the lanes, and it then
    copies the whole arena into that layout and back, at the program's
    edges or around every layer.) Pages no real row falls in, and pages
    past the page row, are redirected to scratch block 0, which is what
    the row scatter did with pad rows; `start` need not be aligned (a
    later chunk of a chunked prefill keeps the rows before it)."""
    import jax
    import jax.numpy as jnp
    bs, w = leaf.shape[4], leaf.shape[5]
    B, heads = rows.shape[0], rows.shape[1]
    P = pages.shape[0]
    n_t = -(-B // bs) + 1                 # pages B unaligned rows can touch
    off = start % bs
    buf = jax.lax.dynamic_update_slice(
        jnp.zeros((n_t * bs, heads, w), rows.dtype), rows, (off, 0, 0))
    tiles = buf.reshape(n_t, bs, heads, w).transpose(0, 2, 1, 3)
    r = jnp.arange(n_t * bs)
    valid = ((r >= off) & (r < off + real_len)).reshape(n_t, 1, bs, 1)
    t = jnp.arange(n_t)
    pidx = start // bs + t
    ids = jnp.where((pidx < P) & (t * bs < off + real_len),
                    pages[jnp.minimum(pidx, P - 1)], 0)
    merged = jnp.where(valid, tiles, leaf[li, 0, ids])
    return leaf.at[li, 0, ids].set(merged)


def write_ring(leaf, li, ring, real_len, rows):
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


def kernel_beside(arena=None, arena_constraint=None, bucket=None):
    """Whether a Mosaic kernel may sit here: the ONE place the served
    blocks ask. The backend is a TPU, always; and of the rest what the
    caller names. `arena` (a bare array, an `(int8, scales)` pair, or one
    group of a tuple of groups): it is the bare full-precision array, its
    row is whole lanes and no mesh plan pins it (`arena_constraint`, only
    asked whether there is one). `bucket`: a prefill's rows are whole
    128-row tiles. A path that names no arena (the latent block's cold
    prefill attends over the prompt's own rows) is held to the rest."""
    import jax
    if jax.default_backend() != "tpu":
        return False
    if bucket is not None and bucket % LANES:
        return False
    return arena is None or (
        not isinstance(arena, tuple) and arena_constraint is None
        and arena.shape[-1] % LANES == 0)


def cold_or_warm(pfx_len, cold, warm, arena):
    """A prefill's attention in its two forms under one `lax.cond` on the
    traced `pfx_len`: `cold(arena)` for a prompt none of whose rows are
    cached (it attends over its own rows, nothing gathered), `warm(arena)`
    after a prefix hit or a later chunk (the page row gathered back)."""
    import jax
    return jax.lax.cond(pfx_len == 0, cold, warm, arena)
