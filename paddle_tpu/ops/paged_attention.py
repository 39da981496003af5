"""Paged decode attention: a Pallas TPU kernel that walks the page table
over the KV block arena where it lies.

The serving decode step attends ONE new position per slot over that
slot's cached rows. The XLA form (models/gpt_decode `_gather_pages` +
einsum) assembles every slot's WHOLE page row into a dense
(S, heads, P*block_size, hd) K and V first and masks afterwards, so a
step moves the arena's worth of bytes whatever is live. This kernel
reads only the live pages, straight out of the arena: the page table and
the positions are scalar-prefetch operands, the arena stays in HBM
(`memory_space=pl.ANY`, no slice of it is ever materialised), and each
program DMAs its slot's pages `arena[layer, 0, pt[s, p]]` for
p <= ts[s] // block_size through a small ring of VMEM buffers while the
online softmax (float32 running maximum, sum and accumulator, float32
scores, the gather path's 1/sqrt(hd) and mask `position <= ts`) runs
over the page that has landed. Pages past the live one are neither
fetched nor computed; a frozen slot (`done`) fetches nothing and returns
zeros (its logits are discarded by the caller).

The step's own K|V row is written by the kernel too: it is put into the
live page in VMEM, the page is attended with it and copied back whole,
and the arena leaves the call as the buffer it came in
(`input_output_aliases`). An XLA scatter beside the kernel would want
the arena in a layout of its own (heads next to the lanes) and XLA would
copy the whole arena into it and back around every layer's call; with
the write here the decode program holds no operation on the arena but
this one, in the layout every other program hands it over in.

Layout is the arena's own (models/gpt_decode `paged_arena_shapes`),
(layers, 1, num_blocks, heads, block_size, 2*hd) with a row's K in lanes
[0, hd) and its V in [hd, 2*hd): a page is copied as it lies, one
contiguous (heads, block_size, 2*hd) piece with block_size on sublanes
and K|V on lanes, and the math keeps that layout. The query comes in
zero-extended to 2*hd lanes, so the score is a lane reduction of
page * q (the V half meets zeros); the context is a sublane reduction of
p * page, whose V half is the answer (the caller drops the K half). No
transpose or lane shuffle of K, V or the arena exists on either side of
the call. Pallas on a TPU backend, `interpret=True` on the CPU (tests),
an error anywhere else, as ops/flash_attention.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["paged_attention", "latent_paged_attention"]

_NEG_INF = -1e30
# pages in flight per program: deep enough to hide a DMA's latency behind
# the pages before it, small enough that the ring stays a few MB of VMEM
# at 25 heads (8 x 25 x 16 x 128 lanes x 2 B = 0.8 MB)
_RING = 8


def _kernel(layer_ref, pt_ref, len_ref, q_ref, new_ref, arena_ref,
            arena_out_ref, o_ref, kv_buf, stage, sems, wsem, *,
            block_size, pages, ring):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = pl.program_id(0)
    li = layer_ref[0]
    length = len_ref[s]                       # live rows; 0 = frozen slot
    n_pages = jax.lax.div(length + block_size - 1, block_size)
    heads, w = q_ref.shape[1], q_ref.shape[3]             # w = 2*hd

    def page_copy(p, slot):
        """K|V of page p of this slot -> ring buffer `slot`."""
        blk = pt_ref[s * pages + p]
        return pltpu.make_async_copy(arena_ref.at[li, 0, blk],
                                     kv_buf.at[slot], sems.at[slot])

    def attend(kv, q, carry, rows=None):
        """One page of the online softmax; kv (heads, bs, w) float32.
        rows: how many rows of the page are live (None: all of them,
        which holds for every page before the last)."""
        m, l, acc = carry
        sc = jnp.sum(kv * q, axis=-1, keepdims=True)      # (heads, bs, 1)
        if rows is not None:
            row = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
            sc = jnp.where(row < rows, sc, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)                        # (heads, 1, 1)
        pr = jnp.exp(sc - m_new)                          # (heads, bs, 1)
        l = l * alpha + jnp.sum(pr, axis=1, keepdims=True)
        acc = acc * alpha + jnp.sum(pr * kv, axis=1, keepdims=True)
        return m_new, l, acc

    @pl.when(length > 0)
    def _live():
        last = n_pages - 1
        for i in range(ring):
            @pl.when(i < n_pages)
            def _prime(i=i):
                page_copy(i, i).start()
        # the V half meets zeros, so the lane reduction is q . k alone
        q = q_ref[0].astype(jnp.float32) * (1.0 / np.sqrt(w // 2))

        def page_step(p, carry):
            slot = jax.lax.rem(p, ring)
            page_copy(p, slot).wait()
            kv = kv_buf[slot].astype(jnp.float32)

            @pl.when(p + ring < n_pages)
            def _refill():
                page_copy(p + ring, slot).start()

            return attend(kv, q, carry)

        carry = jax.lax.fori_loop(
            0, last, page_step,
            (jnp.full((heads, 1, 1), _NEG_INF, jnp.float32),
             jnp.zeros((heads, 1, 1), jnp.float32),
             jnp.zeros((heads, 1, w), jnp.float32)))
        # the live page: this step's own K|V row goes into it at row
        # (length - 1) % block_size, the page is attended WITH the row,
        # and goes back to the arena whole (a block holding a decode
        # position belongs to this slot alone; a DMA cannot write one
        # row of a packed tile)
        slot = jax.lax.rem(last, ring)
        page_copy(last, slot).wait()
        kv = kv_buf[slot].astype(jnp.float32)
        at = jax.lax.rem(length - 1, block_size)
        row = jax.lax.broadcasted_iota(jnp.int32, kv.shape, 1)
        kv = jnp.where(row == at, new_ref[0].astype(jnp.float32), kv)
        stage[...] = kv.astype(stage.dtype)
        back = pltpu.make_async_copy(
            stage, arena_out_ref.at[li, 0, pt_ref[s * pages + last]], wsem)
        back.start()
        _, l, acc = attend(kv, q, carry, rows=at + 1)
        o_ref[0] = (acc / l).astype(o_ref.dtype)
        back.wait()

    @pl.when(length == 0)
    def _frozen():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(q, new, arena, layer, pt, lengths, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s_dim, heads, w = new.shape
    block_size = arena.shape[4]
    pages = pt.shape[1]
    ring = min(_RING, pages)
    kern = functools.partial(_kernel, block_size=block_size, pages=pages,
                             ring=ring)
    row = pl.BlockSpec((1, heads, 1, w), lambda s, *_: (s, 0, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    arena, out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(s_dim,),
            in_specs=[row, row, hbm],
            out_specs=[hbm, row],
            scratch_shapes=[
                pltpu.VMEM((ring, heads, block_size, w), arena.dtype),
                pltpu.VMEM((heads, block_size, w), arena.dtype),
                pltpu.SemaphoreType.DMA((ring,)),
                pltpu.SemaphoreType.DMA(()),
            ]),
        out_shape=[jax.ShapeDtypeStruct(arena.shape, arena.dtype),
                   jax.ShapeDtypeStruct((s_dim, heads, 1, w), q.dtype)],
        # the arena is updated where it lies (operand 5 counts the three
        # scalar-prefetch operands)
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      pt.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.concatenate([q, jnp.zeros_like(q)], -1)[:, :, None, :],
      new[:, :, None, :], arena)
    return out[:, :, 0, w // 2:], arena


def paged_attention(q, k, v, arena, layer, pt, ts, done=None):
    """One decode step's attention over the paged pool, with its write.

    q, k, v: (S, heads, hd), the projections of the new position ts[s]
    of slot s. arena: the bare full-precision array (layers, 1,
    num_blocks, heads, block_size, 2*hd). layer: which plane of it (a
    python int or an int32 scalar: one kernel serves every layer). pt:
    (S, P) int32 page table, ts: (S,) int32 positions. Slot s writes
    k|v as row ts[s] % block_size of block pt[s, ts[s] // block_size]
    and attends over positions 0..ts[s], its own row included, read
    from blocks pt[s, 0..ts[s] // block_size]. done: (S,) bool or None;
    a frozen slot writes nothing, reads nothing and gets zeros.

    Returns (context (S, heads, hd) in q's dtype, the arena): softmax(q
    k^T / sqrt(hd)) v with float32 scores, statistics and accumulator;
    the arena is the input's own buffer (`input_output_aliases`), so a
    caller that donates it gets an in-place update.

    Compiled by Mosaic on a TPU backend, interpreted on the CPU (a test
    facility), an error on any other backend: an interpreted kernel must
    not pass for the real one."""
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(
            "paged_attention compiles for TPU (Mosaic) and interprets on "
            f"CPU for tests; the active backend is {platform!r}")
    if isinstance(arena, tuple):
        raise TypeError(
            "paged_attention reads the full-precision arena; a quantized "
            "(int8, scales) arena takes the gather path")
    lengths = ts + 1
    if done is not None:
        lengths = jnp.where(done, 0, lengths)
    new = jnp.concatenate([k, v], -1).astype(arena.dtype)
    return _call(q, new, arena, layer, pt, lengths, platform == "cpu")


# -- latent rows: every head attends the SAME cached row ----------------------
#
# Multi-head latent attention caches ONE compressed row a token a layer
# (models/moonlight: the normed latent `c`, the rotated shared key
# `k_rope`, zero lanes up to a multiple of 128) and decodes in the
# absorbed form: head h's query is carried into the latent space, its
# score is q_ext_h . row and its context sum p * row, so K and V are both
# the page as it lies and the heads are the ROWS of a matrix product. The
# arena is (layers, 1, num_blocks, 1, block_size, W): a page of a layer is
# one contiguous (block_size, W) tile, copied as it lies; scores are
# q (heads, W) x page^T on the MXU and the context p (heads, block_size)
# x page. The page walk, the DMA ring, the step's own row written through
# the live page and the aliased arena are the kernel's above.


def _latent_kernel(layer_ref, pt_ref, len_ref, q_ref, new_ref, arena_ref,
                   arena_out_ref, o_ref, kv_buf, stage, sems, wsem, *,
                   block_size, pages, ring):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = pl.program_id(0)
    li = layer_ref[0]
    length = len_ref[s]                       # live rows; 0 = frozen slot
    n_pages = jax.lax.div(length + block_size - 1, block_size)
    heads, w = q_ref.shape[1], q_ref.shape[2]

    def page_copy(p, slot):
        blk = pt_ref[s * pages + p]
        return pltpu.make_async_copy(arena_ref.at[li, 0, blk, 0],
                                     kv_buf.at[slot], sems.at[slot])

    def attend(kv, q, carry, rows=None):
        """One page of the online softmax; kv (block_size, W) as stored,
        q (heads, W) in the same type, statistics in float32."""
        m, l, acc = carry
        sc = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if rows is not None:
            col = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
            sc = jnp.where(col < rows, sc, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)                        # (heads, 1)
        pr = jnp.exp(sc - m_new)                          # (heads, bs)
        l = l * alpha + jnp.sum(pr, axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            pr.astype(kv.dtype), kv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    @pl.when(length > 0)
    def _live():
        last = n_pages - 1
        for i in range(ring):
            @pl.when(i < n_pages)
            def _prime(i=i):
                page_copy(i, i).start()
        q = q_ref[0]

        def page_step(p, carry):
            slot = jax.lax.rem(p, ring)
            page_copy(p, slot).wait()
            kv = kv_buf[slot]

            @pl.when(p + ring < n_pages)
            def _refill():
                page_copy(p + ring, slot).start()

            return attend(kv, q, carry)

        carry = jax.lax.fori_loop(
            0, last, page_step,
            (jnp.full((heads, 1), _NEG_INF, jnp.float32),
             jnp.zeros((heads, 1), jnp.float32),
             jnp.zeros((heads, w), jnp.float32)))
        # the live page takes this step's row, is attended WITH it and
        # goes back whole (see the kernel above)
        slot = jax.lax.rem(last, ring)
        page_copy(last, slot).wait()
        kv = kv_buf[slot].astype(jnp.float32)
        at = jax.lax.rem(length - 1, block_size)
        row = jax.lax.broadcasted_iota(jnp.int32, kv.shape, 0)
        kv = jnp.where(row == at, new_ref[0].astype(jnp.float32), kv)
        stage[...] = kv.astype(stage.dtype)
        back = pltpu.make_async_copy(
            stage, arena_out_ref.at[li, 0, pt_ref[s * pages + last], 0],
            wsem)
        back.start()
        _, l, acc = attend(stage[...], q, carry, rows=at + 1)
        o_ref[0] = (acc / l).astype(o_ref.dtype)
        back.wait()

    @pl.when(length == 0)
    def _frozen():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _latent_call(q, new, arena, layer, pt, lengths, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s_dim, heads, w = q.shape
    block_size = arena.shape[4]
    pages = pt.shape[1]
    ring = min(_RING, pages)
    kern = functools.partial(_latent_kernel, block_size=block_size,
                             pages=pages, ring=ring)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    arena, out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(s_dim,),
            in_specs=[pl.BlockSpec((1, heads, w), lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec((1, 1, w), lambda s, *_: (s, 0, 0)),
                      hbm],
            out_specs=[hbm,
                       pl.BlockSpec((1, heads, w), lambda s, *_: (s, 0, 0))],
            scratch_shapes=[
                pltpu.VMEM((ring, block_size, w), arena.dtype),
                pltpu.VMEM((block_size, w), arena.dtype),
                pltpu.SemaphoreType.DMA((ring,)),
                pltpu.SemaphoreType.DMA(()),
            ]),
        out_shape=[jax.ShapeDtypeStruct(arena.shape, arena.dtype),
                   jax.ShapeDtypeStruct((s_dim, heads, w), q.dtype)],
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_paged_attention",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      pt.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      q, new[:, None, :], arena)
    return out, arena


def latent_paged_attention(q, row, arena, layer, pt, ts, done=None):
    """One decode step of absorbed latent attention over the paged pool,
    with its write.

    q: (S, heads, W), each head's query in the ROW's space, already
    scaled, zero in the lanes the row pads. row: (S, W), the new cache
    row of position ts[s] of slot s. arena: (layers, 1, num_blocks, 1,
    block_size, W), full precision. Slot s writes `row` as row ts[s] %
    block_size of block pt[s, ts[s] // block_size] and every head
    attends over positions 0..ts[s], its own row included: score
    q_h . row_t (float32), softmax in float32, context sum p row_t. A
    frozen slot (`done`) writes nothing, reads nothing and gets zeros.

    Returns (context (S, heads, W) in q's dtype, of which the caller
    keeps the latent lanes; the arena, its input's own buffer). Mosaic
    on a TPU backend, interpreted on the CPU (tests), an error elsewhere."""
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(
            "latent_paged_attention compiles for TPU (Mosaic) and "
            f"interprets on CPU for tests; the active backend is "
            f"{platform!r}")
    lengths = ts + 1
    if done is not None:
        lengths = jnp.where(done, 0, lengths)
    return _latent_call(q.astype(arena.dtype), row.astype(arena.dtype),
                        arena, layer, pt, lengths, platform == "cpu")
