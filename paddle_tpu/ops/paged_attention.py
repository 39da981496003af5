"""Paged decode attention: a Pallas TPU kernel that walks the page table
over the KV block arena where it lies.

The serving decode step attends ONE new position per slot over that
slot's cached rows. The XLA form (serving/pages `gather_pages` +
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


# -- grouped heads and a row range ---------------------------------------------
#
# A model whose `group` query heads share one KV head (the arena holds the
# KV heads: a page is (kv_heads, block_size, 2*hd)) reads a page ONCE for all
# of them: the group's queries are the ROWS of two small matrix products, q
# (rows, hd) x K^T (hd, block_size) and p (rows, block_size) x V, per KV
# head, where the kernel above multiplies one query into a page on the VPU.
# And the walk has a lower bound: slot s attends positions lo[s]..ts[s] and
# fetches pages lo // block_size .. ts // block_size alone, the page that
# holds `lo` masked below it, not skipped. The page table is read as a RING:
# page p of a slot is block pt[s, p % P]. For a table that holds every page
# (p < P) that is the table itself; for a window layer's fixed ring of
# ceil(window / block_size) + 1 blocks it is where position p * block_size
# was written (serving/model.py). What PR 32 taught the latent walk is taken
# over where it applies: the walk moves in groups of _GROUPED_PAGES pages,
# each page copied from its own block into a row slice of one buffer a KV
# head, and one step of the online softmax attends a group (two products a
# KV head a group, not a page). A short last group is attended at the
# buffer's size with the pages that were not fetched masked (the buffers are
# zeroed once, so what they hold is always a number: zeros, or an earlier
# group's rows). The live page's write-back is waited for when the stage is
# next needed (or by the last program). And nothing drains between slots:
# the groups of a slot and of the next LIVE slot are one stream, of which
# _GROUPED_BUFFERS - 1 groups are in flight whenever a step computes, carried
# from program to program as the latent walk's is (its section below has the
# account: PRIMED, BASE, the next live slot found through the prefetched
# lengths), the next slot's pages read through its own `lo` and its own ring.


# pages a step of the grouped walk's online softmax and group buffers (the
# latent walk's lesson, PR 32: a step's fixed cost wants several pages)
_GROUPED_PAGES = 4
_GROUPED_BUFFERS = 3


def _grouped_kernel(layer_ref, pt_ref, lo_ref, len_ref, q_ref, new_ref,
                    arena_ref, arena_out_ref, o_ref, kv_buf, stage, state,
                    sems, wsem, *, block_size, pages, group, buffers,
                    block=1):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = pl.program_id(0)
    n_slots = pl.num_programs(0)
    li = layer_ref[0]
    bs = block_size
    length = len_ref[s]                       # live rows; 0 = frozen slot
    lo = lo_ref[s]                            # first position attended
    kv_heads, rows, hd = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    # what one program leaves the next, as in the latent kernel below
    PRIMED, BASE, WRITING = 0, 1, 2

    @pl.when(s == 0)
    def _first():
        # a group's buffer is attended whole, the rows of pages that were
        # not fetched masked: they must be numbers (p = 0 times them)
        kv_buf[...] = jnp.zeros_like(kv_buf)
        state[PRIMED] = 0
        state[BASE] = 0
        state[WRITING] = 0

    def write_back(blk=0):
        return pltpu.make_async_copy(
            stage, arena_out_ref.at[li, 0, blk], wsem)

    def block_of(t, p):
        return pt_ref[t * pages + jax.lax.rem(p, pages)]

    def page_copy(t, p, buf, i):
        """Page p of slot t -> rows [i * bs, (i + 1) * bs) of every KV
        head of group buffer `buf`."""
        return pltpu.make_async_copy(
            arena_ref.at[li, 0, block_of(t, p)],
            kv_buf.at[buf, :, pl.ds(pl.multiple_of(i * bs, bs), bs)],
            sems.at[buf])

    def attend(kv, p0, carry):
        """The group of pages that starts at page p0 in one step of the
        online softmax, every KV head in turn; kv (kv_heads, G * bs, 2*hd)
        as stored. Rows outside [lo, length) are masked: below `lo` in
        the first page, past the live row in the last, the pages of a
        short group that were not fetched, and whatever a ring block
        still holds of a position that left the window."""
        pos = p0 * bs + jax.lax.broadcasted_iota(
            jnp.int32, (rows, group * bs), 1)
        keep = jnp.logical_and(pos >= lo, pos < length)
        out = []
        for h in range(kv_heads):
            m, l, acc = carry[h]
            sc = jax.lax.dot_general(
                q_ref[0, h], kv[h, :, :hd], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)       # (rows, G * bs)
            sc = jnp.where(keep, sc, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            pr = jnp.where(keep, jnp.exp(sc - m_new), 0.0)
            l = l * alpha + jnp.sum(pr, axis=1, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                pr.astype(kv.dtype), kv[h, :, hd:], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)       # (rows, hd)
            out.append((m_new, l, acc))
        return tuple(out)

    @pl.when(length > 0)
    def _live():
        first = jax.lax.div(lo, bs)
        last = jax.lax.div(length - 1, bs)
        n_groups = jax.lax.div(last - first + group, group)
        base = state[BASE]
        # the next live slot (a frozen one in between is passed over and
        # touches nothing); n_slots where this is the last
        nxt = jax.lax.while_loop(
            lambda t: jnp.logical_and(
                t < n_slots, len_ref[jnp.minimum(t, n_slots - 1)] == 0),
            lambda t: t + 1, s + 1)
        nxt_live = nxt < n_slots
        nxt = jnp.minimum(nxt, n_slots - 1)
        first_next = jax.lax.div(lo_ref[nxt], bs)
        end_next = jnp.where(
            nxt_live, jax.lax.div(len_ref[nxt] - 1, bs) + 1, 0)

        def start(j):
            """Group j of the stream of groups that runs from this slot's
            (0..n_groups - 1, cut from its own first page) into the next
            live slot's (cut from ITS first page): its live pages into
            buffer (base + j) % buffers. Nothing where the stream has
            ended."""
            own = j < n_groups
            t = jnp.where(own, s, nxt)
            p0 = jnp.where(own, first + j * group,
                           first_next + (j - n_groups) * group)
            live = jnp.clip(jnp.where(own, last + 1, end_next) - p0, 0, group)
            buf = jax.lax.rem(base + j, buffers)

            def one(i, c):
                page_copy(t, p0 + i, buf, i).start()
                return c
            jax.lax.fori_loop(0, live, one, 0)

        def starts(j, c):
            start(j)
            return c

        def landed(g):
            live = jnp.clip(last + 1 - first - g * group, 0, group)
            buf = jax.lax.rem(base + g, buffers)

            def one(i, c):
                page_copy(s, first, buf, i).wait()     # a wait reads sizes only
                return c
            jax.lax.fori_loop(0, live, one, 0)
            return buf

        # buffers - 1 groups are in flight whenever a step computes: the
        # slot before this one has started this slot's share of them
        # (unless it was none: the first live slot), and what reaches past
        # this slot's last group into the next one's starts here
        have = jnp.where(state[PRIMED] == 1,
                         jnp.minimum(n_groups, buffers - 1), 0)
        jax.lax.fori_loop(have, buffers - 1, starts, 0)

        def group_step(g, carry):
            buf = landed(g)
            # into the buffer the step before this one read
            start(g + buffers - 1)
            return attend(kv_buf[buf], first + g * group, carry)

        carry = jax.lax.fori_loop(
            0, n_groups - 1, group_step,
            tuple((jnp.full((rows, 1), _NEG_INF, jnp.float32),
                   jnp.zeros((rows, 1), jnp.float32),
                   jnp.zeros((rows, hd), jnp.float32))
                  for _ in range(kv_heads)))
        # the last group holds the live page: it takes this step's own
        # K|V row where it has landed, is attended WITH it and goes back
        # whole from the stage (see the kernel above)
        g_last = n_groups - 1
        buf = landed(g_last)
        start(g_last + buffers - 1)
        state[PRIMED] = nxt_live.astype(jnp.int32)
        state[BASE] = jax.lax.rem(base + n_groups, buffers)
        i_live = last - (first + g_last * group)
        # BLOCK ROWS: the pass's `block` new rows are positions length -
        # block .. length - 1, which one page holds (`block` divides the
        # page and the first of them); 1 is the one row of a decode step
        at = jax.lax.rem(length - block, bs)
        r0 = pl.multiple_of(i_live * bs, bs)
        page = kv_buf[buf, :, pl.ds(r0, bs), :]
        row = jax.lax.broadcasted_iota(jnp.int32, page.shape, 1)
        if block == 1:
            page = jnp.where(row == at, new_ref[0].astype(jnp.float32),
                             page.astype(jnp.float32)).astype(stage.dtype)
        else:
            new = new_ref[0].astype(jnp.float32)
            page = page.astype(jnp.float32)
            for b in range(block):
                page = jnp.where(row == at + b, new[:, b:b + 1], page)
            page = page.astype(stage.dtype)
        kv_buf[buf, :, pl.ds(r0, bs), :] = page

        # the write-back of the slot before this one is waited for here,
        # where the stage is next needed (or by the last program), not at
        # its own program's end
        @pl.when(state[WRITING] == 1)
        def _stage_free():
            write_back().wait()
        stage[...] = page
        write_back(block_of(s, last)).start()
        state[WRITING] = 1
        done = attend(kv_buf[buf], first + g_last * group, carry)
        for h in range(kv_heads):
            _, l, acc = done[h]
            o_ref[0, h] = (acc / l).astype(o_ref.dtype)

    @pl.when(length == 0)
    def _frozen():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(jnp.logical_and(s == n_slots - 1, state[WRITING] == 1))
    def _drain():
        write_back().wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def _grouped_call(q, new, arena, layer, pt, lo, lengths, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # BLOCK ROWS (q (S, B, q_heads, hd), new (S, B, kv_heads, w)): a pass
    # of block diffusion writes B rows a slot and every one of its B x
    # group queries a KV head attends all `lengths` rows, the block's own
    # included: the queries are B x group rows of the same two products,
    # under the one mask `pos < length`
    block = new.shape[1] if new.ndim == 4 else 1
    s_dim, kv_heads, w = new.shape[0], new.shape[-2], new.shape[-1]
    hd = w // 2
    heads = q.shape[-2] // kv_heads * block
    # the group's queries as the rows of a matrix product: a whole packed
    # tile of the arena's type (16 rows of bfloat16, 8 of float32), the
    # rows past the group zero
    tile = 32 // arena.dtype.itemsize
    rows = -(-heads // tile) * tile
    qg = (q.astype(jnp.float32) * (1.0 / np.sqrt(hd))).astype(arena.dtype)
    if new.ndim == 4:
        qg = qg.reshape(s_dim, block, kv_heads, heads // block, hd) \
            .transpose(0, 2, 1, 3, 4)
        new = new.transpose(0, 2, 1, 3)
    qg = qg.reshape(s_dim, kv_heads, heads, hd)
    if rows != heads:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rows - heads), (0, 0)))
    block_size = arena.shape[4]
    pages = pt.shape[1]
    group = min(_GROUPED_PAGES, pages)
    kern = functools.partial(_grouped_kernel, block_size=block_size,
                             pages=pages, group=group,
                             buffers=_GROUPED_BUFFERS, block=block)
    q_spec = pl.BlockSpec((1, kv_heads, rows, hd), lambda s, *_: (s, 0, 0, 0))
    new_spec = pl.BlockSpec((1, kv_heads, block, w),
                            lambda s, *_: (s, 0, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    arena, out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(s_dim,),
            in_specs=[q_spec, new_spec, hbm],
            out_specs=[hbm, q_spec],
            scratch_shapes=[
                pltpu.VMEM((_GROUPED_BUFFERS, kv_heads, group * block_size,
                            w), arena.dtype),
                pltpu.VMEM((kv_heads, block_size, w), arena.dtype),
                pltpu.SMEM((3,), jnp.int32),
                pltpu.SemaphoreType.DMA((_GROUPED_BUFFERS,)),
                pltpu.SemaphoreType.DMA(()),
            ]),
        out_shape=[jax.ShapeDtypeStruct(arena.shape, arena.dtype),
                   jax.ShapeDtypeStruct((s_dim, kv_heads, rows, hd),
                                        q.dtype)],
        # operand 6 counts the four scalar-prefetch operands
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention_grouped",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      pt.reshape(-1).astype(jnp.int32), lo.astype(jnp.int32),
      lengths.astype(jnp.int32), qg,
      new if new.ndim == 4 else new[:, :, None, :], arena)
    out = out[:, :, :heads]
    if q.ndim == 4:
        return out.reshape(s_dim, kv_heads, block, heads // block, hd) \
            .transpose(0, 2, 1, 3, 4).reshape(q.shape), arena
    return out.reshape(s_dim, kv_heads * heads, hd), arena


def paged_attention(q, k, v, arena, layer, pt, ts, done=None, lo=None):
    """One decode step's attention over the paged pool, with its write.

    q: (S, q_heads, hd), k, v: (S, heads, hd), the projections of the new
    position ts[s] of slot s; q_heads is heads times the GROUP of query
    heads that share a KV head (1: a GPT's; query head i reads KV head
    i // group). lo: None, or (S,) int32, the first position slot s
    attends (a window layer's max(0, ts - window + 1)); pages before
    lo // block_size are not fetched. With a group or a bound the page
    table is a ring, page p at pt[s, p % P], and the kernel is the
    grouped one above; group 1 without a bound is the kernel it always
    was. arena: the bare full-precision array (layers, 1,
    num_blocks, heads, block_size, 2*hd). layer: which plane of it (a
    python int or an int32 scalar: one kernel serves every layer). pt:
    (S, P) int32 page table, ts: (S,) int32 positions. Slot s writes
    k|v as row ts[s] % block_size of block pt[s, ts[s] // block_size]
    and attends over positions 0..ts[s], its own row included, read
    from blocks pt[s, 0..ts[s] // block_size]. done: (S,) bool or None;
    a frozen slot writes nothing, reads nothing and gets zeros.

    BLOCK ROWS (a pass of block diffusion): q (S, B, q_heads, hd), k, v
    (S, B, heads, hd), the projections of positions ts[s] .. ts[s] + B -
    1. Slot s writes the B rows through its live page (B divides the page
    and ts[s], so they never straddle one) and EVERY one of its B x group
    queries a KV head attends positions 0 .. ts[s] + B - 1: one length, no
    mask inside the block. The grouped kernel with B x group rows in its
    two products; returns (S, B, q_heads, hd). No `lo`.

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
    lengths = ts + (q.shape[1] if q.ndim == 4 else 1)
    if done is not None:
        lengths = jnp.where(done, 0, lengths)
    new = jnp.concatenate([k, v], -1).astype(arena.dtype)
    if q.ndim == 4:
        if lo is not None or arena.shape[4] % q.shape[1]:
            raise ValueError(
                "block rows attend everything and never straddle a page: "
                f"no `lo`, and the block ({q.shape[1]}) divides the page "
                f"({arena.shape[4]})")
        return _grouped_call(q, new, arena, layer, pt,
                             jnp.zeros_like(lengths), lengths,
                             platform == "cpu")
    if q.shape[1] == k.shape[1] and lo is None:
        return _call(q, new, arena, layer, pt, lengths, platform == "cpu")
    if lo is None:
        lo = jnp.zeros_like(lengths)
    return _grouped_call(q, new, arena, layer, pt, lo, lengths,
                         platform == "cpu")


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
# x page.
#
# With 16 or 32 query rows a page is too little work to cover what one
# step of the online softmax costs whatever its size (the semaphore's
# wait, the chain score -> max -> exp -> context, the accumulator's
# rescale), so the walk moves in GROUPS of G pages: each page of a group
# is copied from its own arena block into a row slice of ONE contiguous
# (G * block_size, W) buffer, and one step attends over the group. A
# slot's pages are its whole groups and a TAIL of 1..G pages that ends
# with the live one; the tail is attended at its own size (one branch a
# size), so a page that is not live is neither fetched nor computed.
# Nothing drains between slots: the grid runs the slots in order on one
# core and the scalar-prefetch operands hold every slot's table and
# length, so the groups of a slot and of the next live slot are ONE
# stream, of which `buffers - 1` groups are in flight whenever a step
# computes (a slot's first pages are on their way while the slot before
# it attends its last ones), and the live page's write-back is waited for
# when the stage is next needed (or by the last program). The step's own
# row written through the live page and the aliased arena are the
# kernel's above.

# pages a step of the online softmax (a power of two; 512 rows at the
# serving page of 128) and group buffers. Measured on a v5e at 16 and 32
# heads: tools/bench_latent_decode.py, PERF.md section 6, PR 32 (a page
# costs 0.38-0.41 us walked one by one, 0.24-0.27 in twos, 0.21 in fours
# and in eights, beside 0.20 us for its DMA; two buffers leave 0.31).
_LATENT_GROUP = 4
_LATENT_BUFFERS = 3
_LATENT_VMEM = 4 << 20


def _latent_walk(heads, pages, w, block_size, itemsize):
    """(G, buffers) of the latent walk, from shapes alone: the largest
    power of two of pages a step up to _LATENT_GROUP that a slot's table
    can fill and whose buffers stay inside _LATENT_VMEM."""
    del heads                     # 16 and 32 rows want the same walk
    page = block_size * w * itemsize
    group = 1
    while (2 * group <= min(_LATENT_GROUP, pages)
           and _LATENT_BUFFERS * 2 * group * page <= _LATENT_VMEM):
        group *= 2
    return group, _LATENT_BUFFERS


def _latent_kernel(layer_ref, pt_ref, len_ref, q_ref, new_ref, arena_ref,
                   arena_out_ref, o_ref, kv_buf, stage, state, sems, wsem, *,
                   block_size, pages, group, buffers):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = pl.program_id(0)
    n_slots = pl.num_programs(0)
    li = layer_ref[0]
    bs = block_size
    length = len_ref[s]                       # live rows; 0 = frozen slot
    heads, w = q_ref.shape[1], q_ref.shape[2]
    # the rows of one packed tile of the arena's type (16 of bfloat16, 8
    # of float32), or the page where that is smaller
    tile = 32 // jnp.dtype(kv_buf.dtype).itemsize
    tile = tile if bs % tile == 0 else bs
    # what one program leaves the next: whether this slot's first groups
    # are already on their way, the buffer its group 0 goes to, whether
    # a write-back is in flight
    PRIMED, BASE, WRITING = 0, 1, 2

    @pl.when(s == 0)
    def _first():
        state[PRIMED] = 0
        state[BASE] = 0
        state[WRITING] = 0

    def page_copy(t, p, buf, i):
        """Page p of slot t -> rows [i * bs, (i + 1) * bs) of buffer buf."""
        blk = pt_ref[t * pages + p]
        return pltpu.make_async_copy(
            arena_ref.at[li, 0, blk, 0],
            kv_buf.at[buf, pl.ds(pl.multiple_of(i * bs, bs), bs)],
            sems.at[buf])

    def wait_page(buf, i):
        page_copy(s, 0, buf, i).wait()        # a wait reads sizes only

    def write_back(blk=0):
        return pltpu.make_async_copy(
            stage, arena_out_ref.at[li, 0, blk, 0], wsem)

    def attend(kv, q, carry, rows=None):
        """One step of the online softmax; kv (k * bs, W) as stored, q
        (heads, W) in the same type, statistics in float32. rows: how
        many of kv's rows are live (None: all of them)."""
        m, l, acc = carry
        sc = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if rows is not None:
            col = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
            sc = jnp.where(col < rows, sc, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)                        # (heads, 1)
        pr = jnp.exp(sc - m_new)                          # (heads, k * bs)
        l = l * alpha + jnp.sum(pr, axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            pr.astype(kv.dtype), kv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    @pl.when(length > 0)
    def _live():
        n_pages = jax.lax.div(length + bs - 1, bs)
        n_full = jax.lax.div(n_pages - 1, group)   # whole groups
        tail = n_pages - n_full * group            # 1..G pages, the last live
        base = state[BASE]
        # the next live slot (a frozen one in between is passed over and
        # touches nothing); n_slots where this is the last
        nxt = jax.lax.while_loop(
            lambda t: jnp.logical_and(
                t < n_slots, len_ref[jnp.minimum(t, n_slots - 1)] == 0),
            lambda t: t + 1, s + 1)
        nxt_live = nxt < n_slots
        nxt = jnp.minimum(nxt, n_slots - 1)
        n_next = jnp.where(
            nxt_live, jax.lax.div(len_ref[nxt] + bs - 1, bs), 0)

        def start(j):
            """Group j of the stream of groups that runs from this
            slot's (0..n_full, the tail last) into the next live slot's:
            its live pages, each from its own block, into buffer
            (base + j) % buffers. Nothing where the stream has ended."""
            own = j <= n_full
            t = jnp.where(own, s, nxt)
            p0 = jnp.where(own, j, j - n_full - 1) * group
            live = jnp.clip(jnp.where(own, n_pages, n_next) - p0, 0, group)
            buf = jax.lax.rem(base + j, buffers)

            def one(i, carry):
                page_copy(t, p0 + i, buf, i).start()
                return carry
            jax.lax.fori_loop(0, live, one, 0)

        def starts(j, carry):
            start(j)
            return carry

        # buffers - 1 groups are in flight whenever a step computes. The
        # slot before this one has started this slot's share of them
        # (unless it was none: the first live slot); what reaches past
        # this slot's tail into the next one's groups starts here
        have = jnp.where(state[PRIMED] == 1,
                         jnp.minimum(n_full + 1, buffers - 1), 0)
        jax.lax.fori_loop(have, buffers - 1, starts, 0)
        q = q_ref[0]

        def group_step(g, carry):
            buf = jax.lax.rem(base + g, buffers)
            for i in range(group):
                wait_page(buf, i)
            # into the buffer the step before this one read
            start(g + buffers - 1)
            return attend(kv_buf[buf], q, carry)

        carry = jax.lax.fori_loop(
            0, n_full, group_step,
            (jnp.full((heads, 1), _NEG_INF, jnp.float32),
             jnp.zeros((heads, 1), jnp.float32),
             jnp.zeros((heads, w), jnp.float32)))
        buf = jax.lax.rem(base + n_full, buffers)
        state[PRIMED] = nxt_live.astype(jnp.int32)
        state[BASE] = jax.lax.rem(base + n_full + 1, buffers)
        # the live page takes this step's row where it has landed (the
        # packed tile that holds row `at`, not the page), is attended
        # WITH it as the tail's last page and goes back whole from the
        # stage: a DMA cannot write one row of a packed tile, and the
        # buffer is refilled before a write from it would have ended
        at = jax.lax.rem(length - 1, bs)

        def landed(i, carry):
            wait_page(buf, i)
            return carry
        jax.lax.fori_loop(0, tail, landed, 0)
        start(n_full + buffers - 1)
        live0 = pl.multiple_of((tail - 1) * bs, bs)
        t0 = pl.multiple_of(live0 + at // tile * tile, tile)
        rows = kv_buf[buf, pl.ds(t0, tile), :]
        row = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0)
        kv_buf[buf, pl.ds(t0, tile), :] = jnp.where(
            row == jax.lax.rem(at, tile), new_ref[0].astype(jnp.float32),
            rows.astype(jnp.float32)).astype(kv_buf.dtype)

        @pl.when(state[WRITING] == 1)
        def _stage_free():
            write_back().wait()
        stage[...] = kv_buf[buf, pl.ds(live0, bs), :]
        write_back(pt_ref[s * pages + n_pages - 1]).start()
        state[WRITING] = 1
        for k in range(1, group + 1):
            @pl.when(tail == k)
            def _tail(k=k):
                _, l, acc = attend(kv_buf[buf, pl.ds(0, k * bs), :], q, carry,
                                   rows=(k - 1) * bs + at + 1)
                o_ref[0] = (acc / l).astype(o_ref.dtype)

    @pl.when(length == 0)
    def _frozen():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(jnp.logical_and(s == n_slots - 1, state[WRITING] == 1))
    def _drain():
        write_back().wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def _latent_call(q, new, arena, layer, pt, lengths, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s_dim, heads, w = q.shape
    block_size = arena.shape[4]
    pages = pt.shape[1]
    group, buffers = _latent_walk(heads, pages, w, block_size,
                                  arena.dtype.itemsize)
    kern = functools.partial(_latent_kernel, block_size=block_size,
                             pages=pages, group=group, buffers=buffers)
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
                pltpu.VMEM((buffers, group * block_size, w), arena.dtype),
                pltpu.VMEM((block_size, w), arena.dtype),
                pltpu.SMEM((3,), jnp.int32),
                pltpu.SemaphoreType.DMA((buffers,)),
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
    The walk attends `_latent_walk`'s G pages a step (a function of the
    shapes: nothing a caller sets), so the float32 sums are taken G
    pages at a time; only live pages are fetched or computed.

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
