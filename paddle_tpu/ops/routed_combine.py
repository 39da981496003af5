"""Routed combine: a prompt's expert products read back and summed by ONE
Pallas TPU kernel that fetches every row by a DMA of its own.

THE ROWS. `ops/grouped_swiglu.grouped_swiglu(..., packed=True)` leaves the
products as (R, 1, h / 2) uint32: a row's words lie TOGETHER in HBM (a
(1, 128)-tiled slab a row), word j holding column j (low half) and column
j + h / 2 (high half), both already rounded to bfloat16. A row of a (R,
h) bfloat16 array is NOT a piece of memory (16 rows interleave in a
tile, and Mosaic refuses a one-row slice of any tiled array, whatever
its type: PERF.md, PR 39); a row of this one is a single run of h * 2
bytes, the best a DMA can be given.

THE KERNEL. The grid walks TOKEN TILES, one step behind itself: step i
starts a row DMA for every (token, pick) of tile i whose position is
inside the buffer (`pos < R`), out of `ys` where it lies
(`memory_space=pl.ANY`; no (k, T, h) copy exists anywhere) into one of
two VMEM buffers (k x tile rows), then waits for tile i - 1's rows (64
at a time: the semaphore counts bytes) and sums them, so a tile's rows
fly while the tile before is summed. How many rows a buffer waits for
is carried from step to step in SMEM (`count`); the positions reach the
scalar core a tile at a time (a blocked SMEM operand: a 16k-token prompt of 8 picks has 131,072 of them,
too many for a scalar-prefetch table). A position past the buffer (a
dead token's picks, a pick of an expert held elsewhere) starts NO DMA
and adds exactly 0 by a select on the words: an out-of-range DMA would
fault where XLA's gather clips, and a buffer never written may hold NaN.

THE SUM is `models/_experts._weighted_sum`'s to the bit: each product as
the expert kernel rounded it, times its float32 weight, added in float32
in pick order; then the shared experts' term in float32 (times
`shared_scale`), ONE rounding to the output's type: no float32 (T, h)
leaves the kernel, and the sum is written where the shared term lay (an
alias, where their types agree). Asked for float32 and given no shared
term it hands out the picks' sum itself (a layer that holds a share of
the experts, under its `lax.cond`: models/_experts.moe adds the shared
term behind it as it did). A token whose picks are all past the buffer
gets 0 (+ the shared term): the layout gives a dead token no position,
so its row needs no mask of its own.

Inside a step the sum reads 16 tokens x 128 lanes at a time: the buffer
holds a row as `h / 256` lines of 128 words, so a (tokens, lanes) block is
a STRIDED load of it (one line of each token), and the output block is
the ordinary (tile, h).

WHAT A ROW COSTS (a v5e; PERF.md, PR 39): the start of its DMA, 6-10
bundles of scalar work, and its share of the sum, ~3.5 ns at Mellum's
width: 10.3 and 12.4 ns a (token, pick) at 8 and 6 picks of 4.6 and 4 KB
rows, 19.5 at Xing's 4 picks of 7 KB, 15.2 where seven in eight picks
have no row (a pick without a row is a predicated start: it costs its
bundles).
Two things were worth 2.3x: Mosaic's range checks of a DMA with a dynamic
offset (two a DMA, 17 of the 23 bundles a start took: the kernel tests
every position itself and turns them off), and waiting for the rows 64
at a time (the semaphore counts bytes; a wait a row cost 5 ns a row).
What bounded nothing: the bytes of a row (4 of its 9 lines take as long),
the token tile (64 to 256), the DMA's priority, the order of start and
sum inside a step.

Pallas on a TPU backend, `interpret=True` on the CPU (a test facility),
an error anywhere else, as ops/paged_attention.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .grouped_swiglu import unpack_halves

__all__ = ["routed_combine"]

_LANES = 128
_MIB = 1 << 20
# a blocked one-dimensional SMEM operand moves whole tiles of 1,024 words
_SMEM_TILE = 1024
# tokens a block of the sum: one packed tile of a bfloat16 output
_SUB = 16
# tokens a grid step: with two buffers of k rows a token, command-a's 8
# picks of 2,048 words are 33.5 MiB, the most of any model served
_TILE = 128
# what the two row buffers may take of VMEM
_BUFFERS_VMEM = 40 * _MIB
# rows a wait takes off the semaphore, largest first
_WAITS = (64, 8, 1)


def _kernel(pos_s, pos_v, w_ref, *rest, k, tile, rows, shared_scale):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if shared_scale is None:
        ys_ref, o_ref, buf, count, sems = rest
        shared_ref = None
    else:
        shared_ref, ys_ref, o_ref, buf, count, sems = rest
    i = pl.program_id(0)
    n = pl.num_programs(0) - 1
    W = buf.shape[-1]
    S = W // _LANES
    # the buffers as the DMAs see them, a row a slab: row (slot * k + j) *
    # tile + t is pick j of the tile's token t; and as the sum reads them:
    # LINES of 128 words, a row's S lines together
    lines = buf.reshape(2 * k * tile * S, _LANES)

    def rows_copy(p, at, count, slot):
        """`count` rows (static) from row p of ys to row `at` of the
        buffers. A wait reads the sizes alone."""
        return pltpu.make_async_copy(ys_ref.at[pl.ds(p, count)],
                                     buf.at[pl.ds(at, count)], sems.at[slot])

    @pl.when(i < n)
    def _start():
        slot = jax.lax.rem(i, 2)

        def token(t, started):
            for j in range(k):
                p = pos_s[t * k + j]
                has_row = jnp.logical_and(p >= 0, p < rows)

                @pl.when(has_row)
                def _():
                    rows_copy(p, (slot * k + j) * tile + t, 1, slot).start()
                started = started + has_row.astype(jnp.int32)
            return started
        count[slot] = jax.lax.fori_loop(0, tile, token, 0)

    @pl.when(i > 0)
    def _sum():
        slot = jax.lax.rem(i - 1, 2)
        # the semaphore counts bytes: the rows that were started are
        # waited for 64 at a time, then 8, then one (a wait a row cost
        # 5 ns a row beside the 7-10 of its start)
        left = count[slot]
        for step in _WAITS:
            def landed(_, carry, step=step):
                rows_copy(0, 0, step, slot).wait()
                return carry
            jax.lax.fori_loop(0, left // step, landed, 0)
            left = jax.lax.rem(left, step)

        def block(r, _):
            at = pl.ds(pl.multiple_of(r * _SUB, _SUB), _SUB)
            here = pos_v[at, :]                                # (_SUB, k)
            inside = jnp.logical_and(here >= 0, here < rows)
            weight = w_ref[at, :]
            # a pick's mask and weight over the lanes, once a block
            masks = [jnp.broadcast_to(inside[:, j:j + 1], (_SUB, _LANES))
                     for j in range(k)]
            weights = [jnp.broadcast_to(weight[:, j:j + 1], (_SUB, _LANES))
                       for j in range(k)]
            first = (slot * k * tile + r * _SUB) * S

            # a LOOP over the row's lines, not S copies of its body: the
            # body is traced and lowered for every prompt bucket at every
            # start (S copies cost the warm set-up 11 s at Mellum's widths)
            def line(c, _):
                lo = hi = None
                for j in range(k):
                    word = lines[pl.ds(first + j * (tile * S) + c, _SUB,
                                       stride=S), :]
                    a, b = unpack_halves(
                        jnp.where(masks[j], word, jnp.uint32(0)))
                    a, b = a * weights[j], b * weights[j]
                    lo, hi = (a, b) if j == 0 else (lo + a, hi + b)
                for half, y in ((0, lo), (W, hi)):
                    la = pl.ds(pl.multiple_of(half + c * _LANES, _LANES),
                               _LANES)
                    if shared_ref is not None:
                        s = shared_ref[at, la].astype(jnp.float32)
                        if shared_scale != 1.0:
                            s = s * shared_scale
                        y = y + s
                    o_ref[at, la] = y.astype(o_ref.dtype)
            jax.lax.fori_loop(0, S, line, None)
        jax.lax.fori_loop(0, tile // _SUB, block, None)


@functools.partial(jax.jit, static_argnames=("shared_scale", "dtype",
                                             "interpret"))
def _call(ys, pos, w, shared, shared_scale, dtype, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, k = pos.shape
    R, _, W = ys.shape
    h, tile = 2 * W, _TILE
    if 2 * k * tile * W * 4 > _BUFFERS_VMEM:
        raise ValueError(
            f"two buffers of {k} x {tile} rows of {W} words pass "
            f"{_BUFFERS_VMEM >> 20} MiB of VMEM")
    n = -(-T // tile)
    pad = n * tile - T
    if pad:
        # the last tile's missing tokens: picks past the buffer
        pos = jnp.pad(pos, ((0, pad), (0, 0)), constant_values=R)
        w = jnp.pad(w, ((0, pad), (0, 0)))
        if shared is not None:
            shared = jnp.pad(shared, ((0, pad), (0, 0)))
    slot_words = -(-tile * k // _SMEM_TILE) * _SMEM_TILE
    flat = jnp.pad(pos.reshape(n, tile * k),
                   ((0, 0), (0, slot_words - tile * k)),
                   constant_values=R).reshape(-1)
    before = lambda i: (jnp.maximum(i - 1, 0), 0)
    operands = [flat, pos, w]
    in_specs = [
        pl.BlockSpec((slot_words,), lambda i: (jnp.minimum(i, n - 1),),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((tile, k), before),
        pl.BlockSpec((tile, k), before),
    ]
    aliases = {}
    if shared is not None:
        # the shared term's block is read where the sum's is written: in
        # its type the sum takes its place (XLA's fusion did as much; a
        # (T, h) more was 117 MB of Xing's 16k bucket at its peak)
        if shared.dtype == dtype:
            aliases = {len(operands): 0}
        operands.append(shared)
        in_specs.append(pl.BlockSpec((tile, h), before))
    out = pl.pallas_call(
        functools.partial(
            _kernel, k=k, tile=tile, rows=R,
            shared_scale=None if shared is None else shared_scale),
        grid=(n + 1,),
        in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tile, h), before),
        out_shape=jax.ShapeDtypeStruct((n * tile, h), dtype),
        scratch_shapes=[pltpu.VMEM((2 * k * tile, 1, W), jnp.uint32),
                        pltpu.SMEM((2,), jnp.int32),
                        pltpu.SemaphoreType.DMA((2,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # Mosaic's two range checks of a DMA with a dynamic offset
            # are 17 of the 23 bundles a row's start took (24 ns; 6 and
            # ~7 ns without): a started row is in range by the kernel's
            # own test of its position, its buffer row by construction
            disable_bounds_checks=True,
            vmem_limit_bytes=int(min(
                2 * k * tile * W * 4 + 8 * tile * h + 16 * _MIB,
                110 * _MIB))),
        input_output_aliases=aliases,
        interpret=interpret, name="routed_combine",
    )(*operands, ys)
    return out[:T] if pad else out


def routed_combine(ys, pos, w, shared=None, shared_scale=1.0,
                   dtype=jnp.bfloat16):
    """The weighted sum of every token's routed rows, plus the shared
    experts' term.

    ys: (R, 1, h / 2) uint32, the packed rows of
    `grouped_swiglu(..., packed=True)`; pos: (T, k) int32, the row of
    each (token, pick), `>= R` for a pick that has none (a dead token's,
    a pick of an expert held elsewhere): never fetched, adds exactly 0;
    w: (T, k) float32 weights; shared: (T, h) or None, added in float32
    times `shared_scale` (static). Returns (T, h) in `dtype`: `sum_j
    float32(row pos[t, j]) * w[t, j]` in pick order `+ float32(shared[t])
    * shared_scale`, rounded once. A row that no position names may hold
    anything (NaN included).

    Compiled by Mosaic on a TPU backend, interpreted on the CPU (a test
    facility), an error on any other backend."""
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(
            "routed_combine compiles for TPU (Mosaic) and interprets on "
            f"CPU for tests; the active backend is {platform!r}")
    if ys.dtype != jnp.uint32 or ys.ndim != 3 or ys.shape[1] != 1 \
            or ys.shape[2] % _LANES:
        raise ValueError(
            "ys is not grouped_swiglu's packed rows (R, 1, h / 2) uint32 "
            f"of whole {_LANES}-word lines: {ys.dtype}{ys.shape}")
    return _call(ys, pos.astype(jnp.int32), w.astype(jnp.float32), shared,
                 float(shared_scale), jnp.dtype(dtype), platform == "cpu")
