"""Grouped SwiGLU: the routed experts' three products as ONE Pallas TPU
kernel over rows sorted by expert, every expert's rows starting on a row
tile of their own.

THE LAYOUT (`routed_positions`, `padded_rows`). The rows are cut into
tiles of `row_tile`. Expert e's rows lie together, in the order of their
(token, pick), from row `start[e]` on, where `start` is the running sum
of the group sizes each rounded UP to the tile: a tile has ONE owner.
The rows between a group's end and its last tile's are nobody's (they
hold whatever the caller left there and come back as whatever the
products make of it: a row of a product depends on its own row alone),
and so are the tiles past the last group's, which are never visited,
never written and never to be read. The buffer is static: the routed
rows rounded up to the tile plus one tile an expert. The positions come
from COUNTING, not from a sort: a (token, pick)'s row is its group's
start plus the number of earlier (token, pick)s on the same expert, a
blocked running count over the tokens (a triangular 0/1 matrix product a
block, exact in float32, and a masked sum over the blocks' totals). The
caller lays the rows out once and reads the products back by the same
positions (models/_experts.py::moe, `_combine`): by XLA's gather out
of the plain (R, h) output, or, for a long prompt, by the row DMAs of
ops/routed_combine out of the PACKED one.

THE PACKED OUTPUT (`packed=True`; bfloat16 of whole 256 lanes). A row of
a (R, h) bfloat16 array is no piece of memory: a tile holds 16 rows, a
32-bit word two of them. Packed, the kernel stores the same values,
rounded to bfloat16 the same way, as 32-bit words that hold two COLUMN
halves of ONE row (column j low, column j + h / 2 high: `pack_halves`, a
shift, a mask and an or beside an MXU-bound product), and lays a row's
h / 256 lines of 128 words TOGETHER: the output block is LINES, (tile *
h / 256, 128), a chunk's 128-lane pieces go out by strided stores (one
line of every row), and the caller sees (R, 1, h / 2) uint32, for XLA a
bitcast of the same bytes ((1, 128) tiles: a row is one run of h * 2
bytes, what a one-row DMA can fetch). Measured beside the plain store at
the four models' widths: within 2% either way (PERF.md, PR 39).

THE KERNEL. For the rows of expert e it computes `(silu(x W_gate[e]) *
(x W_up[e])) W_down[e]` with float32 accumulation and a float32 `silu(g)
* u`; the (rows, F) intermediate never leaves VMEM. The weights are read
where they lie, (E, h, F), (E, h, F) and (E, F, h): no fused or
re-laid-out copy exists on either side of the call. The grid walks the
VISITS, `sum(ceil(n_e / row_tile))` of them, one for every tile that has
an owner, in the order of the rows: visit i is tile i, and its expert is
known before the kernel runs (scalar-prefetch operands, computed from
`group_sizes` by a few masked sums on E integers). A visit holds its
expert's three matrices whole in VMEM, (h, F), (h, F), (F, h): one
contiguous block each, double-buffered by the pipeline (34.6 MB at
Moonlight's widths, so `vmem_limit_bytes` is raised), fetched while the
visit before computes and NOT fetched again while the expert stays the
same; an expert with no row has no visit, so it costs no DMA and no
product. A visit computes its tile and stores it whole: it reads nothing
of the output, masks nothing, and no tile is visited twice. The grid is
static (every tile of the buffer); its steps past the walk's count do
nothing and move nothing.

One algorithm, one tile parameter. What differs between the two callers
is how many rows an expert has, and the static row count says it
(`row_tile_for`):
  * few rows, many experts (a decode step: 192 rows over 64 experts):
    the call is a stream of expert weights, a tile is the smallest the
    MXU takes (16 rows: a packed bfloat16 tile), and nearly every visit
    is another expert: the time is the weights' DMA;
  * many rows an expert (a prompt: 4k-131k rows, 64-2,000 an expert):
    the call is bound by the MXU, an expert's matrices are fetched once
    for all its tiles, and a tile is up to 256 rows: a weight tile
    loaded into the MXU then serves 256 rows and the call runs at
    80-85% of the FLOP peak on the rows it computes where 128 gave
    64-72% (PERF.md, PR 37: with ONE owner a tile the larger tile costs
    half a tile more of nobody's rows an expert and still wins from
    1,536 tokens of a 2,048 bucket on; 512 does not fit the VMEM the
    compiler grants at Xing's widths).

Inside a visit the products run in loops over chunks of OUTPUT lanes
(128 of F for gate and up, `_CHUNK` of h for down): each chunk is a
whole contraction, nothing is carried between chunks, and Mosaic
compiles one chunk's code where the whole matrices' took four times as
long to compile for 7-10% of a prompt's call (the serving programs are
compiled at every cold start).

Pallas on a TPU backend, `interpret=True` on the CPU (a test facility),
an error anywhere else, as ops/paged_attention.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["grouped_swiglu", "row_tile_for", "padded_rows",
           "routed_positions", "f_slices", "pack_halves", "unpack_halves"]

# the smallest row tile: one packed bfloat16 tile of 16 sublanes
_MIN_TILE = 16
# the largest: two passes of the MXU's height a weight tile; a larger tile
# computes more rows of nobody (half a tile an expert)
_MAX_TILE = 256
_MIB = 1 << 20
_LANES = 128
# lanes of h a step of the down product writes: the compile time grows
# with it, the speed by 1-2% a doubling
_CHUNK = 256
# tokens a block of the running count: one triangular product a block
_COUNT_BLOCK = 256
# what two buffers of a visit's three weight blocks may take of VMEM (128
# MiB on a v5e, 110 granted to a kernel at most: `vmem_limit_bytes`)
_WEIGHTS_VMEM = 64 * _MIB


def row_tile_for(rows, groups):
    """The row tile for `rows` rows over `groups` experts (both static):
    the power of two that holds an even share of the rows, between the
    packed tile and the largest the MXU gains from."""
    share = max(1, -(-rows // groups))
    tile = 1 << (share - 1).bit_length()
    return max(_MIN_TILE, min(_MAX_TILE, tile))


def _running(v):
    """Running sums of a short int32 vector, as one masked sum: a few
    dozen integers do not earn a scan, a sort or a gather, which cost
    the serving programs more to compile than the kernel does."""
    i = jnp.arange(v.shape[0], dtype=jnp.int32)
    return jnp.sum(jnp.where(i[None, :] <= i[:, None], v[None, :], 0), 1)


def padded_rows(rows, groups, tile):
    """Rows of the buffer that holds `rows` routed rows over `groups`
    experts in tiles of `tile` (all static): the rows rounded up to the
    tile, and a tile an expert for the groups' round-ups."""
    return (-(-rows // tile) + groups) * tile


def routed_positions(picks, live, groups, tile):
    """The layout, by counting. picks (T, k) int32: the experts of each
    token, numbered from the FIRST OF THE `groups` EXPERTS HELD HERE (a
    layer that holds experts f .. f + groups - 1 of more gives picks -
    f). live: (T,) bool by token (every pick of a live token is then one
    of the `groups`), or (T, k) by PICK: a pick is live where `live` says
    so AND its expert is one of the `groups` (0 <= pick < groups); a pick
    of an expert held elsewhere is in no group, is never moved and never
    computed. Returns (pos (T, k) int32, group_sizes (groups,) int32):
    `pos[t, j]` is the row of (t, j) in a buffer of
    `padded_rows(T * k, groups, tile)` rows (or fewer: the live picks'
    groups, each rounded up to `tile`, are all the layout needs), its
    group's start (the groups before it, each rounded up to `tile`) plus
    the number of live (t', j') before (t, j) with the same expert: the
    order a stable sort by expert gives. A pick that is not live counts
    nowhere and its `pos` is `padded_rows(T * k, groups, tile)`: past
    every row, so a scatter drops it and a gather must not trust it."""
    T, k = picks.shape
    e = jnp.arange(groups, dtype=jnp.int32)
    by_pick = live.ndim == 2
    if by_pick:
        live = live & (picks >= 0) & (picks < groups)
    hit = (picks[:, :, None] == e) & (                         # (T, k, E)
        live[:, :, None] if by_pick else live[:, None, None])
    chose = jnp.sum(hit, 1, dtype=jnp.int32)                   # (T, E)
    # how many earlier tokens chose e: inside a block of tokens a strictly
    # lower-triangular product (0/1 and counts up to k in bfloat16, the
    # sums in float32: exact), across blocks a masked sum of their totals
    B = min(T, _COUNT_BLOCK)
    nb = -(-T // B)
    blocks = jnp.pad(chose, ((0, nb * B - T), (0, 0))).reshape(nb, B, groups)
    i = jnp.arange(B, dtype=jnp.int32)
    below = (i[:, None] > i[None, :]).astype(jnp.bfloat16)
    inside = jnp.einsum("ts,bse->bte", below, blocks.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
    totals = jnp.sum(blocks, 1)                                # (nb, E)
    b = jnp.arange(nb, dtype=jnp.int32)
    before = jnp.sum(jnp.where((b[None, :] < b[:, None])[:, :, None],
                               totals[None, :, :], 0), 1)      # (nb, E)
    group_sizes = jnp.sum(totals, 0)
    whole = -(-group_sizes // tile) * tile
    start = _running(whole) - whole
    base = (inside.astype(jnp.int32) + (before + start)[:, None, :]
            ).reshape(nb * B, groups)[:T]
    # a token that names an expert twice (no router does): its earlier picks
    j = jnp.arange(k, dtype=jnp.int32)
    twice = jnp.sum((picks[:, :, None] == picks[:, None, :])
                    & (j[:, None] > j[None, :]), -1, dtype=jnp.int32)
    pos = jnp.sum(jnp.where(hit, base[:, None, :], 0), -1) + twice
    return (jnp.where(live if by_pick else live[:, None], pos,
                      padded_rows(T * k, groups, tile)), group_sizes)


def _visits(group_sizes, tile, visits):
    """The walk over the tiles that have an owner, from `group_sizes`
    (E,): int32 arrays (expert (V,), tile (V,), count (1,)) with V =
    `visits` static. Visit i < count is tile i, whose rows are expert
    `expert[i]`'s; the steps past `count` repeat the last visit, so they
    fetch nothing (with no row anywhere that is tile 0 and the last
    expert, once)."""
    E = group_sizes.shape[0]
    upto = _running(-(-group_sizes.astype(jnp.int32) // tile))
    count = upto[-1]
    at = jnp.clip(jnp.arange(visits, dtype=jnp.int32), 0,
                  jnp.maximum(count - 1, 0))
    expert = jnp.sum(at[:, None] >= upto[None, :], 1, dtype=jnp.int32)
    return jnp.minimum(expert, E - 1), at, count.reshape(1)


def _dot(a, b):
    """a @ b accumulated in float32. bfloat16 operands go through the
    MXU as they are, whatever `jax_default_matmul_precision` says (a
    float32 matter; Mosaic refuses "highest" for bfloat16)."""
    precision = jax.lax.Precision.DEFAULT if a.dtype == jnp.bfloat16 else None
    return jnp.dot(a, b, precision=precision,
                   preferred_element_type=jnp.float32)


def _products(x_ref, gate_ref, up_ref, down_ref, act_ref, store,
              packed=False):
    """One visit's products over the F lanes the weight blocks hold, by
    chunks of output lanes (the module docstring): the activation into
    `act_ref`, then `store(lanes of h, that chunk of act @ down)`; with
    `packed`, `store(n, words)` for chunk n of the packed row: the same
    lanes of h's low and high half (`_lanes_of(h // 2)` of them), two
    products in one word (`pack_halves`)."""
    from jax.experimental import pallas as pl

    h, F = gate_ref.shape
    fc = _LANES if F % _LANES == 0 else F
    hc = _lanes_of(h // 2) if packed else _CHUNK if h % _CHUNK == 0 else h
    x = x_ref[...]

    def f_step(f, _):
        at = pl.ds(pl.multiple_of(f * fc, fc), fc)
        gate = _dot(x, gate_ref[:, at])
        up = _dot(x, up_ref[:, at])
        act_ref[:, at] = (gate * jax.nn.sigmoid(gate)
                          * up).astype(act_ref.dtype)

    jax.lax.fori_loop(0, F // fc, f_step, None)
    act = act_ref[...]

    def h_step(n, _):
        at = pl.ds(pl.multiple_of(n * hc, hc), hc)
        part = _dot(act, down_ref[:, at])
        if packed:
            high = pl.ds(pl.multiple_of(h // 2 + n * hc, hc), hc)
            store(n, pack_halves(part, _dot(act, down_ref[:, high])))
        else:
            store(at, part)

    jax.lax.fori_loop(0, (h // 2 if packed else h) // hc, h_step, None)


def _lanes_of(width):
    """Lanes a chunk of a packed row's `width` words: the most of 512
    down to 128 that divide it."""
    return next(c for c in (512, 384, 256, 128) if width % c == 0)


def pack_halves(lo, hi):
    """Two float32 blocks as ONE block of 32-bit words: each value
    rounded to bfloat16 (to nearest even, as a store in that type rounds
    it), `lo`'s 16 bits in a word's low half and `hi`'s in its high."""
    from jax.experimental.pallas import tpu as pltpu

    def bits(v):
        return pltpu.bitcast(v.astype(jnp.bfloat16).astype(jnp.float32),
                             jnp.uint32)
    return (bits(lo) >> 16) | (bits(hi) & jnp.uint32(0xffff0000))


def unpack_halves(word):
    """(lo, hi) float32 of `pack_halves`'s words, exactly."""
    from jax.experimental.pallas import tpu as pltpu
    return (pltpu.bitcast(word << 16, jnp.float32),
            pltpu.bitcast(word & jnp.uint32(0xffff0000), jnp.float32))


def _store_packed(o_ref):
    """`store(n, words)` of chunk n of a visit's packed rows into its
    output block: LINES of `_LANES` words, (tile * h / 256, 128), a
    row's lines together, so the chunk's 128-lane pieces go to every
    row's line of that piece, a strided store each."""
    from jax.experimental import pallas as pl

    def store(n, word):
        tile, pieces = word.shape[0], word.shape[1] // _LANES
        S = o_ref.shape[0] // tile
        for q in range(pieces):
            o_ref[pl.ds(n * pieces + q, tile, stride=S), :] = \
                word[:, q * _LANES:(q + 1) * _LANES]
    return store


def _kernel(expert_ref, tile_ref, count_ref, x_ref, gate_ref, up_ref,
            down_ref, o_ref, act_ref, *, packed):
    from jax.experimental import pallas as pl

    del expert_ref, tile_ref             # the index maps' alone

    @pl.when(pl.program_id(0) < count_ref[0])
    def _visit():
        def store(at, part):
            o_ref[:, at] = part.astype(o_ref.dtype)

        _products(x_ref, gate_ref, up_ref, down_ref, act_ref,
                  _store_packed(o_ref) if packed else store, packed)


def _kernel_in_slices(expert_ref, tile_ref, count_ref, x_ref, gate_ref,
                      up_ref, down_ref, o_ref, act_ref, acc_ref, *, packed):
    """A visit in `pl.num_programs(1)` steps, a SLICE of F each (the
    expert's matrices do not fit VMEM whole): the slice's part of the
    down product is added up in float32 (`acc_ref`) and the tile stored
    at the last slice."""
    from jax.experimental import pallas as pl

    del expert_ref, tile_ref
    f, last = pl.program_id(1), pl.num_programs(1) - 1

    @pl.when(pl.program_id(0) < count_ref[0])
    def _visit():
        def store(at, part):
            @pl.when(f == 0)
            def _():
                acc_ref[:, at] = part

            @pl.when(f > 0)
            def _():
                acc_ref[:, at] += part

        _products(x_ref, gate_ref, up_ref, down_ref, act_ref, store)

        @pl.when(f == last)
        def _():
            if not packed:
                o_ref[...] = acc_ref[...].astype(o_ref.dtype)
                return
            half = acc_ref.shape[1] // 2
            hc, store_words = _lanes_of(half), _store_packed(o_ref)

            def out_step(n, _):
                low = pl.multiple_of(n * hc, hc)
                store_words(n, pack_halves(
                    acc_ref[:, pl.ds(low, hc)],
                    acc_ref[:, pl.ds(pl.multiple_of(half + low, hc), hc)]))

            jax.lax.fori_loop(0, half // hc, out_step, None)


def f_slices(h, F, itemsize):
    """In how many slices of F a visit holds its expert's matrices: 1
    where two buffers of the three fit the VMEM a kernel is granted
    (Moonlight's 34.6 MB, Xing's 44, Mellum's 24.8), else the power of
    two that makes them fit (command-a's 4096 x 4096: 201 MB whole, 4
    slices of 1,024 lanes, 50 MB)."""
    n = 1
    while 2 * 3 * h * (F // n) * itemsize > _WEIGHTS_VMEM \
            and (F // n) % (2 * _LANES) == 0:
        n *= 2
    return n


@functools.partial(jax.jit, static_argnames=("tile", "interpret", "packed"))
def _call(xs, w_gate, w_up, w_down, group_sizes, tile, interpret,
          packed=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, h = xs.shape
    E, _, F = w_gate.shape
    walk = _visits(group_sizes, tile, R // tile)
    item = jnp.dtype(w_gate.dtype).itemsize
    slices = f_slices(h, F, item)
    Fs = F // slices
    # two buffers of the three matrices (a slice of them) and of the row
    # tile in and out, the activation, and room for the chunks' float32
    # values (and the sliced visit's float32 tile)
    vmem = (2 * 3 * h * Fs * item
            + (4 * h + Fs) * tile * jnp.dtype(xs.dtype).itemsize + 8 * _MIB)
    if packed:
        # LINES of 128 words, a row's h / 256 of them together; the caller
        # sees (R, 1, h / 2), the same bytes (a bitcast for XLA)
        out = jax.ShapeDtypeStruct((R * h // (2 * _LANES), _LANES), jnp.uint32)
        out_block = (tile * h // (2 * _LANES), _LANES)
    else:
        out = jax.ShapeDtypeStruct((R, h), xs.dtype)
        out_block = (tile, h)
    params = dict(out_shape=out, interpret=interpret)
    shape = (R, 1, h // 2) if packed else (R, h)
    if slices == 1:
        return pl.pallas_call(
            functools.partial(_kernel, packed=packed),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(R // tile,),
                in_specs=[
                    pl.BlockSpec((tile, h), lambda i, e, t, *_: (t[i], 0)),
                    pl.BlockSpec((None, h, F), lambda i, e, *_: (e[i], 0, 0)),
                    pl.BlockSpec((None, h, F), lambda i, e, *_: (e[i], 0, 0)),
                    pl.BlockSpec((None, F, h), lambda i, e, *_: (e[i], 0, 0)),
                ],
                out_specs=pl.BlockSpec(out_block,
                                       lambda i, e, t, *_: (t[i], 0)),
                scratch_shapes=[pltpu.VMEM((tile, F), xs.dtype)]),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=int(min(vmem, 110 * _MIB))),
            name="grouped_swiglu", **params,
        )(*walk, xs, w_gate, w_up, w_down).reshape(shape)

    # a step past the walk's count repeats the last visit's LAST slice:
    # it fetches nothing
    def at(i, f, c):
        return jnp.where(i < c[0], f, slices - 1)

    return pl.pallas_call(
        functools.partial(_kernel_in_slices, packed=packed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(R // tile, slices),
            in_specs=[
                pl.BlockSpec((tile, h), lambda i, f, e, t, c: (t[i], 0)),
                pl.BlockSpec((None, h, Fs),
                             lambda i, f, e, t, c: (e[i], 0, at(i, f, c))),
                pl.BlockSpec((None, h, Fs),
                             lambda i, f, e, t, c: (e[i], 0, at(i, f, c))),
                pl.BlockSpec((None, Fs, h),
                             lambda i, f, e, t, c: (e[i], at(i, f, c), 0)),
            ],
            out_specs=pl.BlockSpec(out_block,
                                   lambda i, f, e, t, c: (t[i], 0)),
            scratch_shapes=[pltpu.VMEM((tile, Fs), xs.dtype),
                            pltpu.VMEM((tile, h), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(min(vmem + 4 * tile * h, 110 * _MIB))),
        name="grouped_swiglu_sliced", **params,
    )(*walk, xs, w_gate, w_up, w_down).reshape(shape)


def grouped_swiglu(xs, w_gate, w_up, w_down, group_sizes, row_tile,
                   packed=False):
    """The grouped SwiGLU of rows laid out by `routed_positions`.

    xs: (R, h), R a multiple of `row_tile`: the rows of expert 0, then,
    from the next whole tile on, of expert 1, ...; group_sizes: (E,)
    integers, how many rows each expert has (each rounded up to the
    tile, their sum at most R); w_gate, w_up: (E, h, F), w_down: (E, F,
    h), in xs's type. Returns (R, h) in xs's type: for a row r of expert
    e `(silu(xs[r] w_gate[e]) * (xs[r] w_up[e])) w_down[e]`, products
    accumulated in float32, the activation in float32 and rounded to
    xs's type before the down product. A row between a group's end and
    its tile's comes back as the same function of whatever it held; a
    tile past the last group's is NOT WRITTEN. An expert with no row is
    never read. row_tile: rows a visit computes (a multiple of 16), the
    layout's own (`row_tile_for` of the routed row count).

    packed (static; bfloat16 rows of whole 256 lanes alone): the SAME
    values, rounded to bfloat16 the same way, as (R, 1, h / 2) uint32: a
    row's words lie TOGETHER in memory (one row a (1, 128)-tiled slab,
    where a (R, h) bfloat16 array interleaves 16 rows a tile), word j
    holding column j in its low half and column j + h / 2 in its high
    (`pack_halves`): what ops/routed_combine fetches a row a DMA.

    Compiled by Mosaic on a TPU backend, interpreted on the CPU (a test
    facility), an error on any other backend: an interpreted kernel must
    not pass for the real one."""
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(
            "grouped_swiglu compiles for TPU (Mosaic) and interprets on "
            f"CPU for tests; the active backend is {platform!r}")
    tile = int(row_tile)
    if tile % _MIN_TILE:
        raise ValueError(f"row_tile {tile} is no multiple of {_MIN_TILE}")
    if xs.shape[0] % tile:
        raise ValueError(f"{xs.shape[0]} rows are no whole tiles of {tile}")
    if packed and (xs.dtype != jnp.bfloat16 or xs.shape[1] % (2 * _LANES)):
        raise ValueError(
            f"packed rows are bfloat16 of whole {2 * _LANES} lanes; got "
            f"{xs.dtype} of {xs.shape[1]}")
    return _call(xs, w_gate, w_up, w_down, group_sizes, tile,
                 platform == "cpu", bool(packed))
