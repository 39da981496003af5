"""Grouped SwiGLU: the routed experts' three products as ONE Pallas TPU
kernel over rows sorted by expert.

`xs` (R, h) holds the routed rows, expert by expert; `group_sizes` (E,)
says how many each expert has. For the rows of expert e the kernel
computes `(silu(x W_gate[e]) * (x W_up[e])) W_down[e]` with float32
accumulation and a float32 `silu(g) * u`; the (rows, F) intermediate
never leaves VMEM. Rows past the groups' sum are nobody's and come back
zero. The weights are read where they lie, (E, h, F), (E, h, F) and
(E, F, h): no fused or re-laid-out copy exists on either side of the call.

One algorithm, one tile parameter. The rows are cut into tiles of
`row_tile`; the grid walks the VISITS, one for every (expert, row tile)
pair that shares a row, in the order of the rows, so the walk is known
before the kernel runs (scalar-prefetch operands, computed from
`group_sizes` by a few XLA operations on E + 1 integers). A visit holds
its expert's three matrices whole in VMEM, (h, F), (h, F), (F, h): one
contiguous block each, double-buffered by the pipeline (34.6 MB at
Moonlight's widths, so `vmem_limit_bytes` is raised), fetched while the
visit before computes and NOT fetched again while the expert stays the
same; an expert with no row has no visit, so it costs no DMA and no
product. The visit computes the whole tile and stores the rows that are
its expert's (a tile that a group boundary cuts is visited once by each
side and stays in VMEM between them). After the last expert the rows in
no group are one more group with no product, whose visits store zeros.

What differs between the two callers is how many rows an expert has, and
the static row count says it (`row_tile_for`):
  * few rows, many experts (a decode step: 192 rows over 64 experts):
    the call is a stream of expert weights, a tile is the smallest the
    MXU takes (16 rows: a packed bfloat16 tile), and nearly every visit
    is another expert: the time is the weights' DMA;
  * many rows an expert (a prompt: 12k-49k rows, 190-770 an expert): the
    call is bound by the MXU, a tile is 128 rows (one pass of the MXU's
    own height; at 256 the call is 2-5% faster for the large buckets
    and 512 is slower, PERF.md, PR 28), and an expert's matrices are
    fetched once for all its tiles.

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

__all__ = ["grouped_swiglu", "row_tile_for"]

# the smallest row tile: one packed bfloat16 tile of 16 sublanes
_MIN_TILE = 16
# the largest: the MXU's own height; larger tiles compute more rows of
# other experts and take longer to compile
_MAX_TILE = 128
_MIB = 1 << 20
_LANES = 128
# lanes of h a step of the down product writes: the compile time grows
# with it, the speed by 1-2% a doubling
_CHUNK = 256


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


def _visits(group_sizes, rows, tile):
    """The walk over (group, row tile) pairs, from `group_sizes` (E,).
    Group E is the rows in no group. Returns int32 arrays
    (group (V,), weights (V,), tile (V,), offsets (E + 2,), count (1,))
    with V = tiles + E static: visit i is rows of `group[i]` inside row
    tile `tile[i]`, reads the matrices of expert `weights[i]` (the
    group's own; the last expert's again for group E, so nothing is
    fetched), and visits past `count` repeat the last one."""
    E = group_sizes.shape[0]
    tiles = -(-rows // tile)
    V = tiles + E
    sizes = group_sizes.astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), _running(sizes),
                               jnp.full((1,), tiles * tile, jnp.int32)])
    lo, hi = offsets[:-1], offsets[1:]                 # E + 1 groups
    n = jnp.where(hi > lo, (hi - 1) // tile - lo // tile + 1, 0)
    upto = _running(n)
    count = upto[-1]
    at = jnp.minimum(jnp.arange(V, dtype=jnp.int32), count - 1)
    group = jnp.sum(at[:, None] >= upto[None, :], 1, dtype=jnp.int32)
    of_group = group[:, None] == jnp.arange(E + 1, dtype=jnp.int32)[None, :]
    # the group's first tile, less the visits before the group's own
    start = lo // tile - (upto - n)
    tile_of = at + jnp.sum(jnp.where(of_group, start[None, :], 0), 1)
    last = jnp.max(jnp.where(sizes > 0, jnp.arange(E, dtype=jnp.int32), 0))
    return group, jnp.minimum(group, last), tile_of, offsets, count.reshape(1)


def _dot(a, b):
    """a @ b accumulated in float32. bfloat16 operands go through the
    MXU as they are, whatever `jax_default_matmul_precision` says (a
    float32 matter; Mosaic refuses "highest" for bfloat16)."""
    precision = jax.lax.Precision.DEFAULT if a.dtype == jnp.bfloat16 else None
    return jnp.dot(a, b, precision=precision,
                   preferred_element_type=jnp.float32)


def _kernel(group_ref, weights_ref, tile_ref, offsets_ref, count_ref,
            x_ref, gate_ref, up_ref, down_ref, o_ref, act_ref, *, experts,
            tile):
    from jax.experimental import pallas as pl

    del weights_ref                      # the index maps' alone
    i = pl.program_id(0)

    @pl.when(i < count_ref[0])
    def _visit():
        g, t = group_ref[i], tile_ref[i]
        row = t * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
        mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
        # the first visit of a tile finds whatever the buffer held
        first = (i == 0) | (tile_ref[jnp.maximum(i - 1, 0)] != t)

        def store(y, at):
            """Rows of this group from y, the others as they were, into
            the lanes `at` of the tile."""
            kept = jnp.where(first, jnp.zeros(y.shape, o_ref.dtype),
                             o_ref[:, at])
            o_ref[:, at] = jnp.where(mine, y.astype(o_ref.dtype), kept)

        # the products by chunks of output lanes (the module docstring)
        h, F = gate_ref.shape
        fc = _LANES if F % _LANES == 0 else F
        hc = _CHUNK if h % _CHUNK == 0 else h

        @pl.when(g < experts)
        def _product():
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
                store(_dot(act, down_ref[:, at]), at)

            jax.lax.fori_loop(0, h // hc, h_step, None)

        @pl.when(g == experts)
        def _nobody():
            store(jnp.zeros(o_ref.shape, jnp.float32), slice(None))


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _call(xs, w_gate, w_up, w_down, group_sizes, tile, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, h = xs.shape
    E, _, F = w_gate.shape
    walk = _visits(group_sizes, R, tile)
    # two buffers of the three matrices and of the row tile in and out,
    # the activation, and room for the chunks' float32 values
    vmem = (2 * 3 * h * F * jnp.dtype(w_gate.dtype).itemsize
            + (4 * h + F) * tile * jnp.dtype(xs.dtype).itemsize + 8 * _MIB)
    rows = pl.BlockSpec((tile, h), lambda i, g, w, t, *_: (t[i], 0))
    return pl.pallas_call(
        functools.partial(_kernel, experts=E, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(walk[0].shape[0],),
            in_specs=[
                rows,
                pl.BlockSpec((None, h, F), lambda i, g, w, *_: (w[i], 0, 0)),
                pl.BlockSpec((None, h, F), lambda i, g, w, *_: (w[i], 0, 0)),
                pl.BlockSpec((None, F, h), lambda i, g, w, *_: (w[i], 0, 0)),
            ],
            out_specs=rows,
            scratch_shapes=[pltpu.VMEM((tile, F), xs.dtype)]),
        out_shape=jax.ShapeDtypeStruct((R, h), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(min(vmem, 110 * _MIB))),
        interpret=interpret,
        name="grouped_swiglu",
    )(*walk, xs, w_gate, w_up, w_down)


def grouped_swiglu(xs, w_gate, w_up, w_down, group_sizes, row_tile=None):
    """The grouped SwiGLU of rows sorted by expert.

    xs: (R, h), the rows of expert 0, then of expert 1, ...; group_sizes:
    (E,) integers, how many rows each expert has (their sum at most R);
    w_gate, w_up: (E, h, F), w_down: (E, F, h), in xs's type. Returns
    (R, h) in xs's type: for a row r of expert e `(silu(xs[r] w_gate[e])
    * (xs[r] w_up[e])) w_down[e]`, products accumulated in float32, the
    activation in float32 and rounded to xs's type before the down
    product; zero for a row in no group. An expert with no row is never
    read. row_tile: rows a visit computes (a multiple of 16); None takes
    `row_tile_for(R, E)`.

    Compiled by Mosaic on a TPU backend, interpreted on the CPU (a test
    facility), an error on any other backend: an interpreted kernel must
    not pass for the real one."""
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(
            "grouped_swiglu compiles for TPU (Mosaic) and interprets on "
            f"CPU for tests; the active backend is {platform!r}")
    tile = row_tile_for(xs.shape[0], w_gate.shape[0]) \
        if row_tile is None else int(row_tile)
    if tile % _MIN_TILE:
        raise ValueError(f"row_tile {tile} is no multiple of {_MIN_TILE}")
    return _call(xs, w_gate, w_up, w_down, group_sizes, tile,
                 platform == "cpu")
