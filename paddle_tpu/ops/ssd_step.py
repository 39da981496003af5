"""One decode step of a SCALAR-DECAY state-space recurrence (Mamba-2's SSD)
over the STATE BLOCKS of a paged pool: every slot's state read once,
updated and written once, in place.

    S = a S + outer(dt x, B);   y = S C        (a head; a = exp(dt A) a
                                                scalar a head, float32)

The state arena is `(layers, 1, num_blocks, heads, P, N)` float32 (a state
group's: serving/model.py), a slot's state of one layer ONE block of it,
`heads` tiles of (P, N) with the head's channels P in the sublanes and the
state's N in the lanes: 4 MB a slot a layer at 128 heads of 64 x 128. ONE
grid step a slot takes the slot's WHOLE block (4 MB in and 4 MB out,
double-buffered 16 MB of the 48 MB asked for), picked by the page table's
column through the scalar-prefetched ids, and the same block goes out
through the aliased output: XLA's gather-update-scatter reads and writes
the state three times where this reads and writes it once (6,700 us a
layer at 96 slots against 1,233; a second grid axis over pieces of 64 and
32 heads was slower and was taken out: PR 54's chip runs). A frozen slot is
sent scratch block 0 (serving/model.py's rule): what it writes there is
nobody's.

Inside, the step is its DMA: 1,233 us a layer at 96 slots where a kernel
that only moves the blocks takes 1,232 (PR 55's chip runs; 80% of 819
GB/s, which is what this chip's HBM gives a stream that reads and writes
in equal parts, however the block is cut into descriptors). The state's
update is float32 on the VPU, `state * decay + dx * B` in that order: the
decay a scalar a head read from SMEM and splatted, dt x a column a head of
a `(P, heads)` matrix XLA has transposed, broadcast along the lanes, B a
row broadcast down the sublanes. `y`'s contraction over N is the idle
MXU's: as many heads as fill 128 rows (two at P = 64) are ONE transposed
right operand `(g P, N)` under C, contracted N with N at `HIGHEST`
(float32 passes: a single bfloat16 pass reads 2e-3 off), and what comes
back is a ROW of `g P` lanes, the heads' `y` side by side: `y` leaves as
rows of `(heads / g, g P)`, which IS `(heads, P)`, and nothing is turned
round behind the call. (A lane sum a tile with a one-lane store a head
made the kernel 1,457 us: 1,024 XLU reductions and 1,024 masked stores a
slot beside two lane broadcasts a tile, which the DMA did not hide.)
Mosaic on a TPU backend, interpreted on the CPU (tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["ssd_step_blocks"]


def _kernel(layer_ref, ids_ref, decay_ref, dx_ref, bc_ref, state_ref,
            out_state_ref, y_ref, *, heads, group):
    """decay (S, heads) in SMEM; dx (P, heads): dt x a head a column; bc (2,
    N): B, C; y (rows, group P): `group` heads' y a row."""
    from jax.experimental import pallas as pl

    s = pl.program_id(0)
    b = bc_ref[0:1, :]
    c = jnp.broadcast_to(bc_ref[1:2, :], (8, bc_ref.shape[1]))
    for row, first in enumerate(range(0, heads, group)):
        tiles = []
        for h in range(first, min(first + group, heads)):
            Sn = state_ref[h] * decay_ref[s, h] + dx_ref[:, h:h + 1] * b
            out_state_ref[h] = Sn                          # (P, N)
            tiles.append(Sn)
        # (8, N) x (g P, N)^T: every row of the result is the heads' y
        y = jax.lax.dot_general(
            c, jnp.concatenate(tiles, 0), (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        y_ref[row:row + 1, 0:y.shape[1]] = y[0:1]


def _call(arena, layer, ids, decay, dx, bc, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s_dim = ids.shape[0]
    heads, P, N = arena.shape[3:]
    group = max(1, 128 // P)                 # heads a row of y
    rows = -(-heads // group)
    block = pl.BlockSpec((None, None, None, heads, P, N),
                         lambda s, lay, ids, _: (lay[0], 0, ids[s], 0, 0, 0))
    arena, y = pl.pallas_call(
        functools.partial(_kernel, heads=heads, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(s_dim,),
            in_specs=[pl.BlockSpec((None, P, heads), lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec((None, 2, N), lambda s, *_: (s, 0, 0)),
                      block],
            out_specs=[block,
                       pl.BlockSpec((None, rows, group * P),
                                    lambda s, *_: (s, 0, 0))]),
        out_shape=[jax.ShapeDtypeStruct(arena.shape, arena.dtype),
                   jax.ShapeDtypeStruct((s_dim, rows, group * P),
                                        jnp.float32)],
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=48 << 20),
        interpret=interpret,
        name="ssd_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), ids.astype(jnp.int32),
      decay, dx, bc, arena)
    # a last row that the heads do not fill holds lanes nobody wrote
    return y.reshape(s_dim, rows * group, P)[:, :heads], arena


def ssd_step_blocks(arena, layer, ids, done, x, dt, decay, B, C):
    """The recurrence one position on for every slot. arena (layers, 1,
    num_blocks, heads, P, N) float32; `layer` its plane; ids (S,) each
    slot's state block; done (S,) bool or None: a frozen slot reads and
    writes scratch block 0; x (S, heads, P), dt and decay = exp(dt A) (S,
    heads), B, C (S, N), all float32. Returns (y = S C (S, heads, P)
    float32, without the skip term `D x`; the arena, its input's own
    buffer)."""
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise RuntimeError("ssd_step_blocks compiles for TPU (Mosaic) and "
                           "interprets on CPU for tests; the active backend "
                           f"is {platform!r}")
    if arena.dtype != jnp.float32:
        raise ValueError(f"the state arena is float32, not {arena.dtype}")
    if done is not None:
        ids = jnp.where(done, 0, ids)
    f32 = jnp.float32
    x, dt, decay, B, C = (a.astype(f32) for a in (x, dt, decay, B, C))
    dx = (x * dt[..., None]).transpose(0, 2, 1)     # (S, P, heads)
    return _call(arena, layer, ids, decay, dx, jnp.stack([B, C], 1),
                 platform == "cpu")
