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
layer at 96 slots against 1,456; a second grid axis over pieces of 64 and
32 heads read 1,555 and 1,584 and was taken out: PR 54's chip runs). A
frozen slot is sent scratch block 0 (serving/model.py's rule): what it
writes there is nobody's.

Inside, what varies along a tile's sublanes (dt x, and the head's decay
repeated down them) comes as ONE matrix a slot, already
transposed by XLA to `(P, 2 heads)`: a column a head, broadcast along the
lanes. B and C, one pair for all heads (`n_groups` 1), are two rows,
broadcast down the sublanes. `y`'s contraction over N is a lane sum that
leaves a column a head: the columns go out as they are, `(P, heads)`, and
XLA turns the few kilobytes round. Nothing inside is transposed and the
MXU idles (an M = 1 product would idle its rows): the step is the state's
bytes. Mosaic on a TPU backend, interpreted on the CPU (tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["ssd_step_blocks"]


def _kernel(layer_ref, ids_ref, cols_ref, bc_ref, state_ref, out_state_ref,
            y_ref, *, heads):
    """cols (P, 2 heads): dt x a head a column, then the decay repeated
    down a column; bc (2, N): B, C."""
    b = bc_ref[0:1, :]
    c = bc_ref[1:2, :]
    for h in range(heads):
        dx = cols_ref[:, h:h + 1]                          # (P, 1)
        decay = cols_ref[:, heads + h:heads + h + 1]
        Sn = state_ref[h] * decay + dx * b                 # (P, N)
        out_state_ref[h] = Sn
        y_ref[:, h:h + 1] = jnp.sum(Sn * c, axis=1, keepdims=True)


def _call(arena, layer, ids, cols, bc, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s_dim = ids.shape[0]
    heads, P, N = arena.shape[3:]
    block = pl.BlockSpec((None, None, None, heads, P, N),
                         lambda s, lay, ids: (lay[0], 0, ids[s], 0, 0, 0))
    arena, y = pl.pallas_call(
        functools.partial(_kernel, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s_dim,),
            in_specs=[pl.BlockSpec((None, P, 2 * heads),
                                   lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec((None, 2, N), lambda s, *_: (s, 0, 0)),
                      block],
            out_specs=[block,
                       pl.BlockSpec((None, P, heads),
                                    lambda s, *_: (s, 0, 0))]),
        out_shape=[jax.ShapeDtypeStruct(arena.shape, arena.dtype),
                   jax.ShapeDtypeStruct((s_dim, P, heads), jnp.float32)],
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=48 << 20),
        interpret=interpret,
        name="ssd_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), ids.astype(jnp.int32),
      cols, bc, arena)
    return y, arena


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
    cols = jnp.concatenate([                   # (S, heads, P) -> (S, P, heads)
        (x * dt[..., None]).transpose(0, 2, 1),
        jnp.broadcast_to(decay[..., None], x.shape).transpose(0, 2, 1)], -1)
    y, arena = _call(arena, layer, ids, cols, jnp.stack([B, C], 1),
                     platform == "cpu")
    return y.transpose(0, 2, 1), arena
