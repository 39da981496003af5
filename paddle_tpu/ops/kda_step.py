"""One decode step of the delta-rule recurrence (Kimi Delta Attention) over
the STATE BLOCKS of a paged pool: every slot's state read once, updated
and written once, in place.

    S' = exp(g)[:, None] * S;  S = S' + outer(k, beta (v - S'^T k));
    o = S^T q                                        (a head, float32)

The state arena is `(layers, 1, num_blocks, heads, dk, dv)` float32 (a
state group's: serving/model.py), a slot's state of one layer ONE block of
it. The grid is the slots; the block that comes in is
`arena[layer, 0, ids[slot]]`, picked by the page
table's column through the scalar-prefetched ids, and the same block goes
out through the aliased output: XLA's gather-update-scatter reads and
writes the state three times where this reads and writes it once. A
frozen slot is sent scratch block 0 (serving/model.py's rule): what it
writes there is nobody's.

Inside, a head is a (dk, dv) tile with the KEY channels in the sublanes.
What varies by key channel (q, k, exp g, beta k) comes as ONE matrix a
slot, `(4 heads, dk)`, a vector a row (128 rows of 128 at the published
widths: no lane of it is padding in HBM, where a `(dk, 1)` column a vector
would be padded 128 times over), is transposed ONCE a slot on the XLU and
read a column a head, broadcast along the lanes; what varies by value
channel (beta v, o) is rows. The two contractions over the key axis are
sublane sums on the VPU (an M = 1 product would idle the MXU's rows).
Mosaic on a TPU backend, interpreted on the CPU (tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["kda_step_blocks"]


def _kernel(layer_ref, ids_ref, keyed_ref, rows_ref, state_ref, out_state_ref,
            o_ref, *, heads):
    """keyed (4 heads, dk): q, k, exp(g), beta k, a head a row; rows (heads,
    dv): beta v."""
    cols = keyed_ref[...].T                                # (dk, 4 heads)
    for h in range(heads):
        q = cols[:, h:h + 1]                               # (dk, 1)
        k = cols[:, heads + h:heads + h + 1]
        decay = cols[:, 2 * heads + h:2 * heads + h + 1]
        kb = cols[:, 3 * heads + h:3 * heads + h + 1]      # beta k
        Sd = state_ref[h] * decay                          # (dk, dv)
        # beta (v - S'^T k) = beta v - S'^T (beta k)
        u = rows_ref[h:h + 1, :] - jnp.sum(Sd * kb, axis=0, keepdims=True)
        Sn = Sd + k * u
        out_state_ref[h] = Sn
        o_ref[h:h + 1, :] = jnp.sum(Sn * q, axis=0, keepdims=True)


def _call(arena, layer, ids, keyed, rows, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s_dim = ids.shape[0]
    heads, dk, dv = arena.shape[3:]
    # a slot a grid step: its whole state, 2 MB at the published widths,
    # in and out and double-buffered 8 MB
    block = pl.BlockSpec((None, None, None, heads, dk, dv),
                         lambda s, lay, ids: (lay[0], 0, ids[s], 0, 0, 0))
    arena, o = pl.pallas_call(
        functools.partial(_kernel, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s_dim,),
            in_specs=[pl.BlockSpec((None, 4 * heads, dk),
                                   lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec((None, heads, dv),
                                   lambda s, *_: (s, 0, 0)),
                      block],
            out_specs=[block,
                       pl.BlockSpec((None, heads, dv),
                                    lambda s, *_: (s, 0, 0))]),
        out_shape=[jax.ShapeDtypeStruct(arena.shape, arena.dtype),
                   jax.ShapeDtypeStruct((s_dim, heads, dv), jnp.float32)],
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=48 << 20),
        interpret=interpret,
        name="kda_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), ids.astype(jnp.int32),
      keyed, rows, arena)
    return o, arena


def kda_step_blocks(arena, layer, ids, done, q, k, v, g, beta):
    """The recurrence one position on for every slot. arena (layers, 1,
    num_blocks, heads, dk, dv) float32; `layer` its plane; ids (S,) each
    slot's state block; done (S,) bool or None: a frozen slot reads and
    writes scratch block 0; q, k, g (S, heads, dk) float32, v (S, heads,
    dv), beta (S, heads). Returns (o (S, heads, dv) float32, the arena,
    its input's own buffer)."""
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise RuntimeError("kda_step_blocks compiles for TPU (Mosaic) and "
                           "interprets on CPU for tests; the active backend "
                           f"is {platform!r}")
    if arena.dtype != jnp.float32:
        raise ValueError(f"the state arena is float32, not {arena.dtype}")
    if done is not None:
        ids = jnp.where(done, 0, ids)
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    # what varies by key channel, a vector a row: (S, 4 heads, dk)
    keyed = jnp.concatenate([q, k, jnp.exp(g), k * beta[..., None]], 1)
    return _call(arena, layer, ids, keyed, v * beta[..., None],
                 platform == "cpu")
