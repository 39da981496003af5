"""A slot's RECURRENT STATE and its CONVOLUTION HISTORY as blocks of two
state groups: what every block with a fixed-size state a slot shares
(models/kimi_linear.py's and models/qwen3_next.py's delta-rule layers,
models/granite_hybrid.py's state-space layers; the cells
kimi-linear-longgen-offline, granite-h-shortchat-offline and
qwen3-next-longmix-offline).

Such a layer keeps NO rows a token. What a slot carries of it is one block
of a float32 group (the state, `STATE`) and one block of a group of the
arena's type (the last K - 1 pre-activation rows that its causal
depthwise convolution of width K reads, `CONV`), each one column of the
page table behind the primary group's (serving/model.py
`CacheSpec.state`). Two groups and not one, because a block has one type
and one shape. This module is the plumbing between those blocks and a
mixer's own arithmetic:

  * `specs`: the two `CacheSpec`s; `history_shape`: a history block in
    whole lanes; `block_ids`: a page row's or a page table's state columns;
    `by_name` / `in_order`: the engine's tuple of arenas as a dict and
    back;
  * a PROMPT: `conv_prompt` (the convolution's sums over a padded prompt
    and the history AT `real_len`), `write_block` (the slot's block of one
    layer written whole, never read);
  * a STEP: `conv_step` (every slot's history read, convolved with the
    new row and moved one row on), `read_blocks` / `write_blocks` (XLA's
    gather and scatter of the state where no kernel runs), `state_block`
    (a frozen slot's write goes to scratch block 0).

What a mixer brings itself: its projections, activation, recurrence (one
position and chunked; the delta rule's, which two mixers share, is
models/_delta.py's), gate and kernels.

Imports no model and, at module level, no jax.
"""

from __future__ import annotations

from ..serving import pages as _pages
from ..serving.model import CacheSpec

__all__ = ["STATE", "CONV", "specs", "history_shape", "block_ids", "by_name",
           "in_order", "state_block", "conv_rows", "conv_prompt",
           "conv_step", "write_block", "read_blocks", "write_blocks"]

STATE, CONV = "state", "conv"


def history_shape(rows, width):
    """A slot's convolution history of one layer, `rows` rows of `width`
    values, as the block stores it: whole lanes where the values fill
    them (no row of the block is padding), else the rows as they are."""
    if (rows * width) % _pages.LANES == 0:
        return (1, rows * width // _pages.LANES, _pages.LANES)
    return (1, rows, width)


def specs(layers, heads, head_dim, state_shape, state_dtype, conv_rows,
          conv_width, names=(STATE, CONV)):
    """The two state groups of `layers` recurrent layers: the state
    (`state_shape` values of `state_dtype` a slot a layer) and the
    convolution's history (`conv_rows` rows of `conv_width`, the arena's
    type)."""
    return (CacheSpec(layers, heads, head_dim, name=names[0], state=True,
                      dtype=state_dtype, state_shape=tuple(state_shape)),
            CacheSpec(layers, 1, conv_width, name=names[1], state=True,
                      state_shape=history_shape(conv_rows, conv_width)))


def block_ids(table, columns):
    """Each state group's block of a page row (P,) -> scalars, or of a
    page table (S, P) -> (S,): `columns` the groups' slices
    (serving.model.group_columns), one column each."""
    if table.ndim == 1:
        return tuple(table[c][0] for c in columns)
    return tuple(table[:, c][:, 0] for c in columns)


def by_name(names, arena):
    """{group: its arena} of the tuple the engine threads."""
    return dict(zip(names, arena))


def in_order(names, arenas):
    return tuple(arenas[name] for name in names)


def state_block(ids, done):
    """Where a slot's state block is written: its own, or scratch block 0
    for a frozen slot."""
    import jax.numpy as jnp
    return ids if done is None else jnp.where(done, 0, ids)


def conv_rows(w, padded, T):
    """The causal depthwise convolution's sum over `padded` (K - 1 + T,
    W), the K - 1 rows of history first, filters w (K, W): (T, W)
    float32."""
    import jax.numpy as jnp
    w = w.astype(jnp.float32)
    x = padded.astype(jnp.float32)
    out = w[0] * x[:T]
    for i in range(1, w.shape[0]):
        out = out + w[i] * x[i:i + T]
    return out


def conv_prompt(x, w, real_len):
    """A prompt's convolution from zeros before row 0: x (B, W) the
    pre-activation rows, `real_len` of them real, filters w (K, W).
    Returns (the sums (B, W) float32, the history AT `real_len`: rows
    real_len - (K - 1) .. real_len - 1, zeros before row 0)."""
    import jax
    import jax.numpy as jnp
    K = w.shape[0]
    padded = jnp.pad(x, ((K - 1, 0), (0, 0)))
    hist = jax.lax.dynamic_slice_in_dim(padded, real_len, K - 1, 0)
    return conv_rows(w, padded, x.shape[0]), hist


def conv_step(conv, lg, ids, done, row, w):
    """A step's convolution: every slot's history, block `ids` (S,) of
    layer `lg` of the arena `conv`, read, the new pre-activation row (S,
    W) put behind it, the window summed under the filters w (K, W), and
    the history moved one row on (a frozen slot's to scratch). Returns
    (the sums (S, W) float32, the arena)."""
    import jax.numpy as jnp
    s_dim, K = row.shape[0], w.shape[0]
    hist = conv[lg, 0, ids].reshape(s_dim, K - 1, -1)
    window = jnp.concatenate([hist, row[:, None].astype(hist.dtype)], 1)
    w = w.astype(jnp.float32)
    summed = jnp.sum(window.astype(jnp.float32) * w[None], 1)
    conv = conv.at[lg, 0, state_block(ids, done)].set(
        window[:, 1:].reshape((s_dim,) + conv.shape[3:]))
    return summed, conv


def write_block(arena, lg, block, value):
    """A prompt's end in the slot's block of layer `lg`: written whole,
    in the block's own shape and the arena's type. The arena is viewed as
    ONE run of blocks (its leading axes merged: a bitcast in any tiled
    layout, whatever the block's own shape) and the block put in by one
    `dynamic_update_slice`: an `.at[lg, 0, block].set` of a (heads, P, N)
    state that a product left in another order made the TPU's compiler copy
    a whole 3.4 GB arena into that order and back around the one block's
    write (PR 54, a 256-row bucket whose one-chunk scan is unrolled), and a
    view as rows of the last axis copied an arena whose blocks are no whole
    number of sublane tiles (198 rows of 128)."""
    import jax
    shape = arena.shape[3:]
    blocks = jax.lax.dynamic_update_slice(
        arena.reshape((-1,) + shape),
        value.astype(arena.dtype).reshape((1,) + shape),
        (lg * arena.shape[2] + block,) + (0,) * len(shape))
    return blocks.reshape(arena.shape)


def read_blocks(arena, lg, ids):
    """Every slot's state block (S,) + block shape, float32 (XLA's
    gather: the CPU's path and the kernels' oracle)."""
    import jax.numpy as jnp
    return arena[lg, 0, ids].astype(jnp.float32)


def write_blocks(arena, lg, ids, done, values):
    """XLA's scatter of every slot's new state (a frozen slot's to
    scratch), in the arena's type."""
    return arena.at[lg, 0, state_block(ids, done)].set(
        values.astype(arena.dtype))
