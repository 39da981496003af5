"""The flash forward's walk (ops/flash_attention.py): the list of (query
tile, KV tile) visits as a pure function, and the kernel that runs it,
interpreted on the CPU, against `mha_reference`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import flash_attention as fa
from paddle_tpu.ops.flash_attention import (causal_rows_tiles,
                                            flash_causal_rows, mha_reference)


def _attended(rows, window, kv_len=None):
    """(rows, rows) bool: row i attends column j."""
    i = np.arange(rows)
    mask = i[None, :] <= i[:, None]
    if window is not None:
        mask &= i[:, None] - i[None, :] < window
    if kv_len is not None:
        mask &= i[None, :] < kv_len
    return mask


def _tiles(mask, tile):
    """(nq, nk) bool: some (row, column) of the tile is set."""
    n = mask.shape[0] // tile
    return mask.reshape(n, tile, n, tile).any((1, 3))


WALKS = [(rows, tile, window, length)
         for rows, tile in ((2048, 512), (1536, 512), (1024, 128),
                            (4096, 1024))
         for window in (None, 128, 700, 1024)
         for length in (None, 1, tile - 1, tile, tile + 1, rows - tile,
                        rows - 3, rows)]


@pytest.mark.parametrize("rows,tile,window,length", WALKS)
def test_the_walk_visits_the_tiles_that_hold_work_once(rows, tile, window,
                                                       length):
    """Causal, a band, and a real length on and off a tile edge: every
    (query tile, KV tile) that holds a needed (row, column) is visited
    exactly once and no other, a query tile's visits are consecutive with
    the diagonal one last, a query tile past the length has one visit
    that fetches nothing, and the steps past the count repeat the last
    visit."""
    nq = rows // tile
    walk = fa._walk(nq, tile, tile, True, window, rows)
    if length is None:
        qt, kt, bits = walk[:3]
        count = len(qt)
    else:
        qt, kt, bits, count = (np.asarray(a) for a in fa._walk_to(
            jnp.int32(length), walk, tile, tile))
    real = rows if length is None else length
    attended = _attended(rows, window)
    needed = attended & (np.arange(rows) < real)[:, None]
    held = (bits[:count] & fa._EMPTY) == 0
    visits = list(zip(qt[:count][held].tolist(), kt[:count][held].tolist()))
    assert len(set(visits)) == len(visits)
    assert set(visits) == set(zip(*np.nonzero(_tiles(needed, tile))))
    # a query tile at a time, its KV tiles in order, first and last marked
    assert visits == sorted(visits)
    for i, (j, kk) in enumerate(visits):
        first = i == 0 or visits[i - 1][0] != j
        last = i + 1 == len(visits) or visits[i + 1][0] != j
        assert bool(bits[i] & fa._FIRST) == first
        assert bool(bits[i] & fa._LAST) == last
        if last:
            assert kk == j                                 # the diagonal
    # the tiles of padding: one visit each, in order, nothing fetched
    live = -(-real // tile)
    empty = list(zip(qt[:count][~held].tolist(), kt[:count][~held].tolist()))
    assert [j for j, _ in empty] == list(range(live, nq))
    assert all(kk == visits[-1][1] for _, kk in empty)
    assert (bits[:count][~held] == fa._EMPTY).all()
    assert count == len(visits) + nq - live
    assert (qt[count:] == qt[count - 1]).all()
    assert (kt[count:] == kt[count - 1]).all()
    if fa._causal_rows_blocks(rows, window)[0] == tile:
        assert causal_rows_tiles(rows, length, window)[0] == len(visits)


@pytest.mark.parametrize("causal,window,sq,sk,bq,bk", [
    (False, None, 1024, 1024, 512, 512), (False, None, 384, 640, 128, 128),
    (True, None, 640, 640, 128, 128), (True, None, 1024, 1000, 512, 128),
    (True, 300, 1280, 1280, 128, 128)])
def test_the_static_walk_of_any_tiling(causal, window, sq, sk, bq, bk):
    """Training's walks (no length): not causal, keys shorter than their
    padded tiles, unequal tiles: the visits are the tiles that hold an
    attended (row, column) of the keys' real length, a query tile's first
    and last marked."""
    skp = -(-sk // bk) * bk
    qt, kt, bits, upto = fa._walk(sq // bq, bq, bk, causal, window, sk)
    i, j = np.arange(sq)[:, None], np.arange(skp)[None, :]
    attended = (j < sk) & np.ones((sq, 1), bool)
    if causal:
        attended &= j <= i
    if window is not None:
        attended &= i - j < window
    cut = attended.reshape(sq // bq, bq, skp // bk, bk)
    assert set(zip(qt.tolist(), kt.tolist())) == set(
        zip(*np.nonzero(cut.any((1, 3)))))
    assert len(qt) == upto[-1] == cut.any((1, 3)).sum()
    assert (np.diff(qt) >= 0).all()
    first = np.concatenate([[True], np.diff(qt) > 0])
    assert ((bits & fa._FIRST) != 0).tolist() == first.tolist()
    assert ((bits & fa._LAST) != 0).tolist() == np.roll(first, -1).tolist()


# the cells' prompts in their buckets, a head
XING = [(2048, 2048), (4096, 3072), (4096, 4096), (8192, 6144), (8192, 8192),
        (12288, 10240), (12288, 12288), (16384, 15360)]
MOONLIGHT = [(2048, 1024), (2048, 1536), (2048, 2048), (4096, 3072),
             (4096, 4096), (6144, 5120), (6144, 6144), (8192, 7168)]
MELLUM = [(512, 256), (512, 512), (1024, 768), (1024, 1024), (4096, 3072),
          (4096, 4096), (8192, 8192), (16384, 15360)]


@pytest.mark.parametrize("cycle,window,visited,held", [
    (XING, None, 377, 446), (MOONLIGHT, None, 237, 294),
    (MELLUM, None, 217, 248), (MELLUM, 1024, 168 + 4, 180 + 4),
    ([(4096, 3072)], None, 21, 36), ([(6144, 5120)], None, 55, 78),
    ([(8192, 6144)], None, 21, 36), ([(12288, 10240)], None, 55, 78),
    ([(16384, 15360)], None, 120, 136), ([(16384, 15360)], 1024, 87, 93),
    ([(16384, 16384)], 1024, 93, 93), ([(1024, 17)], None, 1, 1),
    ([(768, 768)], 1024, 1, 1)])
def test_the_tiles_a_cells_prompts_visit(cycle, window, visited, held):
    """`causal_rows_tiles`, the host's count for `engine.stats()`, in the
    kernel's own tiles: a bucket up to 1,024 rows is one tile whatever its
    length, a triangle from 8,192 rows is walked in tiles of 1,024 (the
    band and the shorter triangles in tiles of 512)."""
    got = [causal_rows_tiles(b, n, window) for b, n in cycle]
    assert (sum(g[0] for g in got), sum(g[1] for g in got)) == (visited, held)


def _reference(q, k, v, scale, window):
    """q (rows, heads, d), k (rows, kv_heads, d), v (rows, kv_heads, dv)."""
    rows, group = q.shape[0], q.shape[1] // k.shape[1]
    d, dv = q.shape[-1], v.shape[-1]
    bias = jnp.where(_attended(rows, window), 0.0, -1e30)[None, None]
    # mha_reference wants equal widths: zero-extend v and cut the answer
    vz = jnp.pad(v, ((0, 0), (0, 0), (0, d - dv)))
    return mha_reference(q[None], jnp.repeat(k, group, 1)[None],
                         jnp.repeat(vz, group, 1)[None], bias=bias,
                         sm_scale=scale)[0][..., :dv]


def _operands(rows, heads, kv_heads, d, dv):
    ks = jax.random.split(jax.random.PRNGKey(rows + heads + d), 3)
    return (jax.random.normal(ks[0], (rows, heads, d)),
            jax.random.normal(ks[1], (rows, kv_heads, d)),
            jax.random.normal(ks[2], (rows, kv_heads, dv)))


KERNEL = [
    # rows, heads, kv_heads, d, dv, window, length, tolerance
    (2048, 2, 2, 24, 16, None, None, 2e-5),     # unequal widths
    (2048, 2, 2, 24, 16, None, 1300, 2e-5),
    (1536, 4, 2, 32, 32, None, 1024, 2e-6),     # group 2 x 2, a tile edge
    (1536, 4, 2, 32, 32, None, 1025, 2e-6),
    (2048, 4, 2, 32, 32, 1024, 1999, 2e-6),     # a band and a length
    (2048, 4, 2, 32, 32, 700, 513, 2e-6),
    (1536, 4, 1, 32, 32, 128, 1, 2e-6),
    (256, 4, 2, 32, 32, None, 100, 2e-6),       # one tile a head
    (768, 2, 2, 24, 16, 100, 700, 2e-5),
    (2048, 2, 2, 32, 32, None, 0, 2e-6),        # nothing real: all zeros
]


@pytest.mark.parametrize("rows,heads,kv_heads,d,dv,window,length,atol",
                         KERNEL)
def test_the_walked_forward_against_mha_reference(rows, heads, kv_heads, d,
                                                  dv, window, length, atol):
    """Real rows agree with the reference; rows at or past `length` are
    ZERO, whatever q, k and v hold there."""
    q, k, v = _operands(rows, heads, kv_heads, d, dv)
    real = rows if length is None else length
    if length is not None:
        # what a bucket's padding may hold must not reach the result
        q, k, v = (x.at[real:].set(1e4) for x in (q, k, v))
    got = flash_causal_rows(q, k, v, 0.2, window=window,
                            length=None if length is None
                            else jnp.int32(length))
    want = _reference(q, k, v, 0.2, window)
    assert got.shape == (rows, heads, dv)
    if real:
        assert float(jnp.abs(got[:real] - want[:real]).max()) <= atol
    assert not np.asarray(got[real:]).any()


@pytest.mark.parametrize("length", [None, 2048, 1500, 1024, 3])
def test_large_tiles_in_chunks(length, monkeypatch):
    """A long triangle's tiles of 1,024, each taken in chunks of 512
    columns (here from 2,048 rows, as the chip does from 8,192): the same
    result as the reference and, to a rounding, as tiles of 512."""
    q, k, v = _operands(2048, 2, 2, 24, 16)
    n = None if length is None else jnp.int32(length)
    small = flash_causal_rows(q, k, v, 0.2, length=n)
    monkeypatch.setattr(fa, "_LARGE_TILE_ROWS", 2048)
    jax.clear_caches()
    try:
        assert fa._causal_rows_blocks(2048) == (1024, 1024)
        assert "grid=(2, 3)" in str(jax.make_jaxpr(
            lambda q, k, v: flash_causal_rows(q, k, v, 0.2))(q, k, v))
        large = flash_causal_rows(q, k, v, 0.2, length=n)
    finally:
        jax.clear_caches()
    real = 2048 if length is None else length
    want = _reference(q, k, v, 0.2, None)
    assert float(jnp.abs(large[:real] - want[:real]).max()) <= 2e-5
    assert float(jnp.abs(large - small).max()) <= 2e-6
    assert not np.asarray(large[real:]).any()


@pytest.mark.parametrize("rows,window", [(2048, None), (2048, 700),
                                         (512, None), (1536, 128)])
def test_a_full_length_is_the_static_walk_bit_for_bit(rows, window):
    """`length=None` (a compile-time walk) and `length=rows` (the computed
    one) give the same bits."""
    q, k, v = _operands(rows, 4, 2, 32, 32)
    static = flash_causal_rows(q, k, v, 0.2, window=window)
    traced = flash_causal_rows(q, k, v, 0.2, window=window,
                               length=jnp.int32(rows))
    np.testing.assert_array_equal(np.asarray(static), np.asarray(traced))


@pytest.mark.parametrize("length", [1, 512, 700, 1536])
def test_lse_of_a_length(length):
    """The forward's second result: a real row's log-sum-exp is the static
    walk's, a row past the length reads _NEG_INF (no mass)."""
    q, k, v = (x.swapaxes(0, 1) for x in _operands(1536, 2, 2, 32, 32))
    _, want = fa._flash_call(q, k, v, None, True, 0.2, True)
    o, got = fa._flash_call(q, k, v, None, True, 0.2, True,
                            length=jnp.int32(length))
    np.testing.assert_array_equal(np.asarray(got[:, :length]),
                                  np.asarray(want[:, :length]))
    assert (np.asarray(got[:, length:]) == fa._NEG_INF).all()
    assert not np.asarray(o[:, length:]).any()


@pytest.mark.parametrize("what", ["window", "length"])
def test_a_window_or_a_length_is_causal_self_attention(what):
    q = jnp.zeros((2, 1024, 32))
    kw = {"window": 100} if what == "window" else {"length": jnp.int32(5)}
    with pytest.raises(ValueError, match="causal self-attention"):
        fa._flash_call(q, q, q, None, False, 0.2, True, **kw)
    with pytest.raises(ValueError, match="causal self-attention"):
        fa._flash_call(q, q[:, :512], q[:, :512], None, True, 0.2, True, **kw)
