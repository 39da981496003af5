"""The grouped SwiGLU kernel (ops/grouped_swiglu.py), interpreted on the
CPU at tiny widths, against a float32 loop over the experts in numpy (the
reference's way: benchmarks/reference/moonlight_ref.py computes every
expert apart), and compiled at Moonlight's widths for a described v5e.

Tolerance: both sides are float32 (conftest sets the highest matmul
precision) and differ by the order of a 48- or 64-term sum of products of
magnitude under 1: 1e-5 is a hundred roundings, and a row computed with
another expert's weights differs by 0.1 and more."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401
from paddle_tpu.ops import grouped_swiglu as gs

E, H, F = 6, 64, 48
ATOL = 1e-5


def _weights(seed):
    rng = np.random.default_rng(seed)
    return (rng.normal(0, 0.2, (E, H, F)).astype(np.float32),
            rng.normal(0, 0.2, (E, H, F)).astype(np.float32),
            rng.normal(0, 0.2, (E, F, H)).astype(np.float32))


def _expert_loop(xs, w_gate, w_up, w_down, sizes):
    out = np.zeros_like(xs)
    start = 0
    for e, n in enumerate(sizes):
        x = xs[start:start + n]
        g = x @ w_gate[e]
        out[start:start + n] = (g / (1.0 + np.exp(-g)) * (x @ w_up[e])) \
            @ w_down[e]
        start += n
    return out


# (rows, rows of each expert, the row tiles to run)
CASES = {
    "an_expert_with_no_row": (24, [3, 0, 5, 1, 0, 7], [16]),
    "an_expert_with_every_row": (48, [0, 0, 48, 0, 0, 0], [16]),
    "rows_in_no_group_come_back_zero": (64, [2, 1, 0, 4, 3, 1], [16]),
    "no_row_in_any_group": (20, [0] * E, [16]),
    "a_row_count_that_is_no_multiple_of_the_tile": (37, [9, 4, 0, 11, 6, 7],
                                                    [16]),
    "a_group_boundary_inside_a_tile": (32, [5, 6, 5, 6, 5, 5], [16]),
    "few_rows_and_many_rows_tilings_agree": (200, [40, 0, 71, 3, 60, 20],
                                             [16, 64, 128]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_grouped_swiglu_against_the_expert_loop(case):
    rows, sizes, tiles = CASES[case]
    w_gate, w_up, w_down = _weights(1)
    xs = np.random.default_rng(2).normal(0, 1, (rows, H)).astype(np.float32)
    want = _expert_loop(xs, w_gate, w_up, w_down, sizes)
    # an expert with no row is never read: its weights are poison
    for e, n in enumerate(sizes):
        if n == 0:
            w_gate[e] = w_up[e] = w_down[e] = np.nan
    got = [np.asarray(gs.grouped_swiglu(
        jnp.asarray(xs), jnp.asarray(w_gate), jnp.asarray(w_up),
        jnp.asarray(w_down), jnp.asarray(sizes, jnp.int32), row_tile=tile))
        for tile in tiles]
    for y in got:
        np.testing.assert_allclose(y, want, atol=ATOL)
        assert not y[sum(sizes):].any()          # nobody's rows: zero
    for y in got[1:]:
        np.testing.assert_allclose(y, got[0], atol=ATOL)


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_walk_visits_every_shared_pair_once_and_no_untouched_expert(
        case):
    """The scalar-prefetch walk itself: the (group, tile) pairs are
    exactly those that share a row, in the rows' order; the weights a
    visit names are a touched expert's (so an untouched one costs no
    DMA: the block index never names it), and change only when the
    group does."""
    rows, sizes, tiles = CASES[case]
    for tile in tiles:
        group, weights, tile_of, offsets, count = (
            np.asarray(a) for a in gs._visits(jnp.asarray(sizes, jnp.int32),
                                              rows, tile))
        n_tiles = -(-rows // tile)
        assert group.shape == (n_tiles + E,)
        bounds = np.concatenate([[0], np.cumsum(sizes), [n_tiles * tile]])
        np.testing.assert_array_equal(offsets, bounds)
        want = [(g, t) for g in range(E + 1) for t in range(n_tiles)
                if max(bounds[g], t * tile) < min(bounds[g + 1],
                                                  (t + 1) * tile)]
        n = int(count[0])
        assert list(zip(group[:n], tile_of[:n])) == want
        # the visits past the count repeat the last: nothing is fetched
        assert (group[n:] == group[n - 1]).all()
        assert (tile_of[n:] == tile_of[n - 1]).all()
        touched = [e for e in range(E) if sizes[e]]
        if touched:
            assert set(weights) <= set(touched)
            assert (weights[:n][group[:n] < E] == group[:n][group[:n] < E]
                    ).all()
            assert (weights[group == E] == touched[-1]).all()


@pytest.mark.parametrize("rows,groups,tile", [
    (192, 64, 16),        # a decode step: 32 slots x 6 experts a token
    (24, 64, 16),         # a chat-sized step
    (2048 * 6, 64, 128),  # the smallest prompt bucket
    (8192 * 6, 64, 128),  # the largest
    (24, 8, 16), (400, 8, 64),
])
def test_the_row_tile_follows_the_static_row_count(rows, groups, tile):
    assert gs.row_tile_for(rows, groups) == tile


def test_a_backend_that_is_neither_is_an_error(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="grouped_swiglu compiles for TPU"):
        gs.grouped_swiglu(jnp.zeros((16, H)), *map(jnp.asarray, _weights(0)),
                          jnp.zeros((E,), jnp.int32))


# -- compiled for the chip, without the chip ----------------------------------
# Moonlight's widths, both regimes: what interpret mode cannot refuse (a
# slice off the tiling, more VMEM than the limit asked for). The topology
# is described inside a fixture, never at import (one process may hold
# libtpu; see the on-chip-measurement guide).

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("rows", [192, 2048 * 6, 8192 * 6])
def test_compiles_at_moonlights_widths_for_a_described_v5e(one_chip, rows):
    experts, h, f = 64, 2048, 1408

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = gs._call.lower(
        shape(rows, h), shape(experts, h, f), shape(experts, h, f),
        shape(experts, f, h), shape(experts, dtype=jnp.int32),
        tile=gs.row_tile_for(rows, experts), interpret=False).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    # the weights go in where they lie: nothing of their size is made
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20
