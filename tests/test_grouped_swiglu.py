"""The grouped SwiGLU kernel and its layout (ops/grouped_swiglu.py):
positions by counting against a stable sort, the kernel interpreted on
the CPU at tiny widths against a float32 loop over the experts in numpy
(the reference's way: benchmarks/reference/moonlight_ref.py computes
every expert apart), and compiled at the three expert models' widths for
a described v5e.

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


def _starts(sizes, tile):
    """Where each group starts: the groups before it, whole tiles each."""
    whole = -(-np.asarray(sizes) // tile) * tile
    return np.cumsum(whole) - whole


def _laid_out(rows, sizes, tile, fill):
    """(xs in the kernel's layout, the rows' places): `rows` (sum of
    sizes, H) sorted by expert go to their groups' tiles; every other
    row of the buffer holds `fill`."""
    xs = np.full((gs.padded_rows(len(rows), E, tile), H), fill, np.float32)
    place = np.concatenate(
        [start + np.arange(n) for start, n in zip(_starts(sizes, tile), sizes)]
    ).astype(np.int64)
    xs[place] = rows
    return xs, place


def _expert_loop(rows, w_gate, w_up, w_down, sizes):
    out = np.zeros_like(rows)
    start = 0
    for e, n in enumerate(sizes):
        x = rows[start:start + n]
        g = x @ w_gate[e]
        out[start:start + n] = (g / (1.0 + np.exp(-g)) * (x @ w_up[e])) \
            @ w_down[e]
        start += n
    return out


# (routed rows, rows of each expert, the row tiles to run)
CASES = {
    "an_expert_with_no_row": (16, [3, 0, 5, 1, 0, 7], [16]),
    "an_expert_with_every_row": (48, [0, 0, 48, 0, 0, 0], [16]),
    "rows_past_a_groups_end_are_poison": (11, [2, 1, 0, 4, 3, 1], [16]),
    "no_row_in_any_group": (0, [0] * E, [16]),
    "a_row_count_that_is_no_multiple_of_the_tile": (37, [9, 4, 0, 11, 6, 7],
                                                    [16]),
    "a_group_of_several_tiles_and_a_part": (70, [5, 38, 5, 6, 11, 5], [16]),
    "few_rows_and_many_rows_tilings_agree": (194, [40, 0, 71, 3, 60, 20],
                                             [16, 64, 128]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_grouped_swiglu_against_the_expert_loop(case):
    n, sizes, tiles = CASES[case]
    assert n == sum(sizes)
    w_gate, w_up, w_down = _weights(1)
    rows = np.random.default_rng(2).normal(0, 1, (n, H)).astype(np.float32)
    want = _expert_loop(rows, w_gate, w_up, w_down, sizes)
    # an expert with no row is never read: its weights are poison; and so
    # is every row of the buffer that is nobody's (a product's row reads
    # its own row alone)
    for e, size in enumerate(sizes):
        if size == 0:
            w_gate[e] = w_up[e] = w_down[e] = np.nan
    got = []
    for tile in tiles:
        xs, place = _laid_out(rows, sizes, tile, np.nan)
        y = np.asarray(gs.grouped_swiglu(
            jnp.asarray(xs), jnp.asarray(w_gate), jnp.asarray(w_up),
            jnp.asarray(w_down), jnp.asarray(sizes, jnp.int32), tile))
        assert y.shape == xs.shape
        got.append(y[place])
    for y in got:
        np.testing.assert_allclose(y, want, atol=ATOL)
    for y in got[1:]:
        np.testing.assert_allclose(y, got[0], atol=ATOL)


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_walk_visits_whole_tiles_of_one_expert_and_no_untouched_expert(
        case):
    """The scalar-prefetch walk itself: visit i is tile i, every row of
    it inside ONE group's whole tiles; the visits number the sum of the
    groups' ceilings; the weights a visit names are a touched expert's
    (so an untouched one costs no DMA: the block index never names it)
    and change only when the group does; the steps past the count repeat
    the last visit."""
    n, sizes, tiles = CASES[case]
    for tile in tiles:
        steps = gs.padded_rows(n, E, tile) // tile
        expert, tile_of, count = (
            np.asarray(a) for a in gs._visits(jnp.asarray(sizes, jnp.int32),
                                              tile, steps))
        assert expert.shape == tile_of.shape == (steps,)
        ceilings = [-(-size // tile) for size in sizes]
        v = int(count[0])
        assert v == sum(ceilings) <= steps
        np.testing.assert_array_equal(tile_of[:v], np.arange(v))
        starts = _starts(sizes, tile)
        for i in range(v):
            e = expert[i]
            assert sizes[e] > 0                       # a touched expert
            assert starts[e] <= i * tile              # one owner
            assert (i + 1) * tile <= starts[e] + ceilings[e] * tile
        assert (np.diff(expert[:v]) >= 0).all()
        # the steps past the count repeat the last: nothing is fetched
        if v:
            assert (expert[v:] == expert[v - 1]).all()
            assert (tile_of[v:] == v - 1).all()
        else:
            assert not tile_of.any() and len(set(expert)) == 1


# (tokens, picks a token, experts, tile, how the picks are drawn)
LAYOUTS = {
    "dead_rows": (23, 2, 8, 16, "dead"),
    "empty_experts": (19, 2, 8, 16, "few"),
    "every_pick_on_one_expert": (21, 3, 8, 16, "one"),
    "rows_no_multiple_of_the_tile": (37, 3, 8, 16, "any"),
    "k4": (300, 4, 16, 64, "dead"),
    "k6": (700, 6, 64, 128, "dead"),
    "k8": (48, 8, 64, 16, "any"),
}


@pytest.mark.parametrize("case", sorted(LAYOUTS))
def test_positions_by_counting_are_the_stable_sorts_order(case):
    """`routed_positions` against numpy's stable sort by expert: the
    same order inside a group, every group from a whole tile on, a dead
    token nowhere (its position is past the buffer)."""
    T, k, experts, tile, how = LAYOUTS[case]
    rng = np.random.default_rng(T)
    if how == "one":                     # no router does: counted all the same
        picks = np.full((T, k), 5)
    else:
        among = experts // 2 if how == "few" else experts
        picks = np.stack([rng.permutation(among)[:k] for _ in range(T)])
    live = rng.random(T) < 0.7 if how == "dead" else np.ones(T, bool)
    pos, sizes = gs.routed_positions(
        jnp.asarray(picks, jnp.int32), jnp.asarray(live), experts, tile)
    pos, sizes = np.asarray(pos).reshape(-1), np.asarray(sizes)
    flat = np.where(live[:, None], picks, experts).reshape(-1)
    order = np.argsort(flat, kind="stable")
    want_sizes = np.bincount(flat, minlength=experts + 1)[:experts]
    np.testing.assert_array_equal(sizes, want_sizes)
    whole = -(-want_sizes // tile) * tile
    buffer = gs.padded_rows(T * k, experts, tile)
    assert whole.sum() <= buffer and buffer % tile == 0
    first = np.cumsum(want_sizes) - want_sizes     # in the sorted order
    want = np.full(T * k, buffer)
    for r, at in enumerate(order[:want_sizes.sum()]):
        e = flat[at]
        want[at] = (np.cumsum(whole) - whole)[e] + r - first[e]
    np.testing.assert_array_equal(pos, want)


@pytest.mark.parametrize("rows,groups,tile", [
    (192, 64, 16),        # a decode step: 32 slots x 6 experts a token
    (24, 64, 16),         # a chat-sized step
    (512 * 8, 64, 64),    # Mellum's smallest prompt bucket
    (2048 * 4, 64, 128),  # Xing's
    (2048 * 6, 64, 256),  # Moonlight's
    (16384 * 8, 64, 256), # the largest
    (24, 8, 16), (400, 8, 64),
])
def test_the_row_tile_follows_the_static_row_count(rows, groups, tile):
    assert gs.row_tile_for(rows, groups) == tile


def test_a_backend_that_is_neither_is_an_error(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="grouped_swiglu compiles for TPU"):
        gs.grouped_swiglu(jnp.zeros((16, H)), *map(jnp.asarray, _weights(0)),
                          jnp.zeros((E,), jnp.int32), 16)


# -- compiled for the chip, without the chip ----------------------------------
# The three expert models' widths, both regimes: what interpret mode
# cannot refuse (a slice off the tiling, more VMEM than the limit asked
# for). The topology is described inside a fixture, never at import (one
# process may hold libtpu; see the on-chip-measurement guide).

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


@pytest.mark.parametrize("h,f,rows,experts,tile", [
    (2048, 1408, 192, 64, None), (2048, 1408, 2048 * 6, 64, None),
    (2048, 1408, 8192 * 6, 64, None),
    (3584, 1024, 16384 * 4, 64, None),    # Xing's largest bucket
    (2304, 896, 16384 * 8, 64, None),     # Mellum's
    # command-a's share: 16 experts held of 128, matrices of 4096 x 4096
    # in four slices of F; a 32-slot step's worst case and an 8,192-row
    # prompt's second static size, at the tiles the 128-wide router gives
    (4096, 4096, 32 * 8, 16, 16), (4096, 4096, 8192 * 8 // 4, 16, 256),
], ids=["moonlight_step", "moonlight_2048", "moonlight_8192", "xing_16384",
        "mellum_16384", "command_a_step", "command_a_8192"])
def test_compiles_at_the_models_widths_for_a_described_v5e(one_chip, h, f,
                                                           rows, experts,
                                                           tile):
    tile = tile or gs.row_tile_for(rows, experts)

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = gs._call.lower(
        shape(gs.padded_rows(rows, experts, tile), h), shape(experts, h, f),
        shape(experts, h, f), shape(experts, f, h),
        shape(experts, dtype=jnp.int32), tile=tile,
        interpret=False).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    # the weights go in where they lie: nothing of their size is made
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20


@pytest.mark.parametrize("h,f,k,tokens,router,held,parts,shared", [
    (2304, 896, 8, 16384, 64, 64, 1, False),      # Mellum's largest bucket
    (3584, 1024, 4, 16384, 64, 64, 1, True),      # Xing's
    (2048, 1408, 6, 8192, 64, 64, 1, True),       # Moonlight's
    # command-a's, in slices: under its `lax.cond` the kernel hands out
    # the picks' float32 sum, the shared term comes behind
    (4096, 4096, 8, 8192, 128, 16, 4, False),
], ids=["mellum_16384", "xing_16384", "moonlight_8192", "command_a_8192"])
def test_a_prompts_two_kernels_compile_for_a_described_v5e(
        one_chip, h, f, k, tokens, router, held, parts, shared):
    """The packed store of the expert kernel and the combine kernel that
    reads it (ops/routed_combine.py), at the buckets that take them: a
    row's lines stored by strided stores, fetched by a one-row DMA,
    summed through strided loads; nothing of the products' size is
    copied between the two (the reshape of the lines to rows is a
    bitcast)."""
    from paddle_tpu.ops import routed_combine as rc
    tile = gs.row_tile_for(tokens * k, router)
    rows = gs.padded_rows(tokens * k // parts, held, tile)

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def both(xs, gate, up, down, sizes, pos, w, term):
        ys = gs._call(xs, gate, up, down, sizes, tile=tile, interpret=False,
                      packed=True)
        return rc._call(ys, pos, w, term, 0.25, jnp.dtype(
            jnp.bfloat16 if parts == 1 else jnp.float32), False)

    compiled = jax.jit(both).lower(
        shape(rows, h), shape(held, h, f), shape(held, h, f),
        shape(held, f, h), shape(held, dtype=jnp.int32),
        shape(tokens, k, dtype=jnp.int32), shape(tokens, k, dtype=jnp.float32),
        shape(tokens, h) if shared else None).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    # the products (rows x h x 2 bytes) are the one large temporary
    assert compiled.memory_analysis().temp_size_in_bytes \
        < rows * h * 2 + (8 << 20)


def test_the_mixers_rounds_keep_their_layout_behind_the_combine_kernel(
        one_chip, monkeypatch):
    """Xing's feed-forward sublayer and the next one's coefficients at its
    widths, 4,096 tokens, with the two kernels compiled: every array of
    the Sinkhorn rounds keeps the tokens in the lanes (the last axis
    minor). Handed on token-first, the coefficients took the layout of
    whatever read them, and behind the combine kernel's output that put
    the 4 streams in the lanes (`{0,2,1`: `hc/coeff` 4.6 times slower on
    the chip; PERF.md, PR 39)."""
    import re
    from paddle_tpu.models import moonlight as ml
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = ml.MoonlightConfig(
        vocab_size=512, hidden=3584, layers=2, heads=4, kv_lora_rank=128,
        qk_nope_head_dim=32, qk_rope_head_dim=32, v_head_dim=32,
        intermediate=256, moe_intermediate=1024, n_routed_experts=64,
        n_shared_experts=1, experts_per_tok=4, first_k_dense=1, max_pos=64,
        hc_mult=4)
    tokens = 4096
    lp = jax.eval_shape(lambda: ml.init_params(
        cfg, jax.random.PRNGKey(0), jnp.bfloat16))["layers"][1]

    def two_sublayers(lp, x, live):
        counters = ml._zero_counters(cfg)
        for _ in range(2):
            x, counters = ml._ffn_sublayer(cfg, lp, x, live, counters)
        return x, counters

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    text = jax.jit(two_sublayers).lower(
        jax.tree_util.tree_map(on_chip, lp),
        jax.ShapeDtypeStruct((tokens, 4, 3584), jnp.bfloat16,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((tokens,), jnp.bool_, sharding=one_chip),
    ).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    rounds = re.findall(r"f32\[4,4,%d\]\{([0-9,]+)" % tokens, text)
    assert len(rounds) > 40 and set(rounds) <= {"2,0,1", "2,1,0"}, set(rounds)


def test_a_block_pass_kernels_compile_for_a_described_v5e(one_chip):
    """SDAR-30B-A3B-Chat's widths (4 KV heads of 128, 8 queries a KV head,
    blocks of 4, pages of 128): the grouped paged kernel with BLOCK ROWS
    (32 query rows a KV head, 4 new rows through the live page) over 64
    slots of 32 pages, and the flash forward under the block-causal mask
    at the 3,072-row bucket with a traced length."""
    from paddle_tpu.ops import flash_attention as fa
    from paddle_tpu.ops import paged_attention as pa

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    S, B, heads, kvh, d, pages = 64, 4, 32, 4, 128, 32

    def block_rows(q, new, arena, pt, lengths):
        return pa._grouped_call(q, new, arena, 1, pt, jnp.zeros_like(lengths),
                                lengths, False)

    # (tests/conftest.py asks for float32 products everywhere; the kernel's
    # are the arena's bfloat16, as the chip's run has them)
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(block_rows, donate_argnums=(2,)).lower(
            shape(S, B, heads, d), shape(S, B, kvh, 2 * d),
            shape(6, 1, S * pages + 1, kvh, 128, 2 * d),
            shape(S, pages, dtype=jnp.int32),
            shape(S, dtype=jnp.int32)).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1
    # the arena leaves the call as the buffer it came in
    assert compiled.memory_analysis().temp_size_in_bytes < (64 << 20)

    def prefill_attention(q, k, v, length):
        return fa._causal_rows_call(q, k, v, length, d ** -0.5, False,
                                    block=B)

    with jax.default_matmul_precision("default"):
        compiled = jax.jit(prefill_attention).lower(
            shape(3072, heads, d), shape(3072, kvh, d), shape(3072, kvh, d),
            shape(dtype=jnp.int32)).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("slots,kvh,queries,pages", [
    (48, 4, 8, 9), (48, 4, 8, 128),          # Mellum: a window ring, the table
    (32, 8, 16, 33), (32, 8, 16, 80),        # command-a's share
], ids=["mellum_window", "mellum_full", "command_a_window", "command_a_full"])
def test_a_decode_steps_grouped_walk_compiles_for_a_described_v5e(
        one_chip, slots, kvh, queries, pages):
    """The grouped paged kernel of a decode step (one new row a slot, the
    walk ONE stream of page groups across slots) at the widths and tables
    Mellum and command-a serve: pages of 128 rows, 256 lanes a KV head."""
    from paddle_tpu.ops import paged_attention as pa

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def step(q, new, arena, pt, lo, lengths):
        return pa._grouped_call(q, new, arena, 1, pt, lo, lengths, False)

    with jax.default_matmul_precision("default"):
        compiled = jax.jit(step, donate_argnums=(2,)).lower(
            shape(slots, kvh * queries, 128), shape(slots, kvh, 256),
            shape(2, 1, slots * pages + 1, kvh, 128, 256),
            shape(slots, pages, dtype=jnp.int32),
            shape(slots, dtype=jnp.int32),
            shape(slots, dtype=jnp.int32)).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1
    # the arena leaves the call as the buffer it came in
    assert compiled.memory_analysis().temp_size_in_bytes < (64 << 20)


@pytest.mark.parametrize("bucket", [512, 1024, 2048, 4096])
def test_the_chunked_scans_kernel_compiles_for_a_described_v5e(one_chip, bucket):
    """Kimi-Linear's widths (32 heads of 128 x 128, float32) in the four
    buckets of its cell: the single-row reads, the (128, 128) transposes
    and the `HIGHEST` products of ops/kda_chunk.py are Mosaic's to refuse,
    and nothing of an operand's size is made beside the call."""
    from paddle_tpu.ops import kda_chunk as kc

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    n, d = 32, 128
    heads = kc.heads_a_step(n)
    compiled = kc._call.lower(
        shape(1, dtype=jnp.int32), shape(bucket, n * d), shape(bucket, n * d),
        shape(bucket, n * d), shape(bucket, n * d),
        shape(n // heads, bucket, heads), None, heads=heads,
        interpret=False).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("heads,sq,sk,d,dtype,causal,bias,calls", [
    (96, 1024, 1024, 64, jnp.bfloat16, True, False, 1),
    (8, 1024, 1024, 64, jnp.float32, True, True, 1),
    (16, 1280, 768, 128, jnp.bfloat16, False, True, 1),
    (16, 4096, 4096, 128, jnp.bfloat16, True, False, 1),
    (2, 6144, 6144, 128, jnp.bfloat16, True, True, 1),
    (2, 6656, 6656, 128, jnp.bfloat16, True, True, 2),
], ids=["train_s1024", "float32_bias", "bert_1280x768_d128", "the_tools_4096",
        "the_rules_edge", "past_the_rules_edge"])
def test_the_flash_backward_compiles_for_a_described_v5e(
        one_chip, heads, sq, sk, d, dtype, causal, bias, calls):
    """The tiled backward (ops/flash_attention.py, PR 48) at the training
    cells' layer, with the per-key bias in float32, off the 512 grid, at the
    tool's long shape, and on both sides of `backward_span_rows`' edge: ONE
    Mosaic call named `flash_bwd` while a head's float32 dq fits the VMEM
    Mosaic grants (the compiler agrees with `_bwd_vmem_bytes` at the
    largest head it admits), a call a span of rows beyond."""
    from paddle_tpu.ops import flash_attention as fa

    assert -(-sq // fa.backward_span_rows(sq, sk, d, dtype)) == calls

    def shape(*dims, dtype=dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def backward(q, k, v, b, o, lse, do):
        return fa._flash_bwd_call(q, k, v, b if bias else None, o, lse, do,
                                  causal, d ** -0.5, False)

    tile = fa._pick_blocks(sq, sk)[0]      # the forward's: lse comes padded
    rows = -(-sq // tile) * tile
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(backward).lower(
            shape(heads, sq, d), shape(heads, sk, d), shape(heads, sk, d),
            shape(heads, sk, dtype=jnp.float32), shape(heads, sq, d),
            shape(heads, rows, 128, dtype=jnp.float32),
            shape(heads, sq, d)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == calls
    assert text.count("flash_bwd") >= calls
