"""The combine kernel (ops/routed_combine.py) and the packed rows it reads
(ops/grouped_swiglu.py, `packed=True`), interpreted on the CPU, against
XLA's sum `models/_experts._weighted_sum` bit for bit; and the rule that
says which of the two a pass takes (`models/_experts.combine_path`).

The weights of the bit-for-bit cases lie on a grid of 1/128: a bfloat16
product times such a weight is exact in float32, so a fused multiply-add
(which XLA's CPU backend may emit for the one and not for the other; the
chip has none) cannot show, while the float32 SUMS still round at every
pick and tell a changed order.
"""

import hashlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import _experts as ex
from paddle_tpu.ops import grouped_swiglu as gs
from paddle_tpu.ops import routed_combine as rc
from paddle_tpu.ops.routed_combine import routed_combine

H = 256
NAN_WORD = np.uint32(0x7fc07fc0)          # two bfloat16 NaNs


def _bits(a):
    return np.asarray(a).view(np.uint16 if a.dtype == jnp.bfloat16
                              else np.uint32)


def _pack(y):
    """(R, h) bfloat16 -> (R, 1, h / 2) uint32 in numpy: column j low,
    column j + h / 2 high."""
    bits = np.asarray(y).view(np.uint16).astype(np.uint32)
    half = bits.shape[1] // 2
    return (bits[:, :half] | (bits[:, half:] << 16))[:, None, :]


def _routing(rng, T, k, experts, held, dead):
    """picks (T, k) over `experts`, the first `held` of them here; the
    last `dead` tokens not live. Returns what `_moe` would hand on."""
    picks = np.stack([rng.permutation(experts)[:k] for _ in range(T)])
    live = np.arange(T) < T - dead
    mine = live[:, None] & (picks < held)
    tile = gs.row_tile_for(T * k, experts)
    pos, sizes = gs.routed_positions(
        jnp.asarray(picks, jnp.int32),
        jnp.asarray(mine if held < experts else live), held, tile)
    rows = gs.padded_rows(T * k, held, tile)
    w = rng.integers(0, 129, (T, k)).astype(np.float32) / 128
    return pos, np.asarray(sizes), rows, tile, live, np.where(mine, w, 0)


@pytest.mark.parametrize("k,experts,held,T,shared", [
    (4, 8, 8, 150, "none"), (4, 8, 8, 150, "sum"),
    (6, 16, 16, 130, "average"), (6, 16, 16, 130, "none"),
    (8, 16, 16, 70, "sum"), (8, 16, 16, 70, "average"),
    # a layer holding 16 of 128 experts
    (8, 128, 16, 136, "none"), (8, 128, 16, 136, "average"),
], ids=["k4-none", "k4-sum", "k6-average", "k6-none", "k8-sum", "k8-average",
        "held16of128-none", "held16of128-average"])
def test_the_kernel_is_the_weighted_sum_bit_for_bit(k, experts, held, T,
                                                    shared):
    """Tokens that are no multiple of the token tile (128), some dead,
    picks past the buffer (the dead tokens', and seven in eight where the
    layer holds a share), every row that is nobody's and every row past
    the last group NaN: a skipped pick adds 0, not 0 x NaN."""
    rng = np.random.default_rng(k + T)
    pos, sizes, rows, tile, live, w = _routing(rng, T, k, experts, held, 9)
    y = jnp.asarray(rng.normal(0, 1, (rows, H)), jnp.float32) \
        .astype(jnp.bfloat16)
    someones = np.zeros(rows, bool)
    someones[np.asarray(pos)[np.asarray(pos) < rows]] = True
    assert someones.sum() == sizes.sum() < rows
    clean = jnp.where(someones[:, None], y, 0)
    dirty = np.where(someones[:, None, None], _pack(y), NAN_WORD)
    term = None if shared == "none" else jnp.asarray(
        rng.normal(0, 1, (T, H)), jnp.float32).astype(jnp.bfloat16)
    scale = 0.25 if shared == "average" else None
    want = ex._combine(clean, pos, jnp.asarray(w), jnp.asarray(live), term,
                       scale, jnp.bfloat16, False)
    got = ex._combine(jnp.asarray(dirty), pos, jnp.asarray(w),
                      jnp.asarray(live), term, scale, jnp.bfloat16, True)
    assert got.shape == (T, H) and got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(_bits(got), _bits(want))
    assert T % rc._TILE and (np.asarray(pos) >= rows).any()
    if shared == "none":
        assert not np.asarray(got, np.float32)[~live].any()


def test_a_float32_sum_leaves_the_kernel_unrounded():
    """`dtype` float32: the sum itself, the same bits as XLA's."""
    rng = np.random.default_rng(3)
    pos, _, rows, _, live, w = _routing(rng, 40, 4, 8, 8, 3)
    y = jnp.asarray(rng.normal(0, 1, (rows, H)), jnp.float32) \
        .astype(jnp.bfloat16)
    want = ex._weighted_sum(y, pos, jnp.asarray(w), jnp.asarray(live))
    got = routed_combine(jnp.asarray(_pack(y)), pos, jnp.asarray(w),
                         dtype=jnp.float32)
    np.testing.assert_array_equal(_bits(got), _bits(want))


def test_what_the_kernel_refuses(monkeypatch):
    y = jnp.zeros((32, H), jnp.bfloat16)
    pos, w = jnp.zeros((4, 2), jnp.int32), jnp.zeros((4, 2))
    with pytest.raises(ValueError, match="packed rows"):
        routed_combine(y, pos, w)
    with pytest.raises(ValueError, match="packed rows"):
        routed_combine(jnp.zeros((32, 1, 64), jnp.uint32), pos, w)
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="routed_combine compiles for TPU"):
        routed_combine(jnp.zeros((32, 1, 128), jnp.uint32), pos, w)


# -- the packed store of the expert kernel ------------------------------------

def _weights(rng, experts, h, f):
    return [jnp.asarray(rng.normal(0, 0.1, s), jnp.float32)
            .astype(jnp.bfloat16)
            for s in ((experts, h, f), (experts, h, f), (experts, f, h))]


@pytest.mark.parametrize("h,f,sliced", [(256, 128, False), (768, 256, False),
                                        (512, 512, True)],
                         ids=["one_chunk", "three_chunks", "sliced"])
def test_the_packed_store_is_the_plain_stores_values(monkeypatch, h, f,
                                                     sliced):
    """Every row some expert computed, packed, unpacks to the bfloat16
    bits the plain store gives: whole and in slices of F."""
    if sliced:
        monkeypatch.setattr(gs, "_WEIGHTS_VMEM", 2 * 3 * h * f)
        assert gs.f_slices(h, f, 2) == 2
    rng = np.random.default_rng(h)
    E, tile = 4, 16
    sizes = np.array([5, 0, 33, 16], np.int32)
    R = gs.padded_rows(int(sizes.sum()), E, tile)
    xs = jnp.asarray(rng.normal(0, 1, (R, h)), jnp.float32) \
        .astype(jnp.bfloat16)
    w = _weights(rng, E, h, f)
    plain = gs.grouped_swiglu(xs, *w, jnp.asarray(sizes), tile)
    packed = gs.grouped_swiglu(xs, *w, jnp.asarray(sizes), tile, packed=True)
    assert packed.shape == (R, 1, h // 2) and packed.dtype == jnp.uint32
    visited = int((-(-sizes // tile)).sum()) * tile
    np.testing.assert_array_equal(np.asarray(packed)[:visited],
                                  _pack(plain)[:visited])
    # and the kernel reads them back as those values
    pos = jnp.arange(visited, dtype=jnp.int32).reshape(-1, 1)
    back = routed_combine(packed, pos, jnp.ones((visited, 1)))
    np.testing.assert_array_equal(_bits(back), _bits(plain[:visited]))


def test_packed_rows_are_bfloat16_of_whole_lanes():
    w = [jnp.zeros(s, jnp.float32) for s in ((2, 256, 128), (2, 256, 128),
                                             (2, 128, 256))]
    with pytest.raises(ValueError, match="packed rows are bfloat16"):
        gs.grouped_swiglu(jnp.zeros((32, 256)), *w, jnp.zeros((2,), jnp.int32),
                          16, packed=True)
    w = [jnp.zeros((2, 128, 128), jnp.bfloat16)] * 3
    with pytest.raises(ValueError, match="packed rows are bfloat16"):
        gs.grouped_swiglu(jnp.zeros((32, 128), jnp.bfloat16), *w,
                          jnp.zeros((2,), jnp.int32), 16, packed=True)


# -- which carrier a pass takes -------------------------------------------------

def _layer(rng, h, f, experts, held=None, shared=0):
    held = held or experts
    lp = dict(zip(("w_gate", "w_up", "w_down"), _weights(rng, held, h, f)))
    lp["router"] = jnp.asarray(rng.normal(0, 1, (h, experts)), jnp.float32) \
        .astype(jnp.bfloat16)
    if shared:
        lp.update(zip(("shared_gate", "shared_up", "shared_down"),
                      [a[0] for a in _weights(rng, 1, h, shared * f)]))
    return lp


def test_the_path_is_chosen_by_the_products_static_size(monkeypatch):
    """A TPU's product, bfloat16, whole 256 lanes and COMBINE_KERNEL_FROM
    bytes of rows: the kernel; one of them missing: the gather. At the
    cells' shapes (rows as `_moe` sizes them): every bucket of 4,096
    tokens and Moonlight's and Xing's 2,048 take the kernel, Mellum's 512
    and 1,024 and every decode step do not."""
    lp = {"w_gate": jnp.zeros((2, 256, 128), jnp.bfloat16)}
    x = jnp.zeros((8, 256), jnp.bfloat16)
    enough = ex.COMBINE_KERNEL_FROM // (256 * 2)
    assert ex.combine_path(lp, x, enough, 8) == "gather"          # the CPU
    monkeypatch.setattr(ex, "expert_product_path",
                        lambda lp: "grouped_swiglu_kernel")
    assert ex.combine_path(lp, x, enough, 8) == "row_dma_kernel"
    assert ex.combine_path(lp, x, enough - 1, 8) == "gather"
    assert ex.combine_path(lp, x.astype(jnp.float32), enough, 8) == "gather"
    assert ex.combine_path(lp, jnp.zeros((8, 384), jnp.bfloat16),
                           enough, 8) == "gather"
    # more than eight picks go to the kernel from 4,096 values a row on: where
    # it has been held on the chip (granite's 10 of 4,096), and not where it
    # has not (PR 56: Qwen3-Next's 10 of 2,048)
    assert ex.combine_path(lp, x, enough, 10) == "gather"
    cells = {  # h, k, router's experts, held, parts of a prompt
        "moonlight": (2048, 6, 64, 64, 1), "xing": (3584, 4, 64, 64, 1),
        "mellum": (2304, 8, 64, 64, 1), "commanda": (4096, 8, 128, 16, 4),
        "sdar": (2048, 8, 128, 128, 1), "granite": (4096, 10, 72, 36, 1)}

    def path(model, tokens):
        h, k, experts, held, parts = cells[model]
        tile = gs.row_tile_for(tokens * k, experts)
        slots = tokens * k // (parts if tokens * k >= ex.HELD_SPLIT_FROM
                               else 1)
        return ex.combine_path(lp, jnp.zeros((tokens, h), jnp.bfloat16),
                               gs.padded_rows(slots, held, tile), k)

    for model in cells:
        for tokens in (4096, 6144, 8192, 16384):
            assert path(model, tokens) == "row_dma_kernel", (model, tokens)
        for step in (16, 32, 48):
            assert path(model, step) == "gather", (model, step)
    assert path("mellum", 512) == path("mellum", 1024) == "gather"
    assert path("moonlight", 2048) == path("xing", 2048) == "row_dma_kernel"
    assert path("granite", 2048) == "row_dma_kernel"
    cells["qwen3next"] = (2048, 10, 512, 64, 4)
    for tokens in (2048, 4096, 8192, 12288, 16384):
        assert path("qwen3next", tokens) == "gather", tokens


# sha256 (16 hex) of str(jax.make_jaxpr(...)) of `_moe` at a decode step's and
# a 512 bucket's row count and of a share's parts, computed on the parent commit (42382ae, PR 38) by
# `_digest_of_moe` below under tests/conftest.py: below COMBINE_KERNEL_FROM the
# layer traces what it traced, with the TPU's product too. The one counter
# this PR adds is taken out of the outputs first (it is the `zero` the other
# counters share: no equation of its own).
PARENT_MOE = {
    ("ragged_dot", 48, "all"): "8990c7435e12312d",
    ("ragged_dot", 512, "all"): "0c4d23ba6e52f9a4",
    ("grouped_swiglu_kernel", 48, "all"): "63feea02fe148f20",
    ("grouped_swiglu_kernel", 512, "all"): "2a17545889b1d3f7",
    # a layer that holds 2 of 8 experts, 2,048 tokens in two parts under
    # the `lax.cond` (command-a's 2,048 bucket in small), two averaged
    # shared experts behind it. Computed again at PR 45 (its review round):
    # XLA's sum of a layer that holds a SHARE is pick by pick with a select
    # (`ex._weighted_sum(share=True)`); the four that hold all are the parent's
    ("ragged_dot", 2048, "share"): "153669ce95512193",
    ("grouped_swiglu_kernel", 2048, "share"): "46a8b7face328da8",
}
_CFG = {
    "all": types.SimpleNamespace(
        experts_per_tok=2, n_routed_experts=8, n_shared_experts=1,
        router_scoring="sigmoid", routed_scaling_factor=2.0),
    "share": types.SimpleNamespace(
        experts_per_tok=2, n_routed_experts=8, n_shared_experts=2,
        router_scoring="sigmoid", routed_scaling_factor=2.0,
        experts_held=(0, 2), shared_expert_combination="average"),
}


def _digest_of_moe(tokens, held):
    cfg = _CFG[held]
    lp = _layer(np.random.default_rng(0), 256, 128, 8,
                held=getattr(cfg, "experts_held", (0, 8))[1],
                shared=cfg.n_shared_experts)

    def moe(x, live):
        y, counters = ex.moe(cfg, lp, x, live)
        counters.pop("combine_kernel_passes", None)
        return y, counters
    text = str(jax.make_jaxpr(moe)(jnp.zeros((tokens, 256), jnp.bfloat16),
                                   jnp.ones((tokens,), bool)))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("product,tokens,held", list(PARENT_MOE))
def test_a_step_and_a_short_prompt_trace_the_parents_layer(
        monkeypatch, product, tokens, held):
    monkeypatch.setattr(ex, "expert_product_path", lambda lp: product)
    assert _digest_of_moe(tokens, held) == PARENT_MOE[product, tokens, held]


@pytest.mark.parametrize("held", [8, 2], ids=["all_held", "a_share"])
def test_the_layer_through_the_kernel_is_the_layer_through_the_gather(
        monkeypatch, held):
    """`_moe` whole with the kernel as its combine (the threshold at 0,
    the TPU's product interpreted) against the CPU's layer (`ragged_dot`,
    XLA's gather): the same counters but the two kernels' own, and the
    same output to bfloat16's rounding (XLA keeps excess precision where
    it fuses; the kernels round where they store); where the layer holds
    a share of the experts, inside its `lax.cond` (the second static
    size: the kernel hands out the picks' float32 sum, the shared
    experts' term and the rounding come behind the cond as XLA's do)."""
    rng = np.random.default_rng(held)
    cfg = types.SimpleNamespace(
        experts_per_tok=2, n_routed_experts=8, n_shared_experts=2,
        router_scoring="sigmoid", routed_scaling_factor=2.0,
        experts_held=(0, held), shared_expert_combination="average")
    lp = _layer(rng, 256, 128, 8, held=held, shared=2)
    T = 64
    x = jnp.asarray(rng.normal(0, 1, (T, 256)), jnp.float32) \
        .astype(jnp.bfloat16)
    live = jnp.arange(T) < 57
    monkeypatch.setattr(ex, "HELD_SPLIT_FROM", 64)
    want, gather = jax.jit(lambda x: ex.moe(cfg, lp, x, live))(x)
    monkeypatch.setattr(ex, "expert_product_path",
                        lambda lp: "grouped_swiglu_kernel")
    monkeypatch.setattr(ex, "COMBINE_KERNEL_FROM", 0)
    got, kernel = jax.jit(lambda x: ex.moe(cfg, lp, x, live))(x)
    assert int(kernel["combine_kernel_passes"]) == 1
    assert int(kernel["kernel_passes"]) == 1
    for name in ("expert_tokens", "router_tokens", "experts_touched",
                 "moe_passes"):
        np.testing.assert_array_equal(kernel[name], gather[name])
    for name in ("combine_kernel_passes", "kernel_passes", "rows_computed"):
        assert int(gather[name]) == 0
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=0.1)
