"""command-a-plus-05-2026's block (paddle_tpu.models.command_a) at a small
size on the CPU: a parallel block behind ONE LayerNorm, window layers
with interleaved rotary beside full layers with NO positions, sigmoid
top-k experts of which the chip holds a SHARE, shared experts averaged.

The reference is benchmarks/reference/command_a_ref.py (float32, highest
precision, no cache, independent of the program), given the same held
range. Pinned here: the served path against it at every served position,
below and beyond the window and across a ring wrap; the full layers
ignore positions and the window layers do not; THE SHARES ADD UP (every
share's routed part plus the shared experts once is the uncut reference's
layer); an expert layer that holds every expert is, bit for bit, the one
PR 37 left; the layout of picks that fall outside the held range; the
second static size and its fall-back; the F-sliced expert kernel."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from reference import command_a_ref as ref                   # noqa: E402

from paddle_tpu.models import _decoder as dec                # noqa: E402
from paddle_tpu.models import _experts as ex                 # noqa: E402
from paddle_tpu.models import _grouped as gr                 # noqa: E402
from paddle_tpu.models import command_a as ca                # noqa: E402
from paddle_tpu.models import mellum as mm                   # noqa: E402
from paddle_tpu.models import moonlight as ml                # noqa: E402
from paddle_tpu.ops import grouped_swiglu as gs              # noqa: E402
from paddle_tpu.serving import (ServingConfig, ServingEngine,  # noqa: E402
                                SlotKVCache)
from paddle_tpu.serving.model import ring_pages, serving_model  # noqa: E402

WINDOW, BS, E, HELD = 8, 4, 16, (4, 4)
SIZES = dict(vocab_size=96, hidden=64, layers=4, heads=8, kv_heads=2,
             head_dim=16, moe_intermediate=32, n_routed_experts=E,
             n_shared_experts=2, experts_per_tok=4, sliding_window=WINDOW,
             max_pos=64, init_range=0.08)
CFG = ca.CommandAConfig(experts_held=HELD, vocab_slice=(96, 96, 768), **SIZES)
WHOLE = ca.CommandAConfig(**SIZES)
# the same model under the published keys, as the reference reads them
REF_CFG = {
    "head_dim": 16, "hidden_size": 64, "num_attention_heads": 8,
    "num_key_value_heads": 2, "num_hidden_layers": 4,
    "layer_types": list(CFG.layer_types), "sliding_window": WINDOW,
    "num_experts": HELD[1], "experts_held_first": HELD[0],
    "num_experts_per_tok": 4, "num_shared_experts": 2,
    "norm_topk_prob": True, "layer_norm_eps": 1e-5, "rope_theta": 50000.0,
    "logit_scale": 1.0}
RING = ring_pages(WINDOW, BS)
LOGIT_ATOL = 2e-5


def tokens_of(seed, n):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n) \
        .astype(np.int32)


@pytest.fixture(scope="module")
def whole():
    """Every expert's weights; a share's tree is a slice of it."""
    return ca.init_params(WHOLE, jax.random.PRNGKey(0), jnp.float32)


def share_of(whole, first, count):
    layers = [dict(lp, **{name: lp[name][first:first + count]
                          for name in ("w_gate", "w_up", "w_down")})
              for lp in whole["layers"]]
    return dict(whole, layers=layers)


@pytest.fixture(scope="module")
def params(whole):
    return share_of(whole, *HELD)


def reference_logits(params, seq, **kw):
    return np.asarray(ref.sequence_logits(params, REF_CFG,
                                          jnp.asarray(seq, jnp.int32), **kw))


# -- the config and what the engine is told -------------------------------------

def test_config_kinds_groups_and_the_share():
    assert CFG.layer_types == (gr.WINDOW,) * 3 + (gr.FULL,)
    assert CFG.group == 4 and ex.held_experts(CFG) == HELD
    assert ex.held_experts(WHOLE) == (0, E)
    assert WHOLE.vocab_slice == (0, 96, 96)
    model = serving_model(CFG)
    full, window = model.cache_spec(CFG)
    assert (full.layers, full.heads, full.row_width, full.window) == (1, 2, 32, None)
    assert (window.layers, window.window, window.name) == (3, WINDOW, "window")
    assert model.describe(CFG) == {
        "experts_held": {"first": 4, "count": 4, "of": 16},
        "vocab_slice": {"first": 96, "rows": 96, "of": 768}}
    assert model.counter_names(CFG)["expert_tokens"] == (4,)
    assert model.features == frozenset()
    with pytest.raises(ValueError, match="experts_held"):
        ca.CommandAConfig(experts_held=(14, 4), **SIZES)
    with pytest.raises(ValueError, match="vocab_slice"):
        ca.CommandAConfig(vocab_slice=(0, 64, 768), **SIZES)
    published = ca.CommandAConfig()
    assert (published.heads, published.kv_heads, published.group) == (128, 8, 16)
    assert published.layer_types.count(gr.FULL) == 8 and published.layers == 32


def test_init_makes_only_the_held_experts(params, whole):
    lp = params["layers"][0]
    assert lp["w_gate"].shape == (4, 64, 32) and lp["router"].shape == (64, E)
    assert lp["shared_gate"].shape == (64, 64) and "head" not in params
    made = ca.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    assert made["layers"][0]["w_down"].shape == (4, 32, 64)
    assert whole["layers"][0]["w_gate"].shape == (E, 64, 32)


# -- the system against the reference --------------------------------------------

@pytest.mark.parametrize("length", [5, WINDOW, 3 * WINDOW + 5])
def test_forward_matches_the_reference(params, length):
    seq = tokens_of(length, length)
    got = np.asarray(ca.forward_logits(params, CFG, jnp.asarray(seq)))
    assert np.abs(got - reference_logits(params, seq)).max() <= LOGIT_ATOL


def test_forward_of_the_uncut_model_matches_the_uncut_reference(whole):
    seq = tokens_of(7, 21)
    got = np.asarray(ca.forward_logits(whole, WHOLE, jnp.asarray(seq)))
    want = reference_logits(whole, seq, held=(0, E))
    assert np.abs(got - want).max() <= LOGIT_ATOL


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_a_wrong_reference_is_another_function(params, wrong):
    """Each WRONG reference differs from the true one at a small size, by far
    more than the system does."""
    seq = tokens_of(3, 40)
    true = reference_logits(params, seq)
    assert np.abs(reference_logits(params, seq, wrong=wrong) - true).max() \
        > 50 * LOGIT_ATOL


def test_full_layers_ignore_positions_and_window_layers_do_not(params):
    """Shift every position by 5: a full layer's projections do not move, a
    window layer's q and k do, and their scores (a function of i - j) do
    not."""
    u = jax.random.normal(jax.random.PRNGKey(1), (6, 64), jnp.float32)
    pos = jnp.arange(6)
    for li, kind in ((0, "window"), (3, "full")):
        lp = params["layers"][li]
        q0, k0, v0 = ca._project(CFG, lp, u, pos, kind)
        q1, k1, v1 = ca._project(CFG, lp, u, pos + 5, kind)
        np.testing.assert_array_equal(v0, v1)
        moved = float(jnp.abs(q0 - q1).max())
        if kind == "full":
            assert moved == 0.0 and float(jnp.abs(k0 - k1).max()) == 0.0
        else:
            assert moved > 0.1
            s0 = jnp.einsum("qhd,khd->hqk", q0[:, :4], k0[:, :1].repeat(4, 1))
            s1 = jnp.einsum("qhd,khd->hqk", q1[:, :4], k1[:, :1].repeat(4, 1))
            assert float(jnp.abs(s0 - s1).max()) < 1e-4


def test_interleaved_pairs_against_the_references_rotation(params):
    """The program rotates in halves after a permutation of the pairs; the
    reference turns the interleaved pairs where they lie: the same scores."""
    x = jax.random.normal(jax.random.PRNGKey(2), (5, 3, 16), jnp.float32)
    pos = jnp.asarray([0, 3, 9, 20, 41])
    mine = dec.rope(x, pos[:, None], 50000.0)
    theirs = ref._rope_interleaved(x, pos, 50000.0)
    back = jnp.concatenate([theirs[..., 0::2], theirs[..., 1::2]], -1)
    assert float(jnp.abs(mine - back).max()) < 1e-5


# -- the shares add up -----------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(whole):
    """Eight chips hold two experts each. A chip's layer gives its routed
    part plus what every chip computes alike (the shared experts' mean); the
    eight routed parts and the shared experts ONCE are the uncut reference's
    layer."""
    lp = whole["layers"][1]
    u = jax.random.normal(jax.random.PRNGKey(3), (37, 64), jnp.float32)
    live = jnp.ones((37,), bool)
    with jax.default_matmul_precision("highest"):
        routed, shared, _ = ref.ffn(u, lp, dict(REF_CFG, num_experts=E), held=(0, E))
        uncut = np.asarray(routed + shared)
        total = np.zeros_like(uncut)
        held_picks = 0
        for first in range(0, E, 2):
            cfg = ca.CommandAConfig(experts_held=(first, 2), **SIZES)
            part = share_of(whole, first, 2)["layers"][1]
            y, c = ex.moe(cfg, part, u, live)
            mine, theirs, _ = ref.ffn(u, part, REF_CFG, held=(first, 2))
            assert np.abs(np.asarray(y) - np.asarray(mine + theirs)).max() <= 2e-6
            total += np.asarray(y) - np.asarray(shared)
            held_picks += int(c["expert_tokens"].sum())
        assert held_picks == 37 * 4              # every pick is some chip's
        assert np.abs(total + np.asarray(shared) - uncut).max() <= 5e-6
        # and the uncut layer of the PROGRAM is the same function
        y, _ = ex.moe(WHOLE, lp, u, live)
        assert np.abs(np.asarray(y) - uncut).max() <= 2e-6


def test_the_shared_experts_are_averaged(whole):
    lp = whole["layers"][0]
    u = jax.random.normal(jax.random.PRNGKey(4), (9, 64), jnp.float32)
    _, shared, _ = ref.ffn(u, lp, dict(REF_CFG, num_experts=E), held=(0, E))
    by_hand = sum(ex.swiglu(u, lp["shared_gate"][:, s], lp["shared_up"][:, s],
                             lp["shared_down"][s])
                  for s in (slice(0, 32), slice(32, 64))) / 2
    assert float(jnp.abs(shared - by_hand).max()) < 1e-6


def test_sigmoid_routing_without_bias_or_factor(whole):
    lp = whole["layers"][2]
    u = jax.random.normal(jax.random.PRNGKey(5), (11, 64), jnp.float32)
    picks, w = ex.route(WHOLE, lp, u)
    scores = jax.nn.sigmoid(jnp.dot(u, lp["router"],
                                    precision=jax.lax.Precision.HIGHEST))
    want_w, want = jax.lax.top_k(scores, 4)
    np.testing.assert_array_equal(picks, want)
    np.testing.assert_allclose(w, want_w / want_w.sum(-1, keepdims=True),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)


# -- an expert layer that holds every expert is the one it was -------------------

def _moe_of_pr37(cfg, lp, x, live):
    """models/moonlight.py::_moe as PR 37 left it (commit 0c98a1f), word for
    word but for its imports: what `_moe` must still give, bit for bit,
    where every expert is held."""
    from paddle_tpu.ops.grouped_swiglu import (padded_rows, routed_positions,
                                               row_tile_for)
    T, k, E = x.shape[0], cfg.experts_per_tok, cfg.n_routed_experts
    tile = row_tile_for(T * k, E)
    picks, w = ex.route(cfg, lp, x)
    pos, group_sizes = routed_positions(picks, live, E, tile)
    at = pos.reshape(-1)
    token = jnp.arange(T * k, dtype=jnp.int32) // k
    rows = padded_rows(T * k, E, tile)
    if 4 * T * k <= rows:
        xs = jnp.zeros((rows, x.shape[1]), x.dtype).at[at].set(
            x[token], mode="drop", unique_indices=True)
    else:
        source = jnp.zeros((rows,), jnp.int32).at[at].set(
            token, mode="drop", unique_indices=True)
        xs = x[source]
    ys = ex.grouped_experts(lp, xs, group_sizes, tile)
    if cfg.n_shared_experts:
        shared = ex.swiglu(x, lp["shared_gate"], lp["shared_up"],
                            lp["shared_down"])
    back = ys.at[pos.T.reshape(-1)].get(mode="clip").reshape(k, T, -1)
    y = back[0].astype(jnp.float32) * w[:, 0, None]
    for j in range(1, k):
        y = y + back[j].astype(jnp.float32) * w[:, j, None]
    y = jnp.where(live[:, None], y, 0)
    if cfg.n_shared_experts:
        y = y + shared.astype(jnp.float32)
    return y.astype(x.dtype), group_sizes


_XING = dict(q_lora_rank=16, hc_mult=4, hc_sinkhorn_iters=6,
             name="Xing4.0-29B-A4B")
_LATENT = dict(vocab_size=211, hidden=64, layers=3, heads=4, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               intermediate=96, moe_intermediate=32, n_routed_experts=8,
               n_shared_experts=1, experts_per_tok=2, max_pos=64)


def _held_everything(name):
    if name == "mellum":
        cfg = mm.MellumConfig(vocab_size=211, hidden=64, layers=4, heads=4,
                              kv_heads=1, head_dim=16, moe_intermediate=32,
                              n_routed_experts=8, experts_per_tok=2,
                              sliding_window=8, max_pos=64,
                              rope_scaling={"type": "yarn", "factor": 4,
                                            "original_max_position_embeddings": 16,
                                            "beta_fast": 32, "beta_slow": 1})
        return cfg, mm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    cfg = ml.MoonlightConfig(**_LATENT, **(_XING if name == "xing" else {}))
    return cfg, ml.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("tokens", [3, 40])
@pytest.mark.parametrize("name", ["moonlight", "xing", "mellum"])
def test_every_expert_held_is_bit_for_bit_what_it_was(name, tokens, dtype):
    """Moonlight's, Xing's and Mellum's configs: `_moe` with every expert held
    (the default) against PR 37's, a step's few rows (placed) and a prompt's
    (gathered), some rows dead."""
    cfg, params = _held_everything(name)
    lp = jax.tree_util.tree_map(lambda a: a.astype(dtype), params["layers"][-1])
    x = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, 64)).astype(dtype)
    live = jnp.arange(tokens) % 5 != 1
    got, counters = ex.moe(cfg, lp, x, live)
    want, sizes = _moe_of_pr37(cfg, lp, x, live)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_array_equal(counters["expert_tokens"], sizes)


# -- the layout of a share --------------------------------------------------------

def test_a_pick_outside_the_held_range_has_no_row():
    """Six experts held of more; picks numbered from the first held. By PICK:
    a pick outside [0, 6) gets the past-the-buffer position, no two live picks
    share a row, and `group_sizes` counts held picks only. (By token, as PR 37
    left it, such a pick read row 0 + its `twice`.)"""
    rng = np.random.default_rng(0)
    T, k, groups, tile = 50, 4, 6, 16
    picks = np.stack([rng.choice(np.arange(-5, 27), k, replace=False)
                      for _ in range(T)]).astype(np.int32)
    live = rng.random(T) < 0.8
    held = live[:, None] & (picks >= 0) & (picks < groups)
    by_pick = jnp.asarray(live)[:, None] & jnp.ones((T, k), bool)
    pos, sizes = gs.routed_positions(jnp.asarray(picks), by_pick, groups, tile)
    pos, sizes = np.asarray(pos), np.asarray(sizes)
    past = gs.padded_rows(T * k, groups, tile)
    assert (pos[~held] == past).all() and (pos[held] < past).all()
    assert len(set(pos[held].tolist())) == held.sum()          # no row twice
    np.testing.assert_array_equal(
        sizes, [(held & (picks == e)).sum() for e in range(groups)])
    # every group from a whole tile on, its rows in (token, pick) order
    start = 0
    for e in range(groups):
        mine = pos[held & (picks == e)]
        np.testing.assert_array_equal(mine, start + np.arange(len(mine)))
        start += -(-len(mine) // tile) * tile
    assert start <= gs.padded_rows(int(held.sum()), groups, tile)
    # by token the layout is PR 37's: the same positions for in-range picks
    inside = np.clip(picks, 0, groups - 1)
    a = gs.routed_positions(jnp.asarray(inside), jnp.asarray(live), groups, tile)
    b = gs.routed_positions(jnp.asarray(inside), jnp.asarray(live)[:, None]
                            & jnp.ones((T, k), bool), groups, tile)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])


@pytest.mark.parametrize("skew", ["even", "all_held"])
def test_the_second_static_size_and_its_fall_back(whole, monkeypatch, skew):
    """From HELD_SPLIT_FROM picks on, a share's buffer is sized for
    HELD_SLACK times the average and a pass that does not fit takes the same
    code over the tokens in parts: the same result as the one worst-case
    buffer, whatever the routing, and no pick dropped."""
    cfg = ca.CommandAConfig(experts_held=(0, 2), **SIZES)       # 2 of 16
    lp = dict(share_of(whole, 0, 2)["layers"][0])
    if skew == "all_held":
        # a router that sends every token to experts 0 and 1 first: four
        # times the average, so the pass does NOT fit the second size
        lp["router"] = lp["router"].at[0, :2].set(5.0)
    u = jax.random.normal(jax.random.PRNGKey(6), (64, 64), jnp.float32)
    u = u.at[:, 0].set(4.0)
    live = jnp.arange(64) != 7
    monkeypatch.setattr(ex, "HELD_SPLIT_FROM", 1 << 30)
    one, c_one = ex.moe(cfg, lp, u, live)                     # one buffer
    monkeypatch.setattr(ex, "HELD_SPLIT_FROM", 16)
    text = str(jax.make_jaxpr(lambda x: ex.moe(cfg, lp, x, live)[0])(u))
    assert "cond[" in text
    two, c_two = ex.moe(cfg, lp, u, live)
    np.testing.assert_allclose(np.asarray(two), np.asarray(one), atol=1e-6)
    np.testing.assert_array_equal(c_two["expert_tokens"], c_one["expert_tokens"])
    held = int(c_two["expert_tokens"].sum())
    assert held == (2 * 63 if skew == "all_held" else held) and held > 0
    with jax.default_matmul_precision("highest"):
        routed, shared, _ = ref.ffn(u, lp, dict(REF_CFG, num_experts=2), held=(0, 2))
    want = np.where(np.asarray(live)[:, None], np.asarray(routed), 0) + np.asarray(shared)
    assert np.abs(np.asarray(two) - want).max() <= 5e-6


def test_the_sliced_kernel_is_the_whole_ones_function(monkeypatch):
    """An expert whose matrices do not fit VMEM whole is visited in slices of
    F (interpreted here): the same function as the whole visit's."""
    E_, h, F, tile = 3, 128, 512, 16
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    gate, up = (0.05 * jax.random.normal(k, (E_, h, F), jnp.float32) for k in ks[:2])
    down = 0.05 * jax.random.normal(ks[2], (E_, F, h), jnp.float32)
    sizes = jnp.asarray([20, 0, 7], jnp.int32)
    xs = jax.random.normal(ks[3], (gs.padded_rows(27, E_, tile), h), jnp.float32)
    whole_ = gs.grouped_swiglu(xs, gate, up, down, sizes, tile)
    assert gs.f_slices(h, F, 4) == 1 and gs.f_slices(4096, 4096, 2) == 4
    assert [gs.f_slices(*s, 2) for s in ((2048, 1408), (3584, 1024), (2304, 896))] \
        == [1, 1, 1]
    monkeypatch.setattr(gs, "_WEIGHTS_VMEM", 2 * 3 * h * 128 * 4)
    assert gs.f_slices(h, F, 4) == 4
    sliced = gs._call.__wrapped__(xs, gate, up, down, sizes, tile, True)
    rows = np.r_[0:20, 48:55]                  # the rows that are someone's
    np.testing.assert_allclose(np.asarray(sliced)[rows], np.asarray(whole_)[rows],
                               rtol=2e-5, atol=2e-6)


# -- prefill, then decode through both cache groups ------------------------------

_PREFILL = jax.jit(lambda p, t, n, a, pg: ca.prefill_pages(
    p, CFG, t, jnp.int32(0), n, a, pg))
_DECODE = jax.jit(lambda p, tok, a, pt, ts, done: ca.decode_step_pages(
    p, CFG, tok, a, pt, ts, done, attention={"full": "gather",
                                             "window": "gather"}))


def test_prefill_then_decode_steps_match_the_reference(params):
    """Three slots: prompts shorter than the window (5), equal to it (8) and
    several times it (27: its ring of 3 pages wrapped twice by the prefill),
    the second FROZEN through the steps; 14 steps, so the short prompt's
    decode leaves the window and its ring wraps. Every step's logits of every
    live slot against the reference's full forward; rows and picks counted."""
    steps = 14
    kv = SlotKVCache(CFG, 3, 48, jnp.float32, block_size=BS)
    arena = kv.arena
    prompts = {0: tokens_of(20, 5), 1: tokens_of(21, WINDOW), 2: tokens_of(22, 27)}
    seqs = {s: list(p) + list(tokens_of(50 + s, steps)) for s, p in prompts.items()}
    want = {s: reference_logits(params, seq) for s, seq in seqs.items()}
    for s, prompt in prompts.items():
        assert kv.alloc() == s
        row, hit = kv.map_slot(s, prompt, len(prompt) + steps)
        assert hit == 0 and row.shape == (12 + RING,)
        padded = np.zeros((1, 32), np.int32)
        padded[0, :len(prompt)] = prompt
        logits, arena, c = _PREFILL(params, jnp.asarray(padded),
                                    jnp.int32(len(prompt)), arena,
                                    jnp.asarray(kv.page_table[s]))
        assert np.abs(np.asarray(logits[0]) - want[s][len(prompt) - 1]).max() \
            <= LOGIT_ATOL
        assert int(c["router_tokens"]) == 4 * len(prompt)      # padding is dead
        assert kv.group_rows(s)["window"] <= WINDOW + BS
    pt = jnp.asarray(kv.page_table)
    done = jnp.asarray([False, True, False])
    ts = jnp.asarray([len(prompts[s]) for s in range(3)], jnp.int32)
    for i in range(steps):
        tok = jnp.asarray([seqs[s][len(prompts[s]) + i] for s in range(3)], jnp.int32)
        logits, arena, c = _DECODE(params, tok, arena, pt, ts, done)
        for s in (0, 2):
            at = len(prompts[s]) + i
            assert np.abs(np.asarray(logits[s]) - want[s][at]).max() <= LOGIT_ATOL, (s, at)
        assert int(c["decode_rows_full"]) == sum(
            len(prompts[s]) + i + 1 for s in (0, 2))
        assert int(c["decode_rows_window"]) == sum(
            3 * min(len(prompts[s]) + i + 1, WINDOW) for s in (0, 2))
        assert int(c["router_tokens"]) == 2 * 4 and int(c["moe_passes"]) == 4
        assert 0 <= int(c["expert_tokens"].sum()) <= 2 * 4 * 4
        ts = ts + 1


# -- through the engine -------------------------------------------------------------

def _engine(params, **kw):
    kw.setdefault("num_slots", 3)
    kw.setdefault("prefill_buckets", (8, 16, 32))
    kw.setdefault("max_len", 48)
    kw.setdefault("block_size", BS)
    return ServingEngine(params, CFG, ServingConfig(**kw))


@pytest.mark.parametrize("p_len,new", [(3, 12), (WINDOW, 20), (27, 15), (5, 30)])
def test_the_engine_serves_the_references_greedy_tokens(params, p_len, new):
    """Through submit -> scheduler -> cache -> decode_loop: prompts below, at
    and beyond the window, every request crossing it or wrapping its ring;
    each served token is the argmax of the reference's logits on prompt +
    tokens (where the reference's top two are apart)."""
    eng = _engine(params)
    req = eng.submit(tokens_of(p_len + new, p_len), max_new_tokens=new)
    eng.run_until_drained()
    assert len(req.tokens) == new
    logits = reference_logits(params, req.output())[p_len - 1:-1]
    top = np.sort(logits, -1)
    clear = top[:, -1] - top[:, -2] > 1e-3
    assert clear.sum() >= new - 3
    assert (np.argmax(logits, -1) == np.asarray(req.tokens))[clear].all()
    st = eng.stats()
    assert st["model"] == "command-a-plus-05-2026"
    assert st["experts_held"] == {"first": 4, "count": 4, "of": 16}
    assert st["vocab_slice"] == {"first": 96, "rows": 96, "of": 768}
    assert st["decode_attention"] == {"full": "gather", "window": "gather"}
    assert [g["name"] for g in st["groups"]] == ["full", "window"]
    assert len(st["expert_tokens"]) == 4
    # the picks: routed = tokens x 4 in every layer, held = those of experts 4..7
    assert st["moe_picks_routed"] == 4 * st["router_tokens"]
    assert st["moe_picks_held"] == sum(st["expert_tokens"])
    assert 0 < st["moe_picks_held"] < st["moe_picks_routed"]
    assert st["decode_moe_picks_routed"] == 4 * st["decode_router_tokens"]
    assert st["moe_combine_kernel_passes"] == 0          # the CPU gathers
    assert 0 <= st["decode_moe_picks_held"] <= st["moe_picks_held"]
    assert st["decode_rows_full"] > 0 and st["decode_rows_window"] > 0
    assert st["compiled_executables"] <= 3 + 2
    eng.close()


@pytest.mark.parametrize("option", ["kv_dtype", "speculate_k", "prefill_chunk",
                                    "weight_dtype"])
def test_what_the_block_does_not_implement_is_refused_at_construction(
        params, option):
    value = {"kv_dtype": "int8", "speculate_k": 2, "prefill_chunk": 8,
             "weight_dtype": "int8"}[option]
    with pytest.raises(ValueError, match="does not implement"):
        _engine(params, **{option: value})
