"""Moonlight-16B-A3B's serving model (models/moonlight.py) against its plain
reference (benchmarks/reference/moonlight_ref.py: float32, highest
precision, no cache, expanded attention, a Python loop over experts) on
seeded random weights at a small size: hidden 64, 4 heads, nope 16 / rope
8 / v 16, latent 32, 8 experts of width 32 with 2 a token and a shared one,
1 dense + 2 expert layers; float32 weights, kernels interpreted.

Tolerances. Both sides compute in float32 (conftest sets the highest
matmul precision), so what separates them is the ORDER of the sums: the
absorbed form contracts over the latent where the expanded one contracts
over the head, the grouped product sums a token's experts in another
order, the online softmax rescales. Logits here have a standard deviation
of about 0.16 and a largest magnitude under 1; a float32 rounding is 6e-8
relative, a few hundred of them in a row stay under 1e-5. LOGIT_ATOL is
5e-5: 35 times the 1.4e-6 that was measured on the whole sequence, and 66
times under what rounding the router's WEIGHTS to bfloat16 moves a logit
by (3.3e-3; a norm's statistics in bfloat16 move one by 0.5: the last
test shows both failing it). Top-k picks are discontinuous: every comparison that
depends on the picks runs on inputs whose k-th and (k+1)-th biased scores
are at least PICK_GAP apart (1e-4, a thousand float32 roundings), counts
the tokens that were left out for being nearer, and requires that most
remain; no tolerance is widened for a tie.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401
from paddle_tpu.models import _decoder as dec
from paddle_tpu.models import _experts as ex
from paddle_tpu.models import _latent
from paddle_tpu.models import moonlight as ml
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.kv_cache import SlotKVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGIT_ATOL = 5e-5
PICK_GAP = 1e-4


def _load_reference():
    path = os.path.join(ROOT, "benchmarks", "reference", "moonlight_ref.py")
    spec = importlib.util.spec_from_file_location("moonlight_ref", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()

CFG = ml.MoonlightConfig(
    vocab_size=211, hidden=64, layers=3, heads=4, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, intermediate=96,
    moe_intermediate=32, n_routed_experts=8, n_shared_experts=1,
    experts_per_tok=2, first_k_dense=1, max_pos=64)
# the same numbers under the published keys, as the reference reads them
REF_CFG = {"num_attention_heads": 4, "qk_nope_head_dim": 16,
           "qk_rope_head_dim": 8, "kv_lora_rank": 32, "v_head_dim": 16,
           "rms_norm_eps": 1e-5, "rope_theta": 50000.0,
           "num_experts_per_tok": 2, "norm_topk_prob": True,
           "routed_scaling_factor": 2.446}


@pytest.fixture(scope="module")
def params():
    p = ml.init_params(CFG, jax.random.PRNGKey(7), jnp.float32)
    # norms away from one and a correction bias large enough to move picks
    rng = np.random.default_rng(3)
    for lp in p["layers"]:
        for name in ("norm1", "norm2", "kv_norm"):
            lp[name] = jnp.asarray(rng.uniform(0.5, 1.5, lp[name].shape),
                                   jnp.float32)
        if "router_bias" in lp:
            lp["router_bias"] = jnp.asarray(
                rng.normal(0, 0.02, lp["router_bias"].shape), jnp.float32)
    # weights large enough that logits and router scores spread
    return jax.tree_util.tree_map(
        lambda a: a * 4.0 if a.ndim >= 2 else a, p)


def tokens_of(seed, n):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n)


WIDTH = 48


def reference_logits(params, seq):
    """The reference's logits at every position of `seq`, computed on
    the sequence padded to WIDTH (causal: the padding reaches no real
    position), so that every test shares one compiled reference."""
    padded = list(seq) + [0] * (WIDTH - len(seq))
    return np.asarray(ref.sequence_logits(params, REF_CFG, padded))[:len(seq)]


def clear_of_ties(params, seq):
    """Positions of `seq` at which, in every expert layer of the
    reference, the k-th and (k+1)-th biased scores are PICK_GAP apart."""
    x = jnp.asarray(params["wte"][jnp.asarray(seq)], jnp.float32)
    ok = np.ones(len(seq), bool)
    with jax.default_matmul_precision("highest"):
        for lp in params["layers"]:
            x = ref._attention(x, {k: lp[k] for k in ref._ATTN}, REF_CFG)
            if "router" not in lp:
                x = ref._dense_ffn(x, {k: lp[k] for k in
                                       ("norm2", "gate", "up", "down")},
                                   REF_CFG)
                continue
            xh, dense, acc, gap = ref._moe_head(
                x, {k: lp[k] for k in ref._MOE_HEAD}, REF_CFG)
            ok &= np.asarray(gap) >= PICK_GAP
            for e in range(CFG.n_routed_experts):
                acc = ref._expert(acc, xh, dense[:, e], lp["w_gate"][e],
                                  lp["w_up"][e], lp["w_down"][e])
            x = x + acc
    # a tie at one position changes every later one through attention
    return np.minimum.accumulate(ok)


def test_forward_logits_match_reference(params):
    seq = tokens_of(0, 40)
    got = np.asarray(ml.forward_logits(params, CFG, jnp.asarray(seq)))
    want = reference_logits(params, seq)
    clear = clear_of_ties(params, seq)
    assert clear.sum() >= 30, f"{(~clear).sum()} positions left out for ties"
    assert want.std() > 0.1
    assert np.abs(got - want)[clear].max() <= LOGIT_ATOL


def test_router_matches_reference_and_bias_moves_picks_not_weights(params):
    lp = params["layers"][1]
    x = jnp.asarray(np.random.default_rng(5).normal(0, 1, (64, CFG.hidden)),
                    jnp.float32)
    picks, w = ex.route(CFG, lp, x)
    with jax.default_matmul_precision("highest"):
        r_picks, r_w, _ = ref.router(x, lp["router"], lp["router_bias"],
                                     REF_CFG)
        scores = np.asarray(jax.nn.sigmoid(x @ lp["router"]))
    s = np.sort(scores + np.asarray(lp["router_bias"]), -1)
    clear = (s[:, -2] - s[:, -3]) >= PICK_GAP
    assert clear.sum() >= 56, f"{(~clear).sum()} of 64 tokens left out"
    assert (np.sort(picks, -1) == np.sort(r_picks, -1))[clear].all()
    order, r_order = np.argsort(picks, -1), np.argsort(r_picks, -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(w), order, -1)[clear],
        np.take_along_axis(np.asarray(r_w), r_order, -1)[clear], atol=1e-6)
    # a large bias on one expert pulls it into (nearly) every token's
    # picks, and each weight is still the UNBIASED score over the sum
    biased = dict(lp, router_bias=lp["router_bias"].at[3].add(5.0))
    b_picks, b_w = ex.route(CFG, biased, x)
    assert (np.asarray(b_picks) == 3).any(-1).all()
    assert not (np.asarray(picks) == 3).any(-1).all()
    at = np.take_along_axis(scores, np.asarray(b_picks), -1)
    np.testing.assert_allclose(
        np.asarray(b_w), at / at.sum(-1, keepdims=True) * 2.446, rtol=1e-5)
    assert np.asarray(b_w).max() < 2.446      # no 5.0 leaked into a weight


def test_grouped_product_against_expert_loop(params):
    """An expert with no token, one with every token, rows of nobody:
    the layout by counting, the product over it, and the rows read back
    by the same positions."""
    from paddle_tpu.ops.grouped_swiglu import (padded_rows, routed_positions,
                                               row_tile_for)
    lp = params["layers"][2]
    rng = np.random.default_rng(11)
    T, E = 12, CFG.n_routed_experts
    x = jnp.asarray(rng.normal(0, 1, (T, CFG.hidden)), jnp.float32)
    # every token's first pick is expert 5; expert 2 gets nobody; the
    # last two tokens are not live
    second = rng.choice([0, 1, 3, 4, 6, 7], T)
    picks = np.stack([np.full(T, 5), second], -1)
    live = np.arange(T) < T - 2
    tile = row_tile_for(T * 2, E)
    pos, sizes = routed_positions(jnp.asarray(picks, jnp.int32),
                                  jnp.asarray(live), E, tile)
    pos, sizes = np.asarray(pos), np.asarray(sizes)
    assert sizes[5] == T - 2 and sizes[2] == 0
    rows = padded_rows(T * 2, E, tile)
    assert (pos[~live] == rows).all() and (pos[live] < rows).all()
    assert len(set(pos[live].reshape(-1))) == (T - 2) * 2     # a row each
    # nobody's rows hold poison: a product's row reads its own row alone
    xs = np.full((rows, CFG.hidden), np.nan, np.float32)
    xs[pos[live]] = np.asarray(x)[live][:, None, :]
    ys = np.asarray(ex.grouped_experts(lp, jnp.asarray(xs),
                                       jnp.asarray(sizes), tile))
    for t in range(T - 2):
        for j in range(2):
            e = picks[t, j]
            want = ref._swiglu(x[t], lp["w_gate"][e], lp["w_up"][e],
                               lp["w_down"][e])
            np.testing.assert_allclose(ys[pos[t, j]], want, atol=2e-5)


def test_no_dense_product_and_no_dropped_token(params, monkeypatch):
    """The rows that enter the expert product are tokens x k exactly, in
    a buffer of the static size the layout names."""
    from paddle_tpu.ops.grouped_swiglu import padded_rows, row_tile_for
    seen = []
    real = ex.grouped_experts

    def spy(lp, xs, sizes, tile, packed=False):
        seen.append((xs.shape[0], int(np.asarray(sizes).sum()), tile))
        return real(lp, xs, sizes, tile, packed)

    monkeypatch.setattr(ex, "grouped_experts", spy)
    T = 23
    ml.forward_logits(params, CFG, jnp.asarray(tokens_of(1, T)))
    k, E = CFG.experts_per_tok, CFG.n_routed_experts
    tile = row_tile_for(T * k, E)
    assert seen == [(padded_rows(T * k, E, tile), T * k, tile)] \
        * (CFG.layers - CFG.first_k_dense)


def test_the_expert_kernel_is_the_same_layer_and_is_counted(
        params, monkeypatch):
    """The expert layer with the grouped kernel as its product (the
    TPU's path, interpreted here) against the `ragged_dot` fallback on
    the same tokens, two of them not live: the same output;
    `kernel_passes` counts the pass and `rows_computed` the visits'
    whole tiles on the one and not on the other."""
    from paddle_tpu.ops.grouped_swiglu import row_tile_for
    lp = params["layers"][1]
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(0, 1, (21, CFG.hidden)), jnp.float32)
    live = jnp.arange(21) < 19
    assert ex.expert_product_path(lp) == "ragged_dot"       # the CPU
    want, fallback = ex.moe(CFG, lp, x, live)
    monkeypatch.setattr(ex, "expert_product_path",
                        lambda lp: "grouped_swiglu_kernel")
    got, kernel = ex.moe(CFG, lp, x, live)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert int(kernel["kernel_passes"]) == int(kernel["moe_passes"]) == 1
    assert int(fallback["kernel_passes"]) == 0
    np.testing.assert_array_equal(kernel["expert_tokens"],
                                  fallback["expert_tokens"])
    tile = row_tile_for(21 * CFG.experts_per_tok, CFG.n_routed_experts)
    sizes = np.asarray(kernel["expert_tokens"])
    assert int(kernel["rows_computed"]) == (-(-sizes // tile)).sum() * tile
    assert int(kernel["rows_computed"]) >= sizes.sum() == 19 * 2
    assert int(fallback["rows_computed"]) == 0


def _moe_by_experts(cfg, lp, x, live):
    """The expert layer one token and one expert at a time, float32, in
    numpy: the picks and weights are `route`'s (the router has its own
    tests), everything after them is recomputed."""
    picks, w = (np.asarray(a) for a in ex.route(cfg, lp, x))
    x = np.asarray(x, np.float32)

    def swiglu(row, gate, up, down):
        g = row @ np.asarray(gate, np.float32)
        return (g / (1.0 + np.exp(-g)) * (row @ np.asarray(up, np.float32))) \
            @ np.asarray(down, np.float32)

    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        if live[t]:
            for j, e in enumerate(picks[t]):
                out[t] += w[t, j] * swiglu(x[t], lp["w_gate"][e],
                                           lp["w_up"][e], lp["w_down"][e])
        if cfg.n_shared_experts:
            out[t] += swiglu(x[t], lp["shared_gate"], lp["shared_up"],
                             lp["shared_down"])
    return out


def _wide_layer(lp, hidden):
    """`lp` (an expert layer of CFG's) at `hidden` lanes in bfloat16, the
    type and width the combine kernel takes: seeded matrices of the
    widths' own shapes, the router's bias kept."""
    rng = np.random.default_rng(hidden)
    E, _, F = lp["w_gate"].shape
    shapes = {"router": (hidden, E), "w_gate": (E, hidden, F),
              "w_up": (E, hidden, F), "w_down": (E, F, hidden),
              "shared_gate": (hidden, F), "shared_up": (hidden, F),
              "shared_down": (F, hidden)}
    wide = {name: jnp.asarray(rng.normal(0, 0.1, shape), jnp.float32)
            .astype(jnp.bfloat16) for name, shape in shapes.items()}
    return dict(wide, router_bias=lp["router_bias"])


@pytest.mark.parametrize("product", ["ragged_dot", "grouped_swiglu_kernel",
                                     "row_dma_kernel"])
@pytest.mark.parametrize("shared", [1, 0], ids=["shared", "no_shared"])
@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_the_expert_layer_against_a_loop_over_experts(
        params, monkeypatch, scoring, shared, product):
    """`_moe` whole (layout by counting, product, weighted sum by the
    same positions) under both scoring rules, with and without a shared
    expert, through the interpreted kernel and through `ragged_dot`; the
    last three tokens are not live and get the shared expert alone.
    "row_dma_kernel": the grouped kernel's packed rows read back by the
    combine kernel (`combine_path`, its threshold at 0), on a bfloat16
    layer of 256 lanes, to bfloat16's tolerance: the activation and every
    product are rounded to 8 bits where the loop keeps float32."""
    import types
    cfg = types.SimpleNamespace(
        experts_per_tok=3, n_routed_experts=CFG.n_routed_experts,
        n_shared_experts=shared, router_scoring=scoring,
        routed_scaling_factor=CFG.routed_scaling_factor)
    lp, hidden, dtype, atol = params["layers"][1], CFG.hidden, jnp.float32, 2e-5
    by_dma = product == "row_dma_kernel"
    if by_dma:
        product, hidden, dtype, atol = "grouped_swiglu_kernel", 256, \
            jnp.bfloat16, 3e-2
        lp = _wide_layer(lp, hidden)
        monkeypatch.setattr(ex, "COMBINE_KERNEL_FROM", 0)
    x = jnp.asarray(np.random.default_rng(17).normal(0, 1, (29, hidden)),
                    jnp.float32).astype(dtype)
    live = np.arange(29) < 26
    monkeypatch.setattr(ex, "expert_product_path", lambda lp: product)
    got, counters = ex.moe(cfg, lp, x, jnp.asarray(live))
    want = _moe_by_experts(cfg, lp, x, live)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=atol,
                               rtol=2e-2 if by_dma else 1e-7)
    assert int(counters["expert_tokens"].sum()) == 26 * 3
    assert int(counters["combine_kernel_passes"]) == int(by_dma)
    if not shared:
        assert not np.asarray(got, np.float32)[~live].any()


def _primitives(jaxpr):
    """The names of every primitive in a jaxpr and in the jaxprs it holds."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    names |= _primitives(sub)
    return names


def test_the_expert_layer_sorts_nothing(params):
    """The layout comes from counting: no sort (an `argsort` is one) in
    the layer's jaxpr, at a prompt's row count or at a step's; what
    moves the rows is one scatter of integers and gathers."""
    lp = params["layers"][1]
    for T in (8, 700):
        names = _primitives(jax.make_jaxpr(
            lambda x, live: ex.moe(CFG, lp, x, live))(
                jnp.zeros((T, CFG.hidden)), jnp.ones((T,), bool)).jaxpr)
        assert not [n for n in names if "sort" in n]
        assert {"gather", "scatter", "ragged_dot_general"} <= names \
            or {"gather", "scatter", "ragged_dot"} <= names


def test_absorbed_step_matches_expanded(params):
    """For one query against the same cached rows: the absorbed score
    q_lat . c + q_rope . k_rope and context (sum p c) W_UV equal the
    expanded q . k and sum p v."""
    lp = params["layers"][1]
    rng = np.random.default_rng(2)
    T = 9
    x = jnp.asarray(rng.normal(0, 1, (T, CFG.hidden)), jnp.float32)
    pos = jnp.arange(T)
    q_nope, q_rope, c, k_rope = _latent.project(CFG, lp, x, pos)
    k, v = _latent.expand(CFG, lp, c, k_rope)
    scale = 1.0 / np.sqrt(CFG.qk_head_dim)
    q = jnp.concatenate([q_nope, q_rope], -1)[-1:]           # the last token
    s_exp = jnp.einsum("qnd,knd->nqk", q, k) * scale
    p = jax.nn.softmax(s_exp, -1)
    o_exp = jnp.einsum("nqk,knd->qnd", p, v)[0]
    w_uk, w_uv = _latent.wkvb_heads(CFG, lp)
    q_lat = jnp.einsum("snd,cnd->snc", q_nope[-1:], w_uk)
    pad = jnp.zeros((1, CFG.heads, CFG.row_width - CFG.row_values))
    q_ext = jnp.concatenate([q_lat, q_rope[-1:], pad], -1) * scale
    rows = _latent.cache_rows(CFG, c, k_rope)
    s_abs = jnp.einsum("snw,lw->snl", q_ext, rows)[0]
    np.testing.assert_allclose(s_abs, s_exp[:, 0], atol=2e-6)
    o_ext = _latent.absorbed_attention(q_ext, rows[None], jnp.ones((1, T), bool))
    o_abs = jnp.einsum("snc,cnd->snd", o_ext[..., :CFG.kv_lora_rank], w_uv)[0]
    np.testing.assert_allclose(o_abs, o_exp, atol=2e-6)


def _latent_case(pages_live, at_rows, frozen, P, bs, heads, W,
                 dtype=jnp.float32, seed=4):
    """One pool: slot i holds pages_live[i] live pages with its new row at
    row at_rows[i] of the live one (-1: the page's last row); the slots
    named in `frozen` are done. Pages are dealt out of one permutation, so
    no two slots share a block and block 0 is the scratch block."""
    rng = np.random.default_rng(seed)
    S = len(pages_live)
    arena = jnp.asarray(rng.normal(0, 1, (2, 1, 1 + S * P, 1, bs, W)), dtype)
    pt = jnp.asarray(1 + rng.permutation(S * P).reshape(S, P), jnp.int32)
    ts = jnp.asarray([(n - 1) * bs + (r % bs)
                      for n, r in zip(pages_live, at_rows)], jnp.int32)
    done = jnp.asarray([i in frozen for i in range(S)])
    q = jnp.asarray(rng.normal(0, 0.3, (S, heads, W)), dtype)
    row = jnp.asarray(rng.normal(0, 1, (S, W)), dtype)
    return q, row, arena, pt, ts, done


def _latent_cases():
    from paddle_tpu.ops.paged_attention import _latent_walk
    W, n = CFG.row_width, CFG.heads
    # P is NOT a multiple of G, so the longest slot ends in a short tail
    P, bs = 11, 8
    G, _ = _latent_walk(n, P, W, bs, 4)
    assert G > 2 and P % G and 2 * G + 1 <= P, (G, P)
    return {
        # PR 27's pool: a slot on a page's first row (0, 4), on its last
        # (3, 15), a frozen one; four pages a slot are ONE tail each
        "five-slots": dict(case=dict(
            pages_live=[1, 1, 2, 3, 4], at_rows=[0, 3, 0, 1, 3], frozen=[3],
            P=4, bs=4, heads=n, W=W)),
        # every boundary of the walk in groups of G: 1, G-1, G, G+1, 2G+1
        # and P live pages, the new row on a page's first and last row,
        # a frozen slot between two live ones (the slot before it must
        # start the pages of the slot AFTER it), a frozen slot last
        "walk-boundaries": dict(case=dict(
            pages_live=[1, G - 1, G, G, G + 1, 2 * G + 1, P, 2 * G, 1, 3],
            at_rows=[0, -1, 0, 5, -1, 0, -1, -1, 3, 2], frozen=[3, 9],
            P=P, bs=bs, heads=n, W=W)),
        "frozen-first-and-all-but-one": dict(case=dict(
            pages_live=[2, 2, G + 2, 2], at_rows=[1, 1, 0, 1],
            frozen=[0, 1, 3], P=P, bs=bs, heads=n, W=W)),
        # a table of ONE page: the walk has no whole group at all
        "one-page-table": dict(case=dict(
            pages_live=[1, 1, 1], at_rows=[0, -1, 2], frozen=[],
            P=1, bs=bs, heads=n, W=W)),
        # the serving tile, (128, 640), at both models' head counts
        "real-tile-16-heads": dict(case=dict(
            pages_live=[1, 5, 6, 4], at_rows=[0, -1, 77, 16], frozen=[],
            P=6, bs=128, heads=16, W=640)),
        "real-tile-32-heads": dict(case=dict(
            pages_live=[6, 1, 5], at_rows=[-1, 100, 0], frozen=[1],
            P=6, bs=128, heads=32, W=640)),
        # the arena's own type: the new row goes into a packed tile of 16
        # rows; probabilities are narrowed to bfloat16 on both sides
        "real-tile-bfloat16": dict(atol=2e-2, case=dict(
            pages_live=[5, 1, 6], at_rows=[15, 16, -1], frozen=[],
            P=6, bs=128, heads=16, W=640, dtype=jnp.bfloat16)),
    }


@pytest.mark.parametrize("name", list(_latent_cases()))
def test_latent_kernel_matches_gather(name):
    """ops/paged_attention.latent_paged_attention (interpreted) against a
    gather and two einsums over the same arena, through TWO decode steps
    of two layers each on the arena the call before returned: what a call
    wrote late (the live page's deferred write-back) is what the next
    call reads, and the second step crosses into a new page where the
    first one ended a page."""
    from paddle_tpu.ops.paged_attention import latent_paged_attention
    spec = _latent_cases()[name]
    q, row, arena, pt, ts, done = _latent_case(**spec["case"])
    S, P = pt.shape
    bs, W = arena.shape[4], arena.shape[5]
    live = ~np.asarray(done)
    want_arena = arena
    for step in (0, 1):
        ts_now = jnp.minimum(ts + step, P * bs - 1)
        for li in (0, 1):
            # another row a call, so a stale page cannot pass for a new one
            row_now = jnp.roll(row, 2 * step + li, axis=-1)
            out, arena = latent_paged_attention(q, row_now, arena, li, pt,
                                                ts_now, done)
            wblk = jnp.where(done, 0, pt[jnp.arange(S), ts_now // bs])
            want_arena = want_arena.at[li, 0, wblk, 0, ts_now % bs].set(
                row_now)
            cached = want_arena[li, 0, pt, 0].reshape(S, P * bs, W)
            want = _latent.absorbed_attention(
                q, cached, jnp.arange(P * bs)[None] <= ts_now[:, None])
            np.testing.assert_allclose(
                np.asarray(out, np.float32)[live],
                np.asarray(want, np.float32)[live],
                atol=spec.get("atol", 2e-6))
            assert not np.asarray(out, np.float32)[~live].any()
            # the kernel wrote the live slots' rows and nothing else (the
            # gather's frozen slot dirties the scratch block, the
            # kernel's writes nowhere)
            np.testing.assert_array_equal(
                np.asarray(arena, np.float32)[:, :, 1:],
                np.asarray(want_arena, np.float32)[:, :, 1:])


def test_latent_walk_is_a_function_of_shapes():
    """Pages a step and buffers in flight follow from (heads, pages, W,
    block_size, itemsize) alone: the cells' shapes give what PERF.md
    records (PR 32), a short table a shorter group, and nothing a caller
    or the environment sets reaches the choice."""
    import inspect
    from paddle_tpu.ops import paged_attention as pa
    assert pa._latent_walk(16, 64, 640, 128, 2) == (4, 3)     # Moonlight
    assert pa._latent_walk(32, 128, 640, 128, 2) == (4, 3)    # Xing
    assert pa._latent_walk(16, 1, 640, 128, 2)[0] == 1
    assert pa._latent_walk(16, 3, 640, 128, 2)[0] == 2
    # a page so large that four of them would not fit the buffers
    group, buffers = pa._latent_walk(16, 64, 1024, 256, 4)
    assert group < 4 and buffers * group * 256 * 1024 * 4 <= pa._LATENT_VMEM
    assert list(inspect.signature(pa._latent_walk).parameters) == [
        "heads", "pages", "w", "block_size", "itemsize"]
    assert list(inspect.signature(pa.latent_paged_attention).parameters) == [
        "q", "row", "arena", "layer", "pt", "ts", "done"]
    assert list(inspect.signature(pa._latent_call).parameters) == [
        "q", "new", "arena", "layer", "pt", "lengths", "interpret"]
    assert "environ" not in inspect.getsource(pa)


def test_flash_forward_at_unequal_widths():
    """The prefill's kernel through the entry point the latent block
    calls: q, k of one width and v of another, rows as the projections
    leave them."""
    from paddle_tpu.ops.flash_attention import (flash_causal_rows,
                                                mha_reference)
    rng = np.random.default_rng(6)
    n, s, d, dv = 2, 256, 24, 16
    q, k = (jnp.asarray(rng.normal(0, 1, (s, n, d)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(0, 1, (s, n, dv)), jnp.float32)
    o = flash_causal_rows(q, k, v, 1.0 / np.sqrt(d))
    # mha_reference wants equal widths: zero-extend v and cut the answer
    vz = jnp.concatenate([v, jnp.zeros((s, n, d - dv))], -1)
    want = mha_reference(q[None], k[None], vz[None], causal=True)[0]
    np.testing.assert_allclose(o, want[..., :dv], atol=2e-5)


_PREFILL = jax.jit(lambda params, *a: ml.prefill_pages(params, CFG, *a))
_DECODE = {path: jax.jit(lambda params, *a, path=path: ml.decode_step_pages(
    params, CFG, *a, attention=path)) for path in ("gather",
                                                   "latent_paged_kernel")}


def _prefill(params, arena, kv, slot, prompt, bucket, pfx_len=0):
    padded = np.zeros((1, bucket), np.int32)
    suffix = prompt[pfx_len:]
    padded[0, :len(suffix)] = suffix
    logits, arena, _ = _PREFILL(
        params, jnp.asarray(padded), jnp.int32(pfx_len),
        jnp.int32(len(suffix)), arena, jnp.asarray(kv.page_table[slot]))
    return np.asarray(logits[0]), arena


@pytest.mark.parametrize("attention", ["gather", "latent_paged_kernel"])
def test_prefill_then_decode_matches_reference_at_every_position(
        params, attention):
    """A pool of four slots at different lengths: prompts of 5, 6 (one
    page and a half: it crosses a page boundary) and 9 tokens, slot 1
    FROZEN through the decode steps, slot 3 holding a stale sequence
    first and then REUSED by a new one. Every decode step's logits of
    every live slot against the reference's full forward pass."""
    bs, steps = 4, 6
    kv = SlotKVCache(CFG, 4, 32, jnp.float32, block_size=bs,
                     prefix_cache=False)
    arena = kv.arena
    prompts = {0: tokens_of(20, 5), 1: tokens_of(21, 7), 2: tokens_of(22, 6),
               3: tokens_of(23, 9)}
    stale = tokens_of(24, 11)
    slots = {i: kv.alloc() for i in range(4)}
    # slot 3's previous life: fill its pages, then free and re-map them
    kv.map_slot(3, stale, len(stale) + steps, register=False)
    _, arena = _prefill(params, arena, kv, 3, stale, 16)
    kv.free(3)
    assert kv.alloc() == 3
    # teacher-forced: each slot's continuation is drawn beforehand, so the
    # reference runs ONCE per sequence and gives every position's logits
    seqs = {s: list(p) + list(tokens_of(50 + s, steps))
            for s, p in prompts.items()}
    want = {s: reference_logits(params, seq) for s, seq in seqs.items()}
    clear = {s: clear_of_ties(params, seq) for s, seq in seqs.items()}
    for s, prompt in prompts.items():
        kv.map_slot(s, prompt, len(prompt) + steps, register=False)
        logits, arena = _prefill(params, arena, kv, s, prompt, 16)
        assert np.abs(logits - want[s][len(prompt) - 1]).max() <= LOGIT_ATOL
    pt = jnp.asarray(kv.page_table)
    done = jnp.asarray([False, True, False, False])
    ts = jnp.asarray([len(prompts[s]) for s in range(4)], jnp.int32)
    own = kv.page_table[1][:kv.mapped_block_count(1)]    # not the scratch
    frozen_rows = np.asarray(arena[:, 0, own])
    left_out = 0
    for i in range(steps):
        tok = jnp.asarray([seqs[s][len(prompts[s]) + i] for s in range(4)],
                          jnp.int32)
        logits, arena, counters = _DECODE[attention](
            params, tok, arena, pt, ts, done)
        for s in (0, 2, 3):
            at = len(prompts[s]) + i
            if clear[s][at]:
                assert np.abs(np.asarray(logits[s]) - want[s][at]).max() \
                    <= LOGIT_ATOL, (s, at)
            else:
                left_out += 1
        assert int(counters["router_tokens"]) == 3 * 2   # frozen: not routed
        ts = jnp.where(done, ts, ts + 1)
    assert left_out <= 3
    # the frozen slot's pages were never written
    np.testing.assert_array_equal(np.asarray(arena[:, 0, own]), frozen_rows)


def test_prefill_after_a_prefix_hit_matches_a_cold_prefill(params):
    """The warm branch (attention over the gathered page row): the last
    logits of a prompt whose first two pages are already cached."""
    bs = 4
    kv = SlotKVCache(CFG, 2, 32, jnp.float32, block_size=bs,
                     prefix_cache=False)
    prompt = tokens_of(30, 13)
    for s in (kv.alloc(), kv.alloc()):
        kv.map_slot(s, prompt, 20, register=False)
    cold, arena = _prefill(params, kv.arena, kv, 0, prompt, 16)
    # slot 1: the first 8 positions through one prefill, the rest warm
    _, arena = _prefill(params, arena, kv, 1, prompt[:8], 8)
    warm, arena = _prefill(params, arena, kv, 1, prompt, 8, pfx_len=8)
    assert np.abs(warm - cold).max() <= LOGIT_ATOL
    assert np.abs(cold - reference_logits(params, prompt)[-1]).max() \
        <= LOGIT_ATOL


def _engine(params, **kw):
    kw = dict(dict(num_slots=3, prefill_buckets=(8, 16), max_len=48,
                   block_size=4, decode_chunk=4), **kw)
    return ServingEngine(params, CFG, ServingConfig(**kw))


def test_engine_serves_moonlight_and_counts_without_another_sync(
        params, monkeypatch):
    """The normal path: ServingEngine over the Moonlight tree, greedy
    tokens the reference's best at every step, the counters exact, and
    their fetch riding the fetches there already were: one device_get a
    tick that admitted (with its first tokens) and one a collected
    chunk."""
    eng = _engine(params)
    fetches = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: fetches.append(1) or real(x))
    prompts = [tokens_of(40 + i, n) for i, n in enumerate((5, 11, 7, 9))]
    reqs = [eng.submit(p, 7) for p in prompts]
    eng.run_until_drained()
    stats = eng.stats()
    assert stats["model"] == "Moonlight-16B-A3B"
    assert stats["cache_row_bytes"] == CFG.row_width * 4
    assert stats["decode_attention"] == "gather"        # the CPU
    assert stats["compiled_executables"] == 2 + 2       # 2 buckets
    assert stats["first_tokens"] == stats["prefills"] == 4
    assert len(fetches) == stats["first_token_waits"] + stats["dispatches"]
    assert stats["first_token_waits"] < stats["prefills"]   # 3 slots a tick
    tokens = sum(len(p) for p in prompts) + 4 * 6       # prefilled + decoded
    n_moe, k = CFG.layers - CFG.first_k_dense, CFG.experts_per_tok
    assert stats["router_tokens"] == tokens * n_moe
    assert sum(stats["expert_tokens"]) == tokens * n_moe * k
    assert stats["decode_router_tokens"] == 4 * 6 * n_moe
    assert stats["decode_moe_passes"] <= stats["dispatches"] * 4 * n_moe
    assert stats["moe_kernel_passes"] == 0       # the CPU: `ragged_dot` ran
    assert stats["moe_rows_computed"] == 0       # so the kernel computed none
    assert stats["moe_combine_kernel_passes"] == 0   # and XLA's gather combined
    for prompt, req in zip(prompts, reqs):
        seq = list(prompt) + list(req.tokens)
        rows = reference_logits(params, seq)[len(prompt) - 1:-1]
        deficit = rows.max(-1) - rows[np.arange(7), req.tokens]
        assert deficit.max() <= 2 * LOGIT_ATOL
    eng.close()


@pytest.mark.parametrize("option", [
    dict(weight_dtype="int8"), dict(kv_dtype="int8"),
    dict(max_adapters=2, adapter_rank=2), dict(speculate_k=2),
    dict(mesh_shape=(2,)), dict(prefill_chunk=8)])
def test_moonlight_engine_refuses_what_the_model_lacks(params, option):
    with pytest.raises(ValueError, match="does not implement"):
        _engine(params, **option)


@pytest.mark.parametrize("where", ["norm", "router"])
def test_the_tolerance_catches_bfloat16_where_float32_is_stated(
        params, monkeypatch, where):
    """The norm's statistics or the router's scores in bfloat16 move the
    logits past LOGIT_ATOL: the comparison above would fail."""
    if where == "norm":
        def rms16(x, g, eps):
            x16 = x.astype(jnp.bfloat16)
            inv = jax.lax.rsqrt(jnp.mean(x16 * x16, -1, keepdims=True)
                                + jnp.bfloat16(eps))
            return (x16 * inv).astype(x.dtype) * g
        monkeypatch.setattr(dec, "rms", rms16)
    else:
        real = ex.route

        def route16(cfg, lp, x):
            picks, w = real(cfg, lp, x)
            return picks, w.astype(jnp.bfloat16).astype(jnp.float32)
        monkeypatch.setattr(ex, "route", route16)
    seq = tokens_of(0, 40)
    got = np.asarray(ml.forward_logits(params, CFG, jnp.asarray(seq)))
    want = reference_logits(params, seq)
    clear = clear_of_ties(params, seq)
    assert np.abs(got - want)[clear].max() > 10 * LOGIT_ATOL
