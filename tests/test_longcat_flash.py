"""LongCat-Flash's language model (paddle_tpu.models.longcat_flash) at a
small size on the CPU: a double layer of two latent attentions and two dense
feed-forwards with one expert layer on a shortcut, a router over routed
experts of which the chip holds a SHARE and identity experts that cost
nothing.

The reference is benchmarks/reference/longcat_flash_ref.py (float32, highest
precision, a head and an expert at a time, independent of the program), given
the same held range. Pinned here: the served math against the reference,
without a cache and through the pages; the router's three properties; cache
layer 2l + i; the shares add up with the identity term counted once; the
counters; every WRONG program differs; every refusal by name."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from reference import longcat_flash_ref as ref               # noqa: E402

from paddle_tpu.models import _experts as ex                 # noqa: E402
from paddle_tpu.models import _latent                        # noqa: E402
from paddle_tpu.models import longcat_flash as lf            # noqa: E402
from paddle_tpu.serving import (ServingConfig, ServingEngine,  # noqa: E402
                                SlotKVCache)
from paddle_tpu.serving.model import (CacheSpec, require_features,  # noqa: E402
                                      serving_model)

BS, E, Z, K, HELD = 4, 8, 4, 4, (2, 2)
# the bias at the scores' own size (softmax over 12 outputs: about 0.08), so
# that it changes picks and a program that weighs with it is another function
SIZES = dict(vocab_size=96, hidden=64, layers=2, heads=4, q_lora_rank=24,
             kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, intermediate=96, moe_intermediate=32,
             n_routed_experts=E, zero_expert_num=Z, experts_per_tok=K,
             routed_scaling_factor=6.0, max_pos=64, init_range=0.08,
             router_bias_std=0.05)
CFG = lf.LongcatFlashConfig(experts_held=HELD, vocab_slice=(96, 96, 768),
                            **SIZES)
WHOLE = lf.LongcatFlashConfig(**SIZES)
# the same model under the published keys, as the reference reads them
REF_CFG = {
    "hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "rms_norm_eps": 1e-5, "rope_theta": 1e7,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True, "moe_topk": K,
    "routed_scaling_factor": 6.0, "zero_expert_num": Z,
    "published": {"n_routed_experts": E}, "experts_held_first": HELD[0]}
# float32 on both sides at the highest precision, two layers: what differs is
# the order of the sums (the program's grouped experts, folded scales and
# batched heads against the reference's loops), a few float32 roundings on
# logits of size 1; a wrong program moves them by 1e-3 and more
LOGIT_ATOL = 5e-5


def _key(seed):
    """A key that carries its generator: the executor's tests switch the
    process's default to rbg, and a raw key would then draw other weights
    when one of them shared this worker first."""
    return jax.random.key(seed, impl="threefry2x32")


def tokens_of(seed, n):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n) \
        .astype(np.int32)


@pytest.fixture(scope="module")
def whole():
    """Every expert's weights; a share's tree is a slice of it."""
    return lf.init_params(WHOLE, _key(0), jnp.float32)


def share_of(whole, first, count):
    layers = [dict(lp, moe=dict(lp["moe"], **{
        name: lp["moe"][name][first:first + count]
        for name in ("w_gate", "w_up", "w_down")})) for lp in whole["layers"]]
    return dict(whole, layers=layers)


@pytest.fixture(scope="module")
def params(whole):
    return share_of(whole, *HELD)


def reference_logits(params, seq, **kw):
    return np.asarray(ref.sequence_logits(params, REF_CFG,
                                          jnp.asarray(seq, jnp.int32), **kw))


def _engine(params, **kw):
    kw.setdefault("num_slots", 3)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("max_len", 48)
    kw.setdefault("block_size", BS)
    return ServingEngine(params, CFG, ServingConfig(**kw))


# -- the config and the weights -------------------------------------------------

def test_config_published_widths_scales_and_the_cache_layers():
    full = lf.LongcatFlashConfig()
    assert (full.row_values, full.row_width) == (576, 640)
    assert full.cache_layers == 56 and ex.router_width(full) == 768
    assert full.mla_q_scale == 2.0
    assert abs(full.mla_kv_scale - 12 ** 0.5) < 1e-12
    off = lf.LongcatFlashConfig(mla_scale_q_lora=False, mla_scale_kv_lora=False)
    assert off.mla_q_scale == off.mla_kv_scale == 1.0
    spec = serving_model(CFG).cache_spec(CFG)
    assert spec == CacheSpec(4, 1, 128)
    with pytest.raises(ValueError, match="experts_held"):
        lf.LongcatFlashConfig(experts_held=(500, 16))
    with pytest.raises(ValueError, match="vocab_slice"):
        lf.LongcatFlashConfig(vocab_size=96, vocab_slice=(0, 64, 768))


def test_init_makes_two_attentions_two_dense_and_only_the_held_experts(
        params, whole):
    lp = params["layers"][1]
    assert len(lp["attn"]) == 2 and len(lp["ffn"]) == 2
    assert lp["attn"][0]["wqa"].shape == (64, 24)
    assert lp["attn"][1]["wqb"].shape == (24, 4 * 24)
    assert lp["ffn"][0]["gate"].shape == (64, 96)
    assert lp["moe"]["router"].shape == (64, E + Z)
    assert lp["moe"]["router_bias"].shape == (E + Z,)
    assert lp["moe"]["router_bias"].dtype == jnp.float32
    assert lp["moe"]["w_gate"].shape == (2, 64, 32)
    assert whole["layers"][1]["moe"]["w_gate"].shape == (E, 64, 32)
    held = lf.init_params(CFG, _key(0), jnp.float32)
    assert held["layers"][0]["moe"]["w_gate"].shape == (2, 64, 32)
    # the two attentions of a layer are not one matrix twice
    assert not bool((lp["attn"][0]["wo"] == lp["attn"][1]["wo"]).all())
    assert params["head"].shape == (64, 96)


# -- the router's three properties ------------------------------------------------

def _router_case(seed=3, tokens=40):
    x = jax.random.normal(_key(seed), (tokens, 64))
    lp = {"router": 0.3 * jax.random.normal(_key(seed + 1),
                                            (64, E + Z)),
          "router_bias": 0.05 * jax.random.normal(_key(seed + 2),
                                                  (E + Z,))}
    return x, lp


def test_the_bias_ranks_and_does_not_weigh():
    x, lp = _router_case()
    picks, w = ex.route(CFG, lp, x)
    p = jax.nn.softmax(x @ lp["router"], -1)
    _, want = jax.lax.top_k(p + lp["router_bias"], K)
    assert np.array_equal(np.asarray(picks), np.asarray(want))
    # some token's picks are not the K largest scores: the bias ranked
    plain = np.sort(np.asarray(jax.lax.top_k(p, K)[1]), -1)
    assert (np.sort(np.asarray(picks), -1) != plain).any()
    # the weights are the scores themselves at the picks, times the factor
    np.testing.assert_allclose(np.asarray(w), 6.0 * np.asarray(
        jnp.take_along_axis(p, picks, -1)), rtol=1e-6)


def test_the_weights_are_not_renormalised():
    x, lp = _router_case(seed=5)
    _, w = ex.route(CFG, lp, x)
    total = np.asarray(w.sum(-1))
    # the factor times the picked scores' mass, which is short of 1
    assert (total < 6.0).all() and total.min() < 4.5
    renormalised = lf.LongcatFlashConfig(**SIZES)
    renormalised.router_renormalize = True
    _, wn = ex.route(renormalised, lp, x)
    np.testing.assert_allclose(np.asarray(wn.sum(-1)), 6.0, rtol=1e-5)


def test_an_identity_pick_adds_its_weight_times_the_input_and_lays_out_no_row():
    """A router whose bias sends EVERY pick to the identity experts: the
    layer's output is (the picks' weights' sum) x its input, no expert has a
    row and the kernel's buffer holds none."""
    x, lp = _router_case(seed=7)
    bias = jnp.where(jnp.arange(E + Z) >= E, 10.0, 0.0)
    mp = {"router": lp["router"], "router_bias": bias,
          "w_gate": jnp.ones((E, 64, 32)), "w_up": jnp.ones((E, 64, 32)),
          "w_down": jnp.ones((E, 32, 64))}
    live = jnp.arange(x.shape[0]) < 33
    y, c = ex.moe(WHOLE, mp, x, live)
    picks, w = ex.route(WHOLE, mp, x)
    assert bool((picks >= E).all())
    np.testing.assert_allclose(np.asarray(y), np.asarray(
        w.sum(-1, keepdims=True) * x), rtol=1e-6)
    assert int(c["expert_tokens"].sum()) == 0 and int(c["rows_computed"]) == 0
    assert int(c["moe_identity_picks"]) == 33 * K
    assert int(c["moe_expert_picks"]) == int(c["moe_held_picks"]) == 0
    assert np.asarray(c["moe_real_picks_hist"]).tolist() == [33, 0, 0, 0, 0]


def test_the_counters_tell_identity_real_and_held_picks_apart(params):
    x, _ = _router_case(seed=9, tokens=50)
    mp = params["layers"][0]["moe"]
    live = jnp.arange(50) < 45
    _, c = ex.moe(CFG, mp, x, live)
    picks = np.asarray(ex.route(CFG, mp, x)[0])[:45]
    assert int(c["moe_identity_picks"]) == int((picks >= E).sum())
    assert int(c["moe_expert_picks"]) == int((picks < E).sum())
    held = (picks >= HELD[0]) & (picks < HELD[0] + HELD[1])
    assert int(c["moe_held_picks"]) == int(held.sum()) \
        == int(c["expert_tokens"].sum())
    hist = np.asarray(c["moe_real_picks_hist"])
    assert hist.sum() == int(c["router_tokens"]) == 45
    assert hist.tolist() == np.bincount((picks < E).sum(-1),
                                        minlength=K + 1).tolist()
    assert (hist * np.arange(K + 1)).sum() == int(c["moe_expert_picks"])


# -- the served math against the reference ----------------------------------------

@pytest.mark.parametrize("length", [5, 16, 41])
def test_forward_matches_the_reference(params, length):
    seq = tokens_of(length, length)
    got = np.asarray(lf.forward_logits(params, CFG, jnp.asarray(seq)))
    assert np.abs(got - reference_logits(params, seq)).max() <= LOGIT_ATOL


def test_forward_of_the_uncut_model_matches_the_uncut_reference(whole):
    seq = tokens_of(3, 20)
    got = np.asarray(lf.forward_logits(whole, WHOLE, jnp.asarray(seq)))
    want = np.asarray(ref.sequence_logits(
        whole, dict(REF_CFG, experts_held_first=0), seq))
    assert np.abs(got - want).max() <= LOGIT_ATOL


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_a_wrong_program_is_another_function(params, wrong):
    seq = tokens_of(11, 30)
    true = reference_logits(params, seq)
    other = reference_logits(params, seq, wrong=wrong)
    assert np.abs(other - true).max() > 20 * LOGIT_ATOL, wrong


def test_the_reference_taps_layer_zeros_shortcut(params):
    seq = tokens_of(13, 24)
    rows = np.arange(4, 20)
    logits, gap, taps = ref.sequence_logits(params, REF_CFG, seq, rows,
                                            gaps=True, tap=True)
    assert logits.shape == (16, 96) and gap.shape == (16,)
    assert taps["u0"].shape == taps["s"].shape == (16, 64)
    assert taps["real"].shape == (2, 16)
    routed, term, _, real = ref.shortcut(taps["u0"], params["layers"][0]["moe"],
                                         REF_CFG)
    np.testing.assert_allclose(np.asarray(routed + term),
                               np.asarray(taps["s"]), atol=1e-6)
    assert np.array_equal(np.asarray(real), np.asarray(taps["real"][0]))
    # the program's own layer on the same rows
    got, _ = ex.moe(CFG, params["layers"][0]["moe"], taps["u0"],
                    jnp.ones((16,), bool))
    err = float(jnp.linalg.norm(got - taps["s"]) / jnp.linalg.norm(taps["s"]))
    assert err <= 1e-5


def test_prefill_then_decode_through_the_pages(params):
    """A prompt of 11 in a bucket of 16, then five greedy steps: the logits
    are the reference's full forward's at every position."""
    kv = SlotKVCache(CFG, 2, 48, jnp.float32, block_size=BS)
    prompt = tokens_of(5, 11)
    slot = kv.alloc()
    row, _ = kv.map_slot(slot, prompt, 11 + 6)
    padded = np.zeros((1, 16), np.int32)
    padded[0, :11] = prompt
    logits, arena, c = lf.prefill_pages(params, CFG, jnp.asarray(padded), 0,
                                        jnp.int32(11), kv.arena,
                                        jnp.asarray(row))
    assert int(c["router_tokens"]) == 11 * CFG.layers
    seq = list(prompt)
    want = reference_logits(params, seq)
    assert np.abs(np.asarray(logits[0]) - want[-1]).max() <= LOGIT_ATOL
    pt = jnp.asarray(kv.page_table)
    for _ in range(5):
        seq.append(int(jnp.argmax(logits[0])))
        logits, arena, c = lf.decode_step_pages(
            params, CFG, jnp.asarray([seq[-1], 0]), arena, pt,
            jnp.asarray([len(seq) - 1, 0]), jnp.asarray([False, True]))
        want = reference_logits(params, seq)
        assert np.abs(np.asarray(logits[0]) - want[-1]).max() <= LOGIT_ATOL
        assert int(c["mla_decode_rows"]) == len(seq) * 4
        assert int(c["moe_real_picks_hist"].sum()) == CFG.layers


def test_attention_i_of_layer_l_owns_cache_layer_2l_plus_i(params):
    """The rows each attention writes differ (their own weights), every one
    of the 4 cache layers is written, and a step that reads attention 1's
    rows where attention 0's belong is another function."""
    kv = SlotKVCache(CFG, 2, 48, jnp.float32, block_size=BS)
    prompt = tokens_of(8, 12)
    slot = kv.alloc()
    row, _ = kv.map_slot(slot, prompt, 20)
    padded = np.zeros((1, 16), np.int32)
    padded[0, :12] = prompt
    _, arena, _ = lf.prefill_pages(params, CFG, jnp.asarray(padded), 0,
                                   jnp.int32(12), kv.arena, jnp.asarray(row))
    first = int(row[0])
    written = np.asarray(arena[:, 0, first, 0])            # (4, BS, W)
    assert all(np.abs(written[i]).max() > 0 for i in range(4))
    assert all(np.abs(written[i] - written[j]).max() > 1e-3
               for i in range(4) for j in range(i))
    # cache layer 2l + i holds what attention i of layer l projects
    pt = jnp.asarray(kv.page_table)
    step = lambda a: np.asarray(lf.decode_step_pages(
        params, CFG, jnp.asarray([7, 0]), a, pt, jnp.asarray([12, 0]),
        jnp.asarray([False, True]))[0][0])
    want = reference_logits(params, list(prompt) + [7])[-1]
    assert np.abs(step(arena) - want).max() <= LOGIT_ATOL
    swapped = arena[jnp.asarray([1, 0, 2, 3])]
    assert np.abs(step(swapped) - want).max() > 20 * LOGIT_ATOL


def test_a_frozen_slots_pages_are_bit_identical_after_a_step(params):
    kv = SlotKVCache(CFG, 3, 48, jnp.float32, block_size=BS)
    rows = []
    for seed in (1, 2):
        slot = kv.alloc()
        rows.append(kv.map_slot(slot, tokens_of(seed, 6), 20)[0])
    arena = jax.random.normal(_key(0), kv.arena.shape,
                              kv.arena.dtype)
    pt = jnp.asarray(kv.page_table)
    _, after, _ = lf.decode_step_pages(
        params, CFG, jnp.asarray([3, 4, 5]), arena, pt,
        jnp.asarray([6, 6, 0]), jnp.asarray([False, True, True]))
    frozen = [int(b) for b in rows[1] if b]
    live = int(rows[0][6 // BS])
    assert bool((after[:, :, frozen] == arena[:, :, frozen]).all())
    assert not bool((after[:, :, live] == arena[:, :, live]).all())


# -- the shares add up ---------------------------------------------------------------

def test_the_shares_add_up_with_the_identity_term_once(whole):
    """8 experts + 4 identity experts over 4 shares of 2: the four shares'
    routed parts plus the identity term ONCE (every chip computes it alike,
    as a shared expert) equal the uncut reference's layer; and the program's
    layer of each share is that share's routed part plus the identity term."""
    x = jax.random.normal(_key(4), (24, 64))
    mp = whole["layers"][0]["moe"]
    uncut = dict(REF_CFG, experts_held_first=0)
    with jax.default_matmul_precision("highest"):
        routed_all, term, _, _ = ref.shortcut(x, mp, uncut)
        total = jnp.zeros_like(x)
        live = jnp.ones((24,), bool)
        for first in range(0, E, 2):
            share = {k: (v[first:first + 2] if k.startswith("w_") else v)
                     for k, v in mp.items()}
            routed, term_here, _, _ = ref.shortcut(x, share, uncut,
                                                   held=(first, 2))
            assert np.array_equal(np.asarray(term_here), np.asarray(term))
            total = total + routed
            cfg = lf.LongcatFlashConfig(experts_held=(first, 2), **SIZES)
            got, _ = ex.moe(cfg, share, x, live)
            assert float(jnp.abs(got - (routed + term)).max()) <= 2e-5
        assert float(jnp.abs(total - routed_all).max()) <= 2e-5
        whole_layer, _ = ex.moe(WHOLE, mp, x, live)
        assert float(jnp.abs(whole_layer - (total + term)).max()) <= 2e-5
    assert float(jnp.abs(term).max()) > 1e-2 and float(jnp.abs(total).max()) > 1e-2


# -- through the engine ----------------------------------------------------------------

def test_the_engine_serves_it_and_greedy_tokens_are_the_references(params):
    eng = _engine(params)
    prompts = [tokens_of(s, n) for s, n in ((1, 5), (2, 11), (3, 16))]
    outs = eng.generate([p.tolist() for p in prompts], max_new_tokens=6)
    for prompt, full in zip(prompts, outs):
        seq, out = list(full), list(full[len(prompt):])
        assert len(out) == 6 and seq[:len(prompt)] == list(prompt)
        want = reference_logits(params, seq)
        rows = want[len(prompt) - 1:len(seq) - 1]
        picked = rows[np.arange(len(out)), np.asarray(out)]
        assert (rows.max(-1) - picked).max() <= LOGIT_ATOL
    s = eng.stats()
    assert s["model"] == "LongCat-Flash-Omni"
    assert s["experts_held"] == {"first": 2, "count": 2, "of": E}
    assert s["identity_experts"] == Z and s["router_width"] == E + Z
    assert s["cache_layers"] == 4
    counted = {k: np.asarray(v) for k, v in eng.scheduler.model_counters.items()}
    assert counted["moe_real_picks_hist"].sum() == counted["router_tokens"]
    assert counted["moe_picks_routed"] == counted["router_tokens"] * K
    assert counted["moe_identity_picks"] + counted["moe_expert_picks"] \
        == counted["moe_picks_routed"]
    assert counted["moe_picks_held"] == counted["expert_tokens"].sum()


@pytest.mark.parametrize("option, named", [
    (dict(weight_dtype="int8"), "int8_weights"),
    (dict(kv_dtype="int8"), "int8_kv"),
    (dict(max_adapters=2, adapter_rank=4), "adapters"),
    (dict(speculate_k=2), "speculation"),
    (dict(mesh_shape=(1, 2)), "mesh"),
    (dict(prefill_chunk=8), "prefill_chunk"),
])
def test_what_is_not_built_is_refused_by_name(option, named):
    serving = ServingConfig(num_slots=2, max_len=48, block_size=BS, **option)
    with pytest.raises(ValueError, match=named):
        require_features(serving_model(CFG), serving, CFG)


def test_host_swap_parks_all_eight_cache_layers_and_the_stream_is_the_same(params):
    """An over-subscribed arena with `preempt=True`: a sequence's pages of
    EVERY cache layer (two a model layer) go to the host and come back, and
    its greedy stream is the unpressured engine's."""
    prompts = [tokens_of(s, n).tolist() for s, n in ((1, 5), (2, 7), (3, 6),
                                                     (4, 4), (5, 7))]
    calm = _engine(params, num_slots=4, decode_chunk=4).generate(
        prompts, max_new_tokens=12)
    eng = _engine(params, num_slots=4, kv_blocks=12, decode_chunk=4,
                  preempt=True)
    tight = eng.generate(prompts, max_new_tokens=12)
    assert eng.stats()["preemptions"] >= 1
    for a, b in zip(calm, tight):
        assert list(a) == list(b)
    assert eng.stats()["blocks_used"] == 0


def test_a_prefix_hit_prefills_warm_and_serves_the_references_tokens(params):
    """Two prompts that share their first 8 tokens (two blocks): the second
    takes the hit, its suffix attends the gathered page row of BOTH attentions
    of every layer, and its tokens are the reference's."""
    shared = tokens_of(21, 8).tolist()
    eng = _engine(params)
    first = eng.generate([shared + tokens_of(22, 3).tolist()], max_new_tokens=3)
    prompt = shared + tokens_of(23, 5).tolist()
    out = eng.generate([prompt], max_new_tokens=5)[0]
    assert eng.stats()["prefix_cache_hits"] >= 1 and len(first[0]) == 14
    seq = list(out)
    want = reference_logits(params, seq)
    rows = want[len(prompt) - 1:len(seq) - 1]
    picked = rows[np.arange(5), np.asarray(seq[len(prompt):])]
    assert (rows.max(-1) - picked).max() <= LOGIT_ATOL
