"""Xing4.0-29B-A4B on the block that serves Moonlight (models/moonlight.py
with `q_lora_rank`, `rope_scaling` and `hc_mult` 4) against its plain
reference (benchmarks/reference/xing_ref.py: float32, highest precision, no
cache, expanded attention, a loop over heads and experts) on seeded random
weights at a small size: hidden 64, 4 heads, nope 16 / rope 8 / v 16, latent
32, query rank 16, 2 dense + 2 expert layers (BOTH leading dense layers), 8
experts of width 32 with 2 a token and a shared one, 4 residual streams, 8
Sinkhorn rounds, YaRN factor 4 over 32 original positions (128 in all);
float32 weights, kernels interpreted.

Tolerances: tests/test_moonlight.py's, for its reasons. Both sides compute
in float32, so what separates them is the ORDER of sums (the mixer scales by
the row's norm after `phi` where the reference norms first; its Sinkhorn adds
four vectors where the reference reduces an axis; absorbed against expanded
attention; grouped against looped experts). Logits here have a standard
deviation of about 0.5; LOGIT_ATOL is 5e-5, 30 times the 1.7e-6 measured on
a whole sequence and far under what one bfloat16 rounding in the mixer moves
a logit by (the last tests show the mixer in bfloat16 and a dropped clamp
failing it). Comparisons that depend on the top-k picks run where the k-th
and (k+1)-th biased scores are PICK_GAP apart, as Moonlight's do.
"""

import importlib.util
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401
from paddle_tpu.models import _experts as ex
from paddle_tpu.models import _decoder as dec
from paddle_tpu.models import _latent
from paddle_tpu.models import moonlight as ml
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.kv_cache import SlotKVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
LOGIT_ATOL = 5e-5
PICK_GAP = 1e-4


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load(os.path.join(BENCH, "reference", "xing_ref.py"), "xing_ref")

YARN = {"type": "yarn", "factor": 4, "original_max_position_embeddings": 32,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
SIZES = dict(vocab_size=211, hidden=64, layers=4, heads=4, kv_lora_rank=32,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             intermediate=96, moe_intermediate=32, n_routed_experts=8,
             n_shared_experts=1, experts_per_tok=2, first_k_dense=2,
             routed_scaling_factor=2.0, rms_eps=1e-6, rope_theta=10000.0,
             max_pos=128)
CFG = ml.MoonlightConfig(**SIZES, q_lora_rank=16, rope_scaling=YARN,
                         hc_mult=4, hc_sinkhorn_iters=8,
                         name="Xing4.0-29B-A4B")
# the same numbers under the published keys, as the reference reads them
REF_CFG = {"num_attention_heads": 4, "qk_nope_head_dim": 16,
           "qk_rope_head_dim": 8, "kv_lora_rank": 32, "v_head_dim": 16,
           "rms_norm_eps": 1e-6, "rope_theta": 10000.0, "rope_scaling": YARN,
           "num_experts_per_tok": 2, "norm_topk_prob": True,
           "routed_scaling_factor": 2.0, "hc_mult": 4, "hc_eps": 1e-6,
           "hc_sinkhorn_iters": 8, "mhc_h_res_clamp_min": -30,
           "mhc_h_res_clamp_max": 30}
MIXERS = ("hc_attn", "hc_ffn")


def _spread(p, seed=3):
    """Norms away from one, a correction bias that moves picks, matrices
    large enough that logits, router scores and the mixers' z spread; the
    mixers' own biases and gates as seeded."""
    rng = np.random.default_rng(seed)
    out = dict(p, layers=[])
    for lp in p["layers"]:
        lp = dict(lp)
        for name in ("norm1", "norm2", "kv_norm", "q_norm"):
            if name in lp:
                lp[name] = jnp.asarray(rng.uniform(0.5, 1.5, lp[name].shape),
                                       jnp.float32)
        if "router_bias" in lp:
            lp["router_bias"] = jnp.asarray(
                rng.normal(0, 0.02, lp["router_bias"].shape), jnp.float32)
        for name, value in lp.items():
            if name in MIXERS:
                lp[name] = dict(
                    value, phi=value["phi"] * 4.0, hc_norm=jnp.asarray(
                        rng.uniform(0.5, 1.5, value["hc_norm"].shape),
                        jnp.float32))
            elif value.ndim >= 2:
                lp[name] = value * 4.0
        out["layers"].append(lp)
    out["wte"], out["head"] = p["wte"] * 4.0, p["head"] * 4.0
    return out


@pytest.fixture(scope="module")
def params():
    return _spread(ml.init_params(CFG, jax.random.PRNGKey(7), jnp.float32))


def tokens_of(seed, n):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n)


WIDTH = 64


def reference_logits(params, seq, cfg=None):
    """The reference's logits at every position of `seq`, computed on the
    sequence padded to WIDTH (causal: the padding reaches no real
    position), so that the tests share one compiled reference."""
    padded = list(seq) + [0] * (WIDTH - len(seq))
    return np.asarray(ref.sequence_logits(params, cfg or REF_CFG,
                                          padded))[:len(seq)]


def clear_of_ties(params, seq):
    """Positions of `seq` whose smallest gap between the k-th and the
    (k+1)-th biased score, over the expert layers of the reference, is
    PICK_GAP; a tie at one position changes every later one."""
    padded = list(seq) + [0] * (WIDTH - len(seq))
    _, gap = ref.sequence_logits(params, REF_CFG, padded, gaps=True)
    return np.minimum.accumulate(np.asarray(gap)[:len(seq)] >= PICK_GAP)


# -- the pieces ---------------------------------------------------------------

def test_yarn_frequencies_at_the_published_numbers():
    """d 64, theta 10000, factor 64 over 4096: dimensions 0..10 keep their
    frequency, 23..31 are divided by 64, a linear ramp between; cos and
    sin unscaled (mscale = mscale_all_dim), the softmax scale times
    (0.1 ln 64 + 1)^2 = 2.005."""
    yarn = {"type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
            "original_max_position_embeddings": 4096, "mscale": 1,
            "mscale_all_dim": 1}
    inv, mscale = dec.rope_frequencies(64, 10000.0, yarn)
    plain, one = dec.rope_frequencies(64, 10000.0)
    inv, plain = np.asarray(inv), np.asarray(plain)
    assert mscale == one == 1.0
    np.testing.assert_allclose(plain, 10000.0 ** (-np.arange(32) / 32),
                               rtol=1e-6)
    np.testing.assert_array_equal(inv[:11], plain[:11])
    np.testing.assert_allclose(inv[23:], plain[23:] / 64, rtol=1e-6)
    ramp = (np.arange(11, 23) - 10) / 13.0
    np.testing.assert_allclose(
        inv[11:23], plain[11:23] * (ramp / 64 + 1 - ramp), rtol=1e-5)
    r_inv, r_mscale = ref.rotary_frequencies(64, 10000.0, yarn)
    np.testing.assert_allclose(np.asarray(r_inv), inv, rtol=1e-6)
    assert r_mscale == 1.0
    cfg = ml.MoonlightConfig(qk_nope_head_dim=128, qk_rope_head_dim=64,
                             rope_scaling=yarn)
    want = (0.1 * math.log(64) + 1) ** 2 / math.sqrt(192)
    assert _latent.attention_scale(cfg) == pytest.approx(want, rel=1e-12)
    assert want * math.sqrt(192) == pytest.approx(2.005, abs=1e-3)
    assert ref.softmax_scale({"qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                              "rope_scaling": yarn}) \
        == pytest.approx(want, rel=1e-12)
    # mscale over mscale_all_dim where they differ
    _, ratio = dec.rope_frequencies(64, 10000.0, dict(yarn, mscale=0.707))
    assert ratio == pytest.approx((0.0707 * math.log(64) + 1)
                                  / (0.1 * math.log(64) + 1))


def test_only_yarn_is_a_rope_scaling():
    with pytest.raises(ValueError, match="YaRN"):
        ml.MoonlightConfig(rope_scaling={"type": "linear", "factor": 2})
    with pytest.raises(ValueError, match="hc_mult"):
        ml.MoonlightConfig(hc_mult=0)


@pytest.mark.parametrize("iters,columns_within", [(8, 5e-2), (20, 1e-3),
                                                   (40, 1e-4)])
def test_mixer_coefficients_match_reference_and_are_doubly_stochastic(
        params, iters, columns_within):
    """H_pre in (0, 1), H_post in (0, 2), every entry the reference's, and
    H_res's rows summing to one within 1e-4 after any number of rounds (a
    round ends on the rows) and its columns as the rounds converge:
    measured 1.0e-2, 1.3e-4 and 1.1e-6 at the worst of these 37 tokens
    after 8, 20 and 40 rounds. The entries are not one matrix: they move
    with the token."""
    cfg = ml.MoonlightConfig(**SIZES, hc_mult=4, hc_sinkhorn_iters=iters)
    hp = params["layers"][2]["hc_ffn"]
    X = jnp.asarray(np.random.default_rng(9).normal(0, 1, (37, 4, 64)),
                    jnp.float32)
    # the block keeps the token axis LAST: (n, T), (n, T), (n, n, T)
    h_pre, h_post, h_res = (np.moveaxis(np.asarray(a), -1, 0)
                            for a in ml.hc_coefficients(cfg, hp, X))
    with jax.default_matmul_precision("highest"):
        want = ref.mixer_coefficients(X, hp, dict(REF_CFG,
                                                  hc_sinkhorn_iters=iters))
    np.testing.assert_allclose(h_pre, want[0], atol=2e-6)
    np.testing.assert_allclose(h_post, want[1], atol=2e-6)
    np.testing.assert_allclose(h_res, want[2], atol=2e-6)
    assert 0 < h_pre.min() and h_pre.max() < 1 and h_post.max() < 2
    assert np.abs(h_res.sum(2) - 1).max() <= 1e-4          # rows
    assert np.abs(h_res.sum(1) - 1).max() <= columns_within
    assert h_res.std(0).max() > 0.05 and h_pre.std(0).min() > 0.05


def test_the_clamp_sits_before_the_exponential(params):
    """A gate that sends a_res z far past +-30: the clamp keeps exp finite
    and the result is the reference's; without it the rows are NaN."""
    hp = dict(params["layers"][0]["hc_attn"], a_res=jnp.float32(400.0))
    X = jnp.asarray(np.random.default_rng(1).normal(0, 1, (11, 4, 64)),
                    jnp.float32)
    h_res = jnp.moveaxis(ml.hc_coefficients(CFG, hp, X)[2], -1, 0)
    with jax.default_matmul_precision("highest"):
        want = ref.mixer_coefficients(X, hp, REF_CFG)[2]
    assert np.isfinite(np.asarray(h_res)).all()
    # the gate of 400 multiplies z's float32 rounding (1e-7) into the exponent
    np.testing.assert_allclose(np.asarray(h_res), want, rtol=1e-3,
                               atol=1e-30)
    loose = ml.MoonlightConfig(**SIZES, hc_mult=4, hc_sinkhorn_iters=8,
                               hc_res_clamp=1e9)
    assert not np.isfinite(np.asarray(
        ml.hc_coefficients(loose, hp, X)[2])).all()


# -- the whole sequence ---------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1])
def test_forward_logits_match_reference(params, seed):
    seq = tokens_of(seed, 56)                  # past the 32 original positions
    got = np.asarray(ml.forward_logits(params, CFG, jnp.asarray(seq)))
    want = reference_logits(params, seq)
    clear = clear_of_ties(params, seq)
    assert clear.sum() >= 40, f"{(~clear).sum()} positions left out for ties"
    assert want.std() > 0.1
    assert np.abs(got - want)[clear].max() <= LOGIT_ATOL


def test_the_reference_in_blocks_is_the_reference_whole(params, monkeypatch):
    """On the chip the reference holds the streams in blocks of 2048 tokens;
    here in blocks of 16, with rows picked across the blocks' edges."""
    seq = list(tokens_of(3, 64))
    whole, whole_gap = ref.sequence_logits(params, REF_CFG, seq, gaps=True)
    monkeypatch.setattr(ref, "BLOCK", 16)
    blocks, gap = ref.sequence_logits(params, REF_CFG, seq, gaps=True)
    np.testing.assert_allclose(np.asarray(blocks), np.asarray(whole),
                               atol=5e-6)
    np.testing.assert_allclose(np.asarray(gap), np.asarray(whole_gap),
                               atol=1e-6)
    rows = [0, 15, 16, 17, 47, 63]
    picked, gap = ref.sequence_logits(params, REF_CFG, seq, rows, gaps=True)
    np.testing.assert_allclose(np.asarray(picked), np.asarray(whole)[rows],
                               atol=5e-6)
    np.testing.assert_allclose(np.asarray(gap), np.asarray(whole_gap)[rows],
                               atol=1e-6)


def _as_parent(params, cfg, tokens):
    """models/moonlight.py::forward_logits as PR 30 had it, the two
    `x = x + ...` lines written out, on this module's pieces."""
    T = tokens.shape[0]
    pos = jnp.arange(T)
    x = params["wte"][tokens].astype(jnp.float32)
    mask = pos[None, :] <= pos[:, None]
    live = jnp.ones((T,), bool)
    counters = ml._zero_counters(cfg)
    for lp in params["layers"]:
        h = dec.rms(x, lp["norm1"], cfg.rms_eps)
        q_nope, q_rope, c, k_rope = _latent.project(cfg, lp, h, pos)
        k, v = _latent.expand(cfg, lp, c, k_rope)
        q = jnp.concatenate([q_nope, q_rope], -1)
        o = dec.masked_attention(q, k, v, mask,
                                 1.0 / np.sqrt(cfg.qk_head_dim))
        x = x + o.reshape(T, -1) @ lp["wo"]
        y, counters, _ = ex.ffn(cfg, lp, x, live, counters)
        x = x + y
    return ml._head(cfg, params, x)


@pytest.mark.parametrize("query", ["one_matrix", "low_rank_pair"])
def test_one_stream_is_moonlights_program_bit_for_bit(params, query):
    """`hc_mult` 1 from the SAME weights (the mixers' left in the tree and
    never read): the residual function is `x + f(x)`, every logit equal to
    the bit, nothing of the mixer traced and no `hc_*` counter named."""
    rank = None if query == "one_matrix" else 16
    cfg = ml.MoonlightConfig(**SIZES, q_lora_rank=rank, hc_mult=1)
    p = params
    if rank is None:
        fresh = ml.init_params(cfg, jax.random.PRNGKey(7), jnp.float32)
        assert not any(name in lp for lp in fresh["layers"]
                       for name in MIXERS + ("wqa", "q_norm"))
        p = dict(params, layers=[dict(lp, wq=f["wq"]) for lp, f in
                                 zip(params["layers"], fresh["layers"])])
    seq = jnp.asarray(tokens_of(2, 40))
    got = ml.forward_logits(p, cfg, seq)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(_as_parent(p, cfg, seq)))
    text = str(jax.make_jaxpr(lambda t: ml.forward_logits(p, cfg, t))(seq))
    assert "hc/" not in text and "exp " in text          # softmax, no Sinkhorn
    assert "hc_passes" not in ml.MOONLIGHT_SERVING_MODEL.counter_names(cfg)
    assert cfg.serving_model() is ml.MOONLIGHT_SERVING_MODEL


def test_init_params_leaves_moonlights_weights_as_they_were():
    """The mixers and the query pair draw from keys of their own: every
    weight the two trees share is the same array."""
    one = ml.MoonlightConfig(**SIZES)
    a = ml.init_params(one, jax.random.PRNGKey(5), jnp.float32)
    b = ml.init_params(ml.MoonlightConfig(**SIZES, hc_mult=4),
                       jax.random.PRNGKey(5), jnp.float32)
    for la, lb in zip(a["layers"], b["layers"]):
        assert set(lb) - set(la) == set(MIXERS)
        for name in la:
            np.testing.assert_array_equal(np.asarray(la[name]),
                                          np.asarray(lb[name]))
        hp = lb["hc_attn"]
        assert hp["phi"].shape == (4 * 64, 4 + 4 + 16)
        assert float(hp["a_res"]) == ml.HC_GATE
        assert np.diag(np.asarray(hp["b_res"])).mean() > 1.5


# -- through the pages ----------------------------------------------------------

_PREFILL = jax.jit(lambda params, *a: ml.prefill_pages(params, CFG, *a))
_DECODE = {path: jax.jit(lambda params, *a, path=path: ml.decode_step_pages(
    params, CFG, *a, attention=path)) for path in ("gather",
                                                   "latent_paged_kernel")}


def _prefill(params, arena, kv, slot, prompt, bucket, pfx_len=0):
    padded = np.zeros((1, bucket), np.int32)
    suffix = prompt[pfx_len:]
    padded[0, :len(suffix)] = suffix
    logits, arena, counters = _PREFILL(
        params, jnp.asarray(padded), jnp.int32(pfx_len),
        jnp.int32(len(suffix)), arena, jnp.asarray(kv.page_table[slot]))
    return np.asarray(logits[0]), arena, counters


@pytest.mark.parametrize("attention", ["gather", "latent_paged_kernel"])
def test_prefill_then_twelve_decode_steps_match_the_full_forward(
        params, attention):
    """Three slots: prompts of 30 (its decode crosses the 32 original
    positions), 9 and 41 tokens, the second FROZEN through the steps.
    Every step's logits of every live slot against the reference's full
    forward pass, and the mixer's counters exact."""
    bs, steps = 4, 12
    kv = SlotKVCache(CFG, 3, 64, jnp.float32, block_size=bs,
                     prefix_cache=False)
    arena = kv.arena
    prompts = {0: tokens_of(20, 30), 1: tokens_of(21, 9), 2: tokens_of(22, 41)}
    seqs = {s: list(p) + list(tokens_of(50 + s, steps))
            for s, p in prompts.items()}
    want = {s: reference_logits(params, seq) for s, seq in seqs.items()}
    clear = {s: clear_of_ties(params, seq) for s, seq in seqs.items()}
    for s, prompt in prompts.items():
        assert kv.alloc() == s
        kv.map_slot(s, prompt, len(prompt) + steps, register=False)
        logits, arena, c = _prefill(params, arena, kv, s, prompt, 48)
        if clear[s][len(prompt) - 1]:
            assert np.abs(logits - want[s][len(prompt) - 1]).max() \
                <= LOGIT_ATOL
        assert int(c["hc_passes"]) == 2 * CFG.layers
    pt = jnp.asarray(kv.page_table)
    done = jnp.asarray([False, True, False])
    ts = jnp.asarray([len(prompts[s]) for s in range(3)], jnp.int32)
    left_out = 0
    for i in range(steps):
        tok = jnp.asarray([seqs[s][len(prompts[s]) + i] for s in range(3)],
                          jnp.int32)
        logits, arena, c = _DECODE[attention](params, tok, arena, pt, ts, done)
        for s in (0, 2):
            at = len(prompts[s]) + i
            if clear[s][at]:
                assert np.abs(np.asarray(logits[s]) - want[s][at]).max() \
                    <= LOGIT_ATOL, (s, at)
            else:
                left_out += 1
        assert int(c["router_tokens"]) == 2 * 2          # frozen: not routed
        assert int(c["hc_passes"]) == 2 * CFG.layers
        # a row of H_res sums to one within a few parts per million a pass
        assert 0 <= int(c["hc_rowsum_dev_ppm"]) <= 5 * 2 * CFG.layers
        ts = jnp.where(done, ts, ts + 1)
    assert left_out <= 6


def test_prefill_after_a_prefix_hit_past_the_original_positions(params):
    """The warm branch on YaRN's stretched positions: the last logits of a
    53-token prompt whose first 40 positions (past the 32 original ones)
    are already cached, against a cold prefill and the reference."""
    bs = 4
    kv = SlotKVCache(CFG, 2, 64, jnp.float32, block_size=bs,
                     prefix_cache=False)
    prompt = tokens_of(30, 53)
    for s in (kv.alloc(), kv.alloc()):
        kv.map_slot(s, prompt, 60, register=False)
    cold, arena, _ = _prefill(params, kv.arena, kv, 0, prompt, 56)
    _, arena, _ = _prefill(params, arena, kv, 1, prompt[:40], 40)
    warm, arena, _ = _prefill(params, arena, kv, 1, prompt, 16, pfx_len=40)
    assert np.abs(warm - cold).max() <= LOGIT_ATOL
    if clear_of_ties(params, prompt)[-1]:
        assert np.abs(cold - reference_logits(params, prompt)[-1]).max() \
            <= LOGIT_ATOL


# -- the normal path ---------------------------------------------------------------

def _engine(params, **kw):
    kw = dict(dict(num_slots=3, prefill_buckets=(16, 48), max_len=64,
                   block_size=4, decode_chunk=4), **kw)
    return ServingEngine(params, CFG, ServingConfig(**kw))


def test_engine_serves_xing_and_reports_the_mixer(params):
    """ServingEngine over the Xing tree: the model's name, greedy tokens
    the reference's best at every step, the mixer's counters in stats()."""
    eng = _engine(params)
    prompts = [tokens_of(40 + i, n) for i, n in enumerate((5, 37, 12, 30))]
    reqs = [eng.submit(p, 9) for p in prompts]
    eng.run_until_drained()
    stats = eng.stats()
    assert stats["model"] == "Xing4.0-29B-A4B"
    assert stats["decode_attention"] == "gather"        # the CPU
    assert stats["compiled_executables"] == 2 + 2       # 2 buckets
    n_moe, k = CFG.layers - CFG.first_k_dense, CFG.experts_per_tok
    tokens = sum(len(p) for p in prompts) + 4 * 8
    assert stats["router_tokens"] == tokens * n_moe
    assert sum(stats["expert_tokens"]) == tokens * n_moe * k
    # a prefill mixes 2 sublayers a layer, a decode step with a live slot too
    assert stats["hc_passes"] % (2 * CFG.layers) == 0
    assert stats["hc_passes"] >= (4 + 8) * 2 * CFG.layers
    assert 0 <= stats["hc_rowsum_dev_ppm"] <= 5 * stats["hc_passes"]
    for prompt, req in zip(prompts, reqs):
        seq = list(prompt) + list(req.tokens)
        rows = reference_logits(params, seq)[len(prompt) - 1:-1]
        deficit = rows.max(-1) - rows[np.arange(9), req.tokens]
        assert deficit.max() <= 2 * LOGIT_ATOL
    eng.close()


@pytest.mark.parametrize("option", [
    dict(weight_dtype="int8"), dict(kv_dtype="int8"),
    dict(max_adapters=2, adapter_rank=2), dict(speculate_k=2),
    dict(mesh_shape=(2,)), dict(prefill_chunk=8)])
def test_xing_engine_refuses_what_the_block_lacks(params, option):
    with pytest.raises(ValueError, match="does not implement"):
        _engine(params, **option)


# -- the benchmark's builder ----------------------------------------------------

def _builder():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return importlib.import_module("lib.xing")


def _published():
    import json
    with open(os.path.join(BENCH, "configs", "xing4.0-29b-a4b.json")) as f:
        return json.load(f)


def test_the_builder_reads_every_published_key():
    cfg = _builder().xing_config(_published())
    assert (cfg.hidden, cfg.heads, cfg.q_lora_rank, cfg.kv_lora_rank) \
        == (3584, 32, 768, 512)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps,
            cfg.hc_res_clamp) == (4, 20, 1e-6, 30)
    assert cfg.rope_scaling["factor"] == 64 and cfg.max_pos == 16384
    assert (cfg.layers, cfg.first_k_dense, cfg.n_routed_experts,
            cfg.n_shared_experts, cfg.experts_per_tok) == (6, 1, 64, 1, 4)
    assert cfg.name == "Xing4.0-29B-A4B"


@pytest.mark.parametrize("key,value", [
    ("num_nextn_predict_layers", 1), ("n_group", 2),
    ("scoring_func", "softmax"), ("mhc_h_res_clamp_min", -20),
    ("tie_word_embeddings", True)])
def test_the_builder_refuses_what_the_block_is_not_written_for(key, value):
    """A multi-token-prediction module above all: it is refused, not
    ignored (how it joins four streams is not public)."""
    with pytest.raises(ValueError, match=key):
        _builder().xing_config(dict(_published(), **{key: value}))


# -- the tolerance is tight enough ---------------------------------------------

@pytest.mark.parametrize("where", ["coefficients", "mix"])
def test_the_tolerance_catches_a_bfloat16_mixer(params, monkeypatch, where):
    """The mixer's coefficients, or the streams it mixes, rounded to
    bfloat16 where float32 is stated, move the logits past LOGIT_ATOL."""
    if where == "coefficients":
        real = ml.hc_coefficients

        def rounded(cfg, hp, X):
            return tuple(a.astype(jnp.bfloat16).astype(jnp.float32)
                         for a in real(cfg, hp, X))
        monkeypatch.setattr(ml, "hc_coefficients", rounded)
    else:
        real = ml._add_all
        monkeypatch.setattr(ml, "_add_all", lambda parts: real(
            [p.astype(jnp.bfloat16).astype(jnp.float32) for p in parts]))
    seq = tokens_of(0, 56)
    got = np.asarray(ml.forward_logits(params, CFG, jnp.asarray(seq)))
    want = reference_logits(params, seq)
    clear = clear_of_ties(params, seq)
    assert np.abs(got - want)[clear].max() > 10 * LOGIT_ATOL
