"""Qwen3-Next-80B-A3B-Instruct's block (paddle_tpu.models.qwen3_next) at a
small size on the CPU: three layers of Gated DeltaNet (a SCALAR decay a head,
key heads shared 2 : 1 by the value heads, on models/_delta.py's recurrence
and the state groups of models/_recurrent.py) to every layer of GATED
grouped-query attention (q/k norm, a partial rotation, an output gate), the
family's zero-centred norm, softmax-routed experts of which the chip holds a
SHARE, a shared expert weighed by the token.

The reference is benchmarks/reference/qwen3_next_ref.py (float32, highest
precision, the recurrence TOKEN BY TOKEN, plain softmax, independent of the
program), given the same held range. Pinned here: the served math against
the reference with and without a cache, for prompts that end mid-bucket and
mid-chunk; the step against the chunked form against the recurrence; each of
the eight WRONG programs another function; each new piece where it lies
(value heads over key heads, the rotated part, the gate, `1 + w`, the token's
gate); the shares adding up. Through the engine and the kernels:
tests/test_qwen3_next_engine.py."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from reference import qwen3_next_ref as ref                  # noqa: E402

from paddle_tpu.models import _decoder                       # noqa: E402
from paddle_tpu.models import _delta                         # noqa: E402
from paddle_tpu.models import _experts as ex                 # noqa: E402
from paddle_tpu.models import _recurrent                     # noqa: E402
from paddle_tpu.models import qwen3_next as qn               # noqa: E402
from paddle_tpu.serving import (ServingConfig, ServingEngine,  # noqa: E402
                                SlotKVCache)

BS, E, HELD = 4, 8, (2, 4)
SIZES = dict(vocab_size=96, hidden=64, layers=4, heads=4, kv_heads=2,
             head_dim=16, gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=16,
             gdn_value_dim=16, moe_intermediate=32, shared_intermediate=32,
             n_routed_experts=E, experts_per_tok=2, max_pos=64,
             init_range=0.08)
CFG = qn.Qwen3NextConfig(experts_held=HELD, vocab_slice=(96, 96, 768), **SIZES)
WHOLE = qn.Qwen3NextConfig(**SIZES)
# the same model under the published keys, as the reference reads them
REF_CFG = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 16, "partial_rotary_factor": 0.25,
           "linear_num_key_heads": 2, "linear_num_value_heads": 4,
           "linear_key_head_dim": 16, "linear_value_head_dim": 16,
           "linear_conv_kernel_dim": 4, "rms_norm_eps": 1e-6,
           "rope_theta": 10000000, "num_experts_per_tok": 2,
           "full_attention_interval": 4, "num_hidden_layers": 4,
           "experts_held_first": HELD[0]}
LOGIT_ATOL = 5e-5


def tokens_of(seed, n):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n) \
        .astype(np.int32)


@pytest.fixture(scope="module")
def whole():
    """Every expert's weights; a share's tree is a slice of it."""
    return qn.init_params(WHOLE, jax.random.PRNGKey(0), jnp.float32)


def share_of(whole, first, count):
    layers = [dict(lp, **{name: lp[name][first:first + count]
                          for name in ("w_gate", "w_up", "w_down")})
              for lp in whole["layers"]]
    return dict(whole, layers=layers)


@pytest.fixture(scope="module")
def params(whole):
    return share_of(whole, *HELD)


def reference_logits(params, seq, cfg=REF_CFG, **kw):
    return np.asarray(ref.sequence_logits(params, cfg,
                                          jnp.asarray(seq, jnp.int32), **kw))


def _engine(params, **kw):
    kw.setdefault("num_slots", 3)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("max_len", 48)
    kw.setdefault("block_size", BS)
    return ServingEngine(params, CFG, ServingConfig(**kw))


# -- the config, the kinds, the groups ---------------------------------------------

def test_config_kinds_by_the_interval_and_the_published_widths():
    full = qn.Qwen3NextConfig()
    kinds = [i for i, t in enumerate(full.layer_types) if t == qn.ATTENTION]
    assert kinds == list(range(3, 48, 4)) and full.layers == 48
    assert (full.rotary_dim, full.attention.head_dim, full.attention.group) == (64, 256, 8)
    assert full.state_shape == (32, 128, 128) and full.conv_width == 8192
    assert (full.key_width, full.value_width) == (2048, 4096)
    specs = full.cache_specs()
    assert [s.name for s in specs] == ["full", "gdn", "conv"]
    # a page of the rows: (2 KV heads, block, K | V of 256 each); a history block whole lanes
    assert specs[0].arena_shape(5, 128) == (12, 1, 5, 2, 128, 512)
    assert specs[1].state_shape == (32, 128, 128) and specs[1].dtype == "float32"
    assert specs[2].state_shape == (1, 192, 128) == _recurrent.history_shape(3, 8192)
    assert [CFG.kind(i) for i in range(4)] == [qn.LINEAR] * 3 + [qn.ATTENTION]
    assert [CFG.index_in_group(i) for i in range(4)] == [0, 1, 2, 0]
    assert CFG.rotary_dim == 4 and CFG.state_shape == (4, 16, 16)
    with pytest.raises(ValueError, match="full_attention_interval"):
        qn.Qwen3NextConfig(layers=3)
    with pytest.raises(ValueError, match="value heads do not share"):
        qn.Qwen3NextConfig(gdn_value_heads=24)
    with pytest.raises(ValueError, match="partial_rotary_factor"):
        qn.Qwen3NextConfig(head_dim=16, partial_rotary_factor=0.2)
    with pytest.raises(ValueError, match="experts_held"):
        qn.Qwen3NextConfig(experts_held=(500, 64))


def test_init_makes_a_layer_by_its_kind_and_only_the_held_experts(params, whole):
    gdn, att = params["layers"][0], params["layers"][3]
    assert "w_qkvz" in gdn and "wq" not in gdn and "wq" in att and "w_qkvz" not in att
    assert gdn["w_qkvz"].shape == (64, 2 * 32 + 2 * 64) and gdn["w_ba"].shape == (64, 8)
    assert gdn["conv_w"].shape == (4, 128) and "conv_b" not in gdn
    assert gdn["a_log"].dtype == gdn["dt_bias"].dtype == jnp.float32
    assert att["wq"].shape == (64, 4 * 2 * 16) and att["q_norm"].shape == (16,)
    assert gdn["w_gate"].shape == (4, 64, 32) and gdn["router"].shape == (64, E)
    assert whole["layers"][0]["w_gate"].shape == (E, 64, 32)
    assert gdn["shared_token_gate"].shape == (64,)
    assert params["head"].shape == (64, 96) and params["wte"].shape == (96, 64)
    # the zero-centred norms are seeded AWAY from zero: `1 + w` is not `w`, nor 1
    for w in (gdn["norm1"], gdn["norm2"], att["q_norm"], params["norm_f"]):
        assert 0.1 < float(jnp.abs(w).mean()) < 0.5 and float(jnp.abs(w).max()) <= 0.5
    assert 0.5 <= float(gdn["gate_norm"].min()) and float(gdn["gate_norm"].max()) <= 1.5
    # exp(A_log) in (0, 16): a head keeps between exp(-16 x dt) and nearly all of its state
    assert float(jnp.exp(gdn["a_log"]).max()) <= 16.0
    # two layers of one kind differ, and a seed draws what it drew
    assert not bool((gdn["w_qkvz"] == params["layers"][1]["w_qkvz"]).all())
    again = qn.init_params(WHOLE, jax.random.PRNGKey(0), jnp.float32)
    assert bool((again["layers"][2]["w_ba"] == whole["layers"][2]["w_ba"]).all())


# -- the recurrence: the step, the chunked form, the reference token by token ------------

def _gdn_operands(T, seed=0, nk=2, nv=4, d=16):
    """A prompt's operands as `_gdn_activate` leaves them: a SCALAR decay a head over
    its key channels, a key head's q, k for its two value heads."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = jnp.repeat(unit(jax.random.normal(ks[0], (T, nk, d))) * d ** -0.5, nv // nk, 1)
    k = jnp.repeat(unit(jax.random.normal(ks[1], (T, nk, d))), nv // nk, 1)
    v = jax.random.normal(ks[2], (T, nv, d))
    g = -jnp.exp(jax.random.uniform(ks[3], (T, nv), minval=-6.0, maxval=1.0))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (T, nv)))
    return q, k, v, g, beta


@pytest.mark.parametrize("length,chunk", [(1, 16), (15, 16), (16, 16), (17, 16),
                                          (100, 64), (100, 16)])
def test_the_step_the_chunked_form_and_the_reference_agree(length, chunk):
    """`_delta.kda_step` T times, `_delta.kda_chunked` (the CPU's prefill) and the
    reference's own token-by-token recurrence, under a scalar decay and shared key
    heads, for lengths that end before, at and behind a chunk's edge."""
    q, k, v, g, beta = _gdn_operands(length, seed=length)
    wide = jnp.broadcast_to(g[..., None], q.shape)
    want_o, want_S = ref.gdn_recurrence(q, k, v, g, beta)
    S = jnp.zeros((4, 16, 16), jnp.float32)
    for t in range(length):
        S, o = _delta.kda_step(S, q[t], k[t], v[t], wide[t], beta[t])
        assert float(jnp.abs(o - want_o[t]).max()) <= 2e-6
    assert float(jnp.abs(S - want_S).max()) <= 2e-6
    got_o, got_S = _delta.kda_chunked(q, k, v, wide, beta, chunk=chunk)
    assert float(jnp.abs(got_o - want_o).max()) <= 5e-6
    assert float(jnp.abs(got_S - want_S).max()) <= 5e-6


def test_a_padded_row_leaves_the_state_as_it_was(params):
    """Rows at or past `real_len` come with g = 0 and beta = 0 from
    `gdn_prompt_inputs`: the state and the history are those AT `real_len`."""
    lp = params["layers"][0]
    u = jax.random.normal(jax.random.PRNGKey(1), (16, 64), jnp.float32)
    q, k, v, g, beta, z, hist = qn.gdn_prompt_inputs(CFG, lp, u, jnp.int32(11))
    assert float(jnp.abs(g[11:]).max()) == 0.0 and float(jnp.abs(beta[11:]).max()) == 0.0
    assert float(jnp.abs(g[:11]).min()) > 0.0
    _, S_pad, _ = qn.gdn_scan(q, k, v, g, beta, jnp.int32(11), "xla")
    cut = qn.gdn_prompt_inputs(CFG, lp, u[:11], jnp.int32(11))
    _, S_cut, _ = qn.gdn_scan(*cut[:5], jnp.int32(11), "xla")
    assert float(jnp.abs(S_pad - S_cut).max()) <= 2e-6
    assert bool((hist == cut[6]).all()) and hist.shape == (3, 128)
    assert z.shape == (16, 64)


def test_value_heads_read_their_key_head_two_to_one(params):
    """Value head h reads q, k of key head h // 2, and the decay is one number a head
    over its 16 key channels."""
    lp = params["layers"][1]
    u = jax.random.normal(jax.random.PRNGKey(2), (5, 64), jnp.float32)
    q, k, v, g, beta, _, _ = qn.gdn_prompt_inputs(CFG, lp, u, jnp.int32(5))
    assert q.shape == k.shape == g.shape == (5, 4, 16) and v.shape == (5, 4, 16)
    for h in (0, 2):
        assert bool((q[:, h] == q[:, h + 1]).all()) and bool((k[:, h] == k[:, h + 1]).all())
    assert not bool((q[:, 1] == q[:, 2]).all()) and not bool((v[:, 0] == v[:, 1]).all())
    assert bool((g == g[..., :1]).all()) and not bool((g[:, 0] == g[:, 1]).all())
    # l2-normalised a key head; q also times d^-0.5
    np.testing.assert_allclose(np.asarray(jnp.sum(k * k, -1)), 1.0, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(jnp.sum(q * q, -1)), 1 / 16, rtol=1e-4)
    rq, rk, rv, rg, rbeta, _, _ = ref.gdn_inputs(u, lp, dict(ref._static(REF_CFG)))
    np.testing.assert_allclose(np.asarray(q), np.asarray(ref.to_value_heads(rq, 4)), atol=2e-6)
    np.testing.assert_allclose(np.asarray(g[..., 0]), np.asarray(rg), atol=2e-6)
    np.testing.assert_allclose(np.asarray(beta), np.asarray(rbeta), atol=2e-6)
    assert not bool((ref.to_value_heads(rq, 4, unshared=True) == ref.to_value_heads(rq, 4)).all())


# -- the served math against the reference ----------------------------------------------

# the two programs as the engine runs them: jitted (an eager call compiles op by op)
PREFILL = jax.jit(lambda p, t, n, a, row: qn.prefill_pages(p, CFG, t, 0, n, a, row))
STEP = jax.jit(lambda p, t, a, pt, ts, d, recurrence=None: qn.decode_step_pages(
    p, CFG, t, a, pt, ts, d, recurrence=recurrence), static_argnames=("recurrence",))


@pytest.mark.parametrize("length", [5, 41])
def test_forward_matches_the_reference(params, length):
    seq = tokens_of(length, length)
    got = np.asarray(qn.forward_logits(params, CFG, jnp.asarray(seq)))
    assert np.abs(got - reference_logits(params, seq)).max() <= LOGIT_ATOL
    assert got.std() > 0.1


def test_forward_of_the_uncut_model_matches_the_uncut_reference(whole):
    seq = tokens_of(9, 21)
    got = np.asarray(qn.forward_logits(whole, WHOLE, jnp.asarray(seq)))
    want = reference_logits(whole, seq, dict(REF_CFG, experts_held_first=0))
    assert np.abs(got - want).max() <= LOGIT_ATOL


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_a_wrong_program_is_another_function(params, wrong):
    """Each of the eight is told from the served math by the logits (the two of lower
    precision by a hundred times float32's noise, the others by thousands)."""
    seq = tokens_of(3, 27)
    got = np.asarray(qn.forward_logits(params, CFG, jnp.asarray(seq)))
    other = reference_logits(params, seq, wrong=wrong, prompt_len=19, bucket=32)
    low = wrong in ("state_bf16", "scan_bf16")
    assert np.abs(got - other).max() > (100 if low else 2000) * LOGIT_ATOL, wrong
    with pytest.raises(ValueError, match="wrong is None or one of"):
        ref.sequence_logits(params, REF_CFG, seq, wrong="rotary")


@pytest.mark.parametrize("p_len", [11, 16])
def test_prefill_then_decode_through_the_state_blocks_and_the_pages(params, p_len):
    """A prompt of 11 in a bucket of 16 (the state and the history written are
    those at row 11) and one of 16 that fills it: three steps on, the logits
    are the reference's full forward."""
    kv = SlotKVCache(CFG, 2, 48, jnp.float32, block_size=BS)
    prompt = tokens_of(5, p_len)
    slot = kv.alloc()
    row, _ = kv.map_slot(slot, prompt, p_len + 6)
    padded = np.zeros((1, 16), np.int32)
    padded[0, :p_len] = prompt
    logits, arena, c = PREFILL(params, jnp.asarray(padded), jnp.int32(p_len), kv.arena,
                               jnp.asarray(row))
    assert int(c["gdn_prefill_rows"]) == p_len * 3
    assert int(c["gdn_prefill_chunks"]) == 3          # one chunk of 64 a layer
    seq = list(prompt)
    want = reference_logits(params, seq)
    assert np.abs(np.asarray(logits[0]) - want[-1]).max() <= LOGIT_ATOL
    pt = jnp.asarray(kv.page_table)
    for _ in range(3):
        seq.append(int(jnp.argmax(logits[0])))
        logits, arena, c = STEP(params, jnp.asarray([seq[-1], 0]), arena, pt,
                                jnp.asarray([len(seq) - 1, 0]), jnp.asarray([False, True]))
        want = reference_logits(params, seq)
        assert np.abs(np.asarray(logits[0]) - want[-1]).max() <= LOGIT_ATOL
        assert int(c["gdn_state_steps"]) == 3
        assert int(c["decode_rows_full"]) == len(seq)


def test_a_prompt_that_ends_mid_chunk_over_several_chunks(params, monkeypatch):
    """Chunks of 8 rows: a prompt of 21 in a bucket of 32 ends inside the third of four
    chunks; what is handed over is the reference's state, history and rows at row 21."""
    monkeypatch.setattr(_delta, "KDA_CHUNK", 8)
    monkeypatch.setattr(_delta.kda_chunked, "__defaults__", (None, 8, 4))
    kv = SlotKVCache(CFG, 2, 48, jnp.float32, block_size=BS)
    prompt = tokens_of(8, 21)
    slot = kv.alloc()
    row, _ = kv.map_slot(slot, prompt, 30)
    padded = np.zeros((1, 32), np.int32)
    padded[0, :21] = prompt
    logits, arena, c = jax.jit(lambda p, t, a, r: qn.prefill_pages(
        p, CFG, t, 0, jnp.int32(21), a, r))(params, jnp.asarray(padded), kv.arena,
                                            jnp.asarray(row))
    assert int(c["gdn_prefill_chunks"]) == 3 * 4
    cache = {}
    want = np.asarray(ref.sequence_logits(params, REF_CFG, prompt, rows=[20], cache=cache))
    assert np.abs(np.asarray(logits) - want).max() <= LOGIT_ATOL
    starts = [g.start for g in kv.group_layout]
    for lg in range(3):
        np.testing.assert_allclose(np.asarray(arena[1][lg, 0, row[starts[1]]]),
                                   np.asarray(cache["state"][lg]), atol=2e-5)
        np.testing.assert_allclose(
            np.asarray(arena[2][lg, 0, row[starts[2]]]).reshape(3, 128),
            np.asarray(cache["history"][lg]), atol=2e-5)
    pages = row[starts[0]:starts[0] + 6]
    rows = np.asarray(arena[0][0, 0, pages]).transpose(0, 2, 1, 3).reshape(24, 2, 32)[:21]
    np.testing.assert_allclose(rows, np.asarray(cache["rows"][0]), atol=2e-5)


def test_a_frozen_slots_blocks_are_bit_identical_after_a_step(params):
    kv = SlotKVCache(CFG, 3, 48, jnp.float32, block_size=BS)
    rows = []
    for seed in (1, 2):
        slot = kv.alloc()
        rows.append(kv.map_slot(slot, tokens_of(seed, 6), 20)[0])
    arena = tuple(jax.random.normal(jax.random.PRNGKey(i), a.shape, a.dtype)
                  for i, a in enumerate(kv.arena))
    pt = jnp.asarray(kv.page_table)
    done = jnp.asarray([False, True, True])
    _, after, _ = STEP(params, jnp.asarray([3, 4, 5]), arena, pt, jnp.asarray([6, 6, 0]), done)
    for group in (1, 2):
        col = kv.group_layout[group].start
        frozen, live = int(rows[1][col]), int(rows[0][col])
        assert bool((after[group][:, :, frozen] == arena[group][:, :, frozen]).all())
        assert not bool((after[group][:, :, live] == arena[group][:, :, live]).all())
    pages = [int(b) for b in rows[1][:5] if b]
    assert bool((after[0][:, :, pages] == arena[0][:, :, pages]).all())


# -- the pieces this family adds, each where it lies ---------------------------------------

def test_the_norm_is_zero_centred_and_every_other_model_keeps_its_own():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 8), jnp.float32)
    w = jnp.linspace(-0.5, 0.5, 8)
    plain = _decoder.rms(x, w, 1e-6)
    centred = _decoder.rms(x, w, 1e-6, centred=True)
    np.testing.assert_allclose(np.asarray(centred), np.asarray(_decoder.rms(x, 1 + w, 1e-6)),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(centred), np.asarray(ref.rms_norm(x, w, 1e-6)),
                               rtol=1e-6)
    assert float(jnp.abs(plain - centred).max()) > 0.1
    # a weight of zeros is the plain norm of unit weight (the published initialiser)
    np.testing.assert_allclose(np.asarray(_decoder.rms(x, 0 * w, 1e-6, centred=True)),
                               np.asarray(_decoder.rms(x, 0 * w + 1, 1e-6)), rtol=1e-6)
    # the default traces what it traced: no `1 +` in another model's norm (its one `add`
    # is the eps's)
    adds = lambda **kw: str(jax.make_jaxpr(
        lambda x, w: _decoder.rms(x, w, 1e-6, **kw))(x, w)).count(" add ")
    assert (adds(), adds(centred=True)) == (1, 2)


def test_the_rotation_turns_the_first_quarter_of_a_head_and_passes_the_rest(params):
    lp = params["layers"][3]
    u = jax.random.normal(jax.random.PRNGKey(4), (6, 64), jnp.float32)
    at0 = qn._project(CFG, lp, u, jnp.zeros((6,), jnp.int32))
    at9 = qn._project(CFG, lp, u, jnp.full((6,), 9, jnp.int32))
    for a, b in zip(at0[:2], at9[:2]):                       # q and k
        assert bool((a[..., 4:] == b[..., 4:]).all())        # 12 of 16 pass
        assert float(jnp.abs(a[..., :4] - b[..., :4]).max()) > 0.1
        # a rotation: the turned part keeps its length, pair by pair (i, i + 2)
        np.testing.assert_allclose(np.asarray(a[..., 0] ** 2 + a[..., 2] ** 2),
                                   np.asarray(b[..., 0] ** 2 + b[..., 2] ** 2), rtol=1e-5)
    assert bool((at0[2] == at9[2]).all()) and bool((at0[3] == at9[3]).all())   # v, the gate
    q, k, v, gate = at9
    assert q.shape == (6, 4, 16) and k.shape == v.shape == (6, 2, 16) and gate.shape == (6, 64)
    # the frequencies are those of the ROTATED width (theta^(-2i/4)), not of the head's 16
    c = dict(ref._static(REF_CFG))
    rq, rk, rv, rgate = ref._attn_qkv(u, dict(lp, norm1=jnp.zeros(64)), c, True, False)
    pos9 = ref._rope_halves(jnp.ones((1, 1, 4)), jnp.asarray([9]), 1e7)
    assert float(jnp.abs(pos9[0, 0, 1] - jnp.cos(9 * 1e7 ** -0.5) + jnp.sin(9 * 1e7 ** -0.5))) < 1e-5
    assert rq.shape == q.shape and rgate.shape == (6, 4, 16)


def test_the_output_gate_is_the_query_projections_second_half(params):
    """A head's [q | gate] columns: with the gate's columns zeroed the output is half
    the ungated attention's (sigmoid(0)), and the q columns are untouched by them."""
    lp = params["layers"][3]
    u = jax.random.normal(jax.random.PRNGKey(5), (7, 64), jnp.float32)
    pos = jnp.arange(7)
    q, k, v, gate = qn._project(CFG, lp, u, pos)
    wq = lp["wq"].reshape(64, 4, 2, 16)
    shut = dict(lp, wq=wq.at[:, :, 1].set(0.0).reshape(64, 128))
    q0, _, _, gate0 = qn._project(CFG, shut, u, pos)
    assert bool((q0 == q).all()) and float(jnp.abs(gate0).max()) == 0.0
    o = jax.random.normal(jax.random.PRNGKey(6), (7, 4, 16), jnp.float32)
    half = qn._gated_out(lp, o, gate0)
    np.testing.assert_allclose(np.asarray(half), np.asarray(0.5 * o.reshape(7, 64) @ lp["wo"]),
                               rtol=1e-4, atol=1e-6)
    want = (o.reshape(7, 64) * jax.nn.sigmoid(gate)) @ lp["wo"]
    np.testing.assert_allclose(np.asarray(qn._gated_out(lp, o, gate)), np.asarray(want),
                               rtol=1e-4, atol=1e-6)


def test_the_recurrent_mixers_gate_comes_behind_its_norm(params):
    lp = params["layers"][0]
    o = jax.random.normal(jax.random.PRNGKey(7), (5, 4, 16), jnp.float32)
    z = jax.random.normal(jax.random.PRNGKey(8), (5, 64), jnp.float32)
    got = qn._gdn_gate(CFG, lp, o, z)
    normed = ref.rms_norm(o, lp["gate_norm"], 1e-6, centred=False)     # a PLAIN weight
    want = (normed * jax.nn.silu(z).reshape(5, 4, 16)).reshape(5, 64) @ lp["w_out"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-6)
    granite = ref.rms_norm(o * jax.nn.silu(z).reshape(5, 4, 16), lp["gate_norm"], 1e-6,
                           centred=False).reshape(5, 64) @ lp["w_out"]
    assert float(jnp.abs(got - granite).max()) > 0.01


def test_the_shared_expert_is_weighed_by_the_token(whole):
    """`moe` under "token_gate" is the routed sum plus sigmoid(x . w_s) times the shared
    SwiGLU; under "sum" (every older model's) the same tree adds it whole."""
    lp = whole["layers"][2]
    x = jax.random.normal(jax.random.PRNGKey(3), (9, 64), jnp.float32)
    live = jnp.ones((9,), bool)
    with jax.default_matmul_precision("highest"):
        gated, _ = ex.moe(WHOLE, lp, x, live)

        class Summed:
            def __getattr__(self, name):
                return getattr(WHOLE, name)
            shared_expert_combination = "sum"

        summed, _ = ex.moe(Summed(), lp, x, live)
        shared = ex.swiglu(x, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
        token = jax.nn.sigmoid(x @ lp["shared_token_gate"])
        np.testing.assert_allclose(np.asarray(summed - gated),
                                   np.asarray((1 - token)[:, None] * shared), atol=2e-6)
    assert 0.0 < float(token.min()) and float(token.max()) < 1.0 and float(token.std()) > 0.05


def test_the_published_routing_rule_is_the_softmax_rule(whole):
    """Softmax over all the router's outputs, the k largest, over their sum: `route`'s
    "softmax" rule is the reference's dense weights, pick for pick."""
    lp = whole["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(11), (13, 64), jnp.float32)
    picks, w = ex.route(WHOLE, lp, x)
    dense = ref.router(x, lp["router"], {"experts_per_tok": 2})
    assert picks.shape == (13, 2) and np.allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-6)
    got = np.zeros((13, E), np.float32)
    np.put_along_axis(got, np.asarray(picks), np.asarray(w), 1)
    np.testing.assert_allclose(got, np.asarray(dense), atol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer(whole):
    """Eight chips hold an eighth of the experts each (one of eight here, as the
    deployment's 64 of 512). A chip's layer gives its routed part plus what every chip
    computes alike (the token-gated shared expert); all eight routed parts and the shared
    one ONCE are the uncut reference's layer."""
    lp = whole["layers"][2]
    x = jax.random.normal(jax.random.PRNGKey(3), (37, 64), jnp.float32)
    live = jnp.ones((37,), bool)
    with jax.default_matmul_precision("highest"):
        routed, shared, _ = ref.ffn(x, lp, REF_CFG, held=(0, E))
        uncut = np.asarray(routed + shared)
        u = ref.rms_norm(x, lp["norm2"], 1e-6)
        total = np.zeros_like(uncut)
        held_picks = 0
        for first in range(E):
            cfg = qn.Qwen3NextConfig(experts_held=(first, 1), **SIZES)
            part = share_of(whole, first, 1)["layers"][2]
            y, c = ex.moe(cfg, part, u, live)
            total += np.asarray(y) - np.asarray(shared)
            held_picks += int(c["expert_tokens"].sum())
        assert held_picks == 37 * 2              # every pick is some chip's
        assert np.abs(total + np.asarray(shared) - uncut).max() <= 5e-6
        y, _ = ex.moe(WHOLE, lp, u, live)
        assert np.abs(np.asarray(y) - uncut).max() <= 2e-6
