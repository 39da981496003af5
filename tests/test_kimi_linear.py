"""Kimi-Linear-48B-A3B-Instruct's block (paddle_tpu.models.kimi_linear) at a
small size on the CPU: delta-rule linear attention (KDA) whose per-slot
state is a STATE GROUP of the one cache manager, three such layers to a
position-free latent layer, sigmoid-routed experts of which the chip holds
a SHARE.

The reference is benchmarks/reference/kimi_linear_ref.py (float32, highest
precision, the recurrence TOKEN BY TOKEN, independent of the program),
given the same held range. Pinned here: the chunked form against the
recurrence under decays that overflow a naive exp(-G); the served path
against the reference through the state blocks and the pages; one stream
whatever the decode chunk and the admission's time; a retired slot's block
reused; a frozen slot's block untouched; the shares add up; the latent
mixer with its rotation on is Moonlight's; every refusal by name; the
state group's layout, pool and stats; the step's kernel, interpreted."""

import hashlib
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from reference import kimi_linear_ref as ref                 # noqa: E402

from paddle_tpu.models import _experts as ex                 # noqa: E402
from paddle_tpu.models import _delta                         # noqa: E402
from paddle_tpu.models import _latent                        # noqa: E402
from paddle_tpu.models import kimi_linear as kl              # noqa: E402
from paddle_tpu.models import moonlight as ml                # noqa: E402
from paddle_tpu.ops import kda_step as ks                    # noqa: E402
from paddle_tpu.serving import (ServingConfig, ServingEngine,  # noqa: E402
                                SlotKVCache)
from paddle_tpu.serving.model import (CacheSpec, cache_groups,  # noqa: E402
                                      group_columns, require_features,
                                      serving_model, state_groups)

BS, E, HELD = 4, 8, (2, 4)
SIZES = dict(vocab_size=96, hidden=64, layers=5, heads=4, kv_lora_rank=32,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             kda_heads=4, kda_head_dim=16, intermediate=96,
             moe_intermediate=32, n_routed_experts=E, n_shared_experts=1,
             experts_per_tok=2, kda_decay_rank=16, kda_gate_rank=16,
             max_pos=64, init_range=0.08)
CFG = kl.KimiLinearConfig(experts_held=HELD, vocab_slice=(96, 96, 768), **SIZES)
WHOLE = kl.KimiLinearConfig(**SIZES)
# the same model under the published keys, as the reference reads them
REF_CFG = {
    "linear_attn_config": {"kda_layers": [1, 2, 3, 5], "full_attn_layers": [4],
                           "num_heads": 4, "head_dim": 16,
                           "short_conv_kernel_size": 4},
    "num_attention_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rms_norm_eps": 1e-5,
    "rope_theta": 10000, "num_experts_per_token": 2,
    "routed_scaling_factor": 2.446, "experts_held_first": HELD[0]}
LOGIT_ATOL = 5e-5


def tokens_of(seed, n):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n) \
        .astype(np.int32)


@pytest.fixture(scope="module")
def whole():
    """Every expert's weights; a share's tree is a slice of it."""
    return kl.init_params(WHOLE, jax.random.PRNGKey(0), jnp.float32)


def share_of(whole, first, count):
    layers = [dict(lp, **{name: lp[name][first:first + count]
                          for name in ("w_gate", "w_up", "w_down")
                          if name in lp})
              for lp in whole["layers"]]
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


# -- the config, the kinds, the groups ---------------------------------------------

def test_config_kinds_by_the_published_lists():
    full = kl.KimiLinearConfig()
    assert full.full_attn_layers == (4, 8, 12, 16, 20, 24, 27)
    assert len(full.kda_layers) == 20 and full.kda_layers[:4] == (1, 2, 3, 5)
    assert [CFG.kind(i) for i in range(5)] == ["kda"] * 3 + ["mla", "kda"]
    assert [CFG.index_in_group(i) for i in range(5)] == [0, 1, 2, 0, 3]
    assert CFG.state_shape == (4, 16, 16) and CFG.conv_shape == (1, 3, 192)
    assert full.state_shape == (32, 128, 128) and full.conv_shape == (1, 288, 128)
    assert (full.row_values, full.row_width) == (576, 640)
    with pytest.raises(ValueError, match="do not name each"):
        kl.KimiLinearConfig(layers=5, kda_layers=[1, 2], full_attn_layers=[4])
    with pytest.raises(ValueError, match="experts_held"):
        kl.KimiLinearConfig(experts_held=(250, 32))


def test_init_makes_a_layer_by_its_kind_and_only_the_held_experts(params, whole):
    kda, mla = params["layers"][0], params["layers"][3]
    assert "wqkv" in kda and "wq" not in kda and "gate" in kda      # layer 1 dense
    assert "wq" in mla and "wqkv" not in mla and "router" in mla
    assert kda["wqkv"].shape == (64, 192) and kda["conv_w"].shape == (4, 192)
    assert kda["a_log"].dtype == kda["dt_bias"].dtype == jnp.float32
    assert float(jnp.exp(kda["a_log"]).min()) >= 1 and float(jnp.exp(kda["a_log"]).max()) <= 16
    step = jax.nn.softplus(kda["dt_bias"])
    assert 0.0009 < float(step.min()) and float(step.max()) < 0.11
    assert params["layers"][1]["w_gate"].shape == (4, 64, 32)
    assert whole["layers"][1]["w_gate"].shape == (8, 64, 32)
    assert params["layers"][1]["router"].shape == (64, 8)
    assert params["head"].shape == (64, 96)


# -- the chunked form is the recurrence ---------------------------------------------

def _kda_inputs(T, seed, strong=True):
    n, d = 2, 16
    ks_ = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks_[0], (T, n, d))) * d ** -0.5
    k = unit(jax.random.normal(ks_[1], (T, n, d)))
    v = jax.random.normal(ks_[2], (T, n, d))
    # up to exp(3.5) = 33 a step in log space: exp(-G) passes float32 in 3 steps
    g = -jnp.exp(jax.random.uniform(ks_[3], (T, n, d), minval=-6.0,
                                    maxval=3.5 if strong else 0.0))
    beta = jax.nn.sigmoid(jax.random.normal(ks_[4], (T, n)))
    return q, k, v, g, beta


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("length", [1, 63, 64, 65, 200])
def test_the_chunked_form_is_the_recurrence(length, chunk):
    q, k, v, g, beta = _kda_inputs(length, length)
    if length > 4:
        assert not bool(jnp.isfinite(jnp.exp(-jnp.cumsum(g, 0))).all())
    with jax.default_matmul_precision("highest"):
        want = ref.kda_recurrence(q, k, v, g, beta)
        got, S = _delta.kda_chunked(q, k, v, g, beta, chunk=chunk)
    assert got.shape == (length, 2, 16) and bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got - want).max()) <= 5e-6
    # the state it ends in is the recurrence's
    S_want = jnp.zeros((2, 16, 16))
    for t in range(length):
        S_want, _ = _delta.kda_step(S_want, q[t], k[t], v[t], g[t], beta[t])
    assert float(jnp.abs(S - S_want).max()) <= 5e-5


def test_a_padded_row_leaves_the_state_as_it_was():
    q, k, v, g, beta = _kda_inputs(40, 7, strong=False)
    live = jnp.arange(40) < 27
    _, S_cut = _delta.kda_chunked(q[:27], k[:27], v[:27], g[:27], beta[:27], chunk=16)
    _, S_pad = _delta.kda_chunked(q, k, v, jnp.where(live[:, None, None], g, 0.0),
                              jnp.where(live[:, None], beta, 0.0), chunk=16)
    assert float(jnp.abs(S_cut - S_pad).max()) <= 1e-6


def test_the_chunked_form_carries_a_state_in():
    q, k, v, g, beta = _kda_inputs(48, 9, strong=False)
    whole_o, whole_S = _delta.kda_chunked(q, k, v, g, beta, chunk=16)
    _, S0 = _delta.kda_chunked(q[:20], k[:20], v[:20], g[:20], beta[:20], chunk=16)
    o, S = _delta.kda_chunked(q[20:], k[20:], v[20:], g[20:], beta[20:], S0, chunk=16)
    assert float(jnp.abs(o - whole_o[20:]).max()) <= 5e-6
    assert float(jnp.abs(S - whole_S).max()) <= 5e-6


# -- the served math against the reference ----------------------------------------

@pytest.mark.parametrize("length", [5, 16, 41])
def test_forward_matches_the_reference(params, length):
    seq = tokens_of(length, length)
    got = np.asarray(kl.forward_logits(params, CFG, jnp.asarray(seq)))
    assert np.abs(got - reference_logits(params, seq)).max() <= LOGIT_ATOL


def test_forward_of_the_uncut_model_matches_the_uncut_reference(whole):
    seq = tokens_of(3, 20)
    got = np.asarray(kl.forward_logits(whole, WHOLE, jnp.asarray(seq)))
    want = np.asarray(ref.sequence_logits(
        whole, dict(REF_CFG, experts_held_first=0), seq))
    assert np.abs(got - want).max() <= LOGIT_ATOL


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_a_wrong_program_is_another_function(params, wrong):
    seq = tokens_of(11, 30)
    true = reference_logits(params, seq)
    other = reference_logits(params, seq, wrong=wrong, prompt_len=13, bucket=16)
    moved = np.abs(other - true).max(-1)
    assert moved.max() > 1e-3, wrong
    if wrong in ("conv_reset", "bucket_end"):
        # the hand-over's: nothing before the first generated position moves
        assert moved[:13].max() == 0.0 and moved[13] > 1e-4


def test_prefill_then_decode_through_the_state_block_and_the_pages(params):
    """A prompt of 11 in a bucket of 16: the state and the history written
    are those at row 11, and five steps on the logits are the reference's."""
    kv = SlotKVCache(CFG, 2, 48, jnp.float32, block_size=BS)
    prompt = tokens_of(5, 11)
    slot = kv.alloc()
    row, _ = kv.map_slot(slot, prompt, 11 + 6)
    padded = np.zeros((1, 16), np.int32)
    padded[0, :11] = prompt
    logits, arena, c = kl.prefill_pages(params, CFG, jnp.asarray(padded), 0,
                                        jnp.int32(11), kv.arena, jnp.asarray(row))
    assert int(c["kda_prefill_rows"]) == 11 * 4
    seq = list(prompt)
    want = reference_logits(params, seq)
    assert np.abs(np.asarray(logits[0]) - want[-1]).max() <= LOGIT_ATOL
    pt = jnp.asarray(kv.page_table)
    for _ in range(5):
        seq.append(int(jnp.argmax(logits[0])))
        logits, arena, c = kl.decode_step_pages(
            params, CFG, jnp.asarray([seq[-1], 0]), arena, pt,
            jnp.asarray([len(seq) - 1, 0]), jnp.asarray([False, True]))
        want = reference_logits(params, seq)
        assert np.abs(np.asarray(logits[0]) - want[-1]).max() <= LOGIT_ATOL
        assert int(c["kda_state_steps"]) == 4
        assert int(c["mla_decode_rows"]) == len(seq)


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
    _, after, _ = kl.decode_step_pages(params, CFG, jnp.asarray([3, 4, 5]), arena,
                                       pt, jnp.asarray([6, 6, 0]), done)
    state_col, conv_col = kv.group_layout[1].start, kv.group_layout[2].start
    frozen, live = int(rows[1][state_col]), int(rows[0][state_col])
    assert bool((after[1][:, :, frozen] == arena[1][:, :, frozen]).all())
    assert not bool((after[1][:, :, live] == arena[1][:, :, live]).all())
    frozen, live = int(rows[1][conv_col]), int(rows[0][conv_col])
    assert bool((after[2][:, :, frozen] == arena[2][:, :, frozen]).all())
    assert not bool((after[2][:, :, live] == arena[2][:, :, live]).all())
    # the latent rows of the frozen slot's pages too
    pages = [int(b) for b in rows[1][:5] if b]
    assert bool((after[0][:, :, pages] == arena[0][:, :, pages]).all())


# -- through the engine ----------------------------------------------------------

@pytest.mark.parametrize("p_len,new", [(3, 12), (8, 20), (13, 15)])
def test_the_engine_serves_the_references_greedy_tokens(params, p_len, new):
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
    assert st["model"] == "Kimi-Linear-48B-A3B-Instruct"
    assert st["experts_held"] == {"first": 2, "count": 4, "of": 8}
    assert st["vocab_slice"] == {"first": 96, "rows": 96, "of": 768}
    assert st["decode_attention"] == {"latent": "gather"}
    assert st["kda_state_steps"] == 4 * (new - 1)
    assert st["kda_prefill_rows"] == 4 * p_len
    assert st["kda_prefill_chunks"] == 4          # one chunk holds a bucket
    assert st["moe_picks_routed"] == 2 * st["router_tokens"]
    assert st["moe_picks_held"] == sum(st["expert_tokens"])
    assert st["prefix_cache"].startswith("off: a hit is valid only with")
    assert st["compiled_executables"] <= 2 + 2
    eng.close()


def _streams(params, decode_chunk, late=False):
    eng = _engine(params, decode_chunk=decode_chunk)
    prompts = [tokens_of(s, n) for s, n in ((1, 7), (2, 12), (3, 5))]
    reqs = [eng.submit(p, max_new_tokens=14) for p in prompts[:2]]
    if late:
        for _ in range(3):
            eng.step()
    reqs.append(eng.submit(prompts[2], max_new_tokens=14))
    eng.run_until_drained()
    eng.close()
    return [list(map(int, r.tokens)) for r in reqs]


@pytest.mark.parametrize("decode_chunk,late", [(1, False), (5, False), (8, False),
                                               (5, True)])
def test_one_stream_whatever_the_chunk_and_the_admission(params, decode_chunk, late):
    assert _streams(params, decode_chunk, late) == _streams(params, 4)


def test_a_retired_slots_state_block_is_reused_and_the_stream_is_a_fresh_engines(params):
    eng = _engine(params, num_slots=1)
    first = eng.submit(tokens_of(1, 9), max_new_tokens=10)
    eng.run_until_drained()
    block = eng.kv.page_table[0].copy()
    st = eng.stats()["state"]
    assert st["blocks_used"] == 0 and st["peak_blocks_used"] == 2
    second = eng.submit(tokens_of(2, 6), max_new_tokens=12)
    eng.step()
    state_col = eng.kv.group_layout[1].start
    assert eng.kv.page_table[0][state_col] == 1        # the one block, again
    eng.run_until_drained()
    eng.close()
    assert len(first.tokens) == 10 and block[state_col] == 0
    fresh = _engine(params, num_slots=1)
    alone = fresh.submit(tokens_of(2, 6), max_new_tokens=12)
    fresh.run_until_drained()
    fresh.close()
    assert list(second.tokens) == list(alone.tokens)


# -- the state group in the cache manager -------------------------------------------

def test_the_state_groups_layout_is_one_column_each():
    layout = cache_groups(serving_model(CFG), CFG, 48, BS)
    assert [g.spec.name for g in layout] == ["latent", "state", "conv"]
    assert [(g.start, g.pages) for g in layout] == [(0, 12), (12, 1), (13, 1)]
    assert [g.spec.state for g in layout] == [False, True, True]
    assert layout[1].spec.arena_shape(7, BS) == (4, 1, 7, 4, 16, 16)
    assert layout[2].spec.arena_shape(7, BS) == (4, 1, 7, 1, 3, 192)
    cols = group_columns(CFG.cache_specs(), 14, BS)
    assert cols == (slice(0, 12), slice(12, 13), slice(13, 14))
    assert [s.name for s in state_groups(serving_model(CFG), CFG)] == ["state", "conv"]
    # a state block without a shape of its own is a block of rows' shape
    assert CacheSpec(2, 4, 16, state=True).arena_shape(3, 16) == (2, 1, 3, 4, 16, 16)


def test_a_state_group_is_never_the_primary_one():
    class Model:
        def cache_spec(self, cfg):
            return (CacheSpec(1, 1, 8, state=True, state_shape=(1, 2, 8)),)

    with pytest.raises(ValueError, match="state group after it"):
        cache_groups(Model(), None, 16, 4)


def test_the_pools_hand_a_slot_one_block_and_take_it_back():
    kv = SlotKVCache(CFG, 3, 48, jnp.bfloat16, block_size=BS)
    latent, state, conv = kv.arena
    assert (latent.dtype, state.dtype, conv.dtype) == (jnp.bfloat16, jnp.float32,
                                                        jnp.bfloat16)
    assert state.shape == (4, 1, 4, 4, 16, 16) and kv.table_width == 14
    slots = [kv.alloc() for _ in range(3)]
    rows = [kv.map_slot(s, tokens_of(s, 5), 9 + 4 * s)[0] for s in slots]
    assert sorted(int(r[12]) for r in rows) == [1, 2, 3]
    assert sorted(int(r[13]) for r in rows) == [1, 2, 3]
    occ = kv.state_occupancy()
    assert occ == {"groups": ["state", "conv"], "blocks_total": 6, "blocks_used": 6,
                   "peak_blocks_used": 6,
                   "bytes_a_slot": 4 * 4 * 16 * 16 * 4 + 4 * 3 * 192 * 2}
    assert kv.group_rows(slots[0]) == {"latent": 12}
    kv.free(slots[1])
    assert kv.state_occupancy()["blocks_used"] == 4
    assert (kv.page_table[slots[1]] == 0).all()
    again = kv.alloc()
    assert kv.map_slot(again, tokens_of(9, 4), 8)[0][12] == rows[1][12]
    groups = kv.occupancy()["groups"]
    assert [g["name"] for g in groups] == ["latent", "state", "conv"]
    assert groups[1]["state"] is True and groups[1]["dtype"] == "float32"
    assert "state" not in groups[0]
    assert kv.occupancy()["prefix_cache"].startswith("off: a hit is valid only")
    with pytest.raises(ValueError, match="primary cache group alone"):
        kv.can_adopt(1)


def test_a_state_blocks_allocation_is_a_span_of_the_ring():
    from paddle_tpu.observability.tracer import (disable_tracing, enable_tracing,
                                                 get_tracer)
    kv = SlotKVCache(CFG, 2, 48, jnp.float32, block_size=BS)
    enable_tracing()
    try:
        get_tracer().clear()
        slot = kv.alloc()
        row, _ = kv.map_slot(slot, tokens_of(1, 5), 9)
        spans = [s for s in get_tracer().snapshot() if s.name == "serving/state_alloc"]
    finally:
        disable_tracing()
    assert [s.args["group"] for s in spans] == ["state", "conv"]
    assert [s.args["block"] for s in spans] == [int(row[12]), int(row[13])]


def test_engine_stats_name_the_state(params):
    eng = _engine(params)
    st = eng.stats()
    assert st["state"] == {"groups": ["state", "conv"], "blocks_total": 6,
                           "blocks_used": 0, "peak_blocks_used": 0,
                           "bytes_a_slot": 4 * 4 * 16 * 16 * 4 + 4 * 3 * 192 * 4,
                           "recurrence_path": "xla",
                           "prefill_recurrence_path": "xla",
                           "prefill_kernel_buckets": [],
                           "prefill_chunk_rows": 64}
    assert [g["name"] for g in st["groups"]] == ["latent", "state", "conv"]
    assert st["prefill_attention"]["groups"] == {"latent": "gather"}
    eng.close()


# -- what a state group refuses -----------------------------------------------------

REFUSED = {"weight_dtype": ("int8", "no int8 path"), "kv_dtype": ("int8", "scale"),
           "max_adapters": (2, "LoRA"), "speculate_k": (2, "rejected draft"),
           "mesh_shape": ((1,), "one chip's program"),
           "prefill_chunk": (8, "carried in"), "preempt": (True, "no snapshot")}


@pytest.mark.parametrize("option", sorted(REFUSED))
def test_every_option_a_state_group_lacks_is_refused_by_name(params, option):
    value, says = REFUSED[option]
    extra = {"adapter_rank": 4} if option == "max_adapters" else {}
    with pytest.raises(ValueError, match="does not implement") as err:
        _engine(params, **{option: value}, **extra)
    assert "the state group 'state'" in str(err.value) and says in str(err.value)


def test_refusals_without_a_state_group_say_what_they_said():
    """A model without a state group is refused what it was, as it was."""
    cfg = ml.MoonlightConfig(vocab_size=211, hidden=64, layers=2, heads=4,
                             kv_lora_rank=32, qk_nope_head_dim=16,
                             qk_rope_head_dim=8, v_head_dim=16, intermediate=96,
                             moe_intermediate=32, n_routed_experts=8,
                             n_shared_experts=1, experts_per_tok=2, max_pos=64)
    with pytest.raises(ValueError, match=r"speculate_k > 0 \(the verify pass\) "
                                         "needs 'speculation'"):
        require_features(serving_model(cfg), ServingConfig(speculate_k=2), cfg)
    require_features(serving_model(cfg), ServingConfig(preempt=True), cfg)


def test_migration_is_refused_at_the_call(params):
    from paddle_tpu.serving.migration import MigrationError
    eng = _engine(params)
    req = eng.submit(tokens_of(1, 5), max_new_tokens=8)
    eng.step()
    with pytest.raises(MigrationError, match="3 cache groups"):
        eng.migrate_out(req)
    eng.close()


# -- the shares add up ----------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(whole):
    """Eight chips hold one expert each. A chip's layer gives its routed part
    plus what every chip computes alike (the shared expert); the eight routed
    parts and the shared expert ONCE are the uncut reference's layer."""
    lp = whole["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(3), (37, 64), jnp.float32)
    live = jnp.ones((37,), bool)
    with jax.default_matmul_precision("highest"):
        routed, shared, _ = ref.ffn(x, lp, REF_CFG, held=(0, E))
        uncut = np.asarray(routed + shared)
        h = ref.rms_norm(x, lp["norm2"], 1e-5)
        total = np.zeros_like(uncut)
        held_picks = 0
        for first in range(E):
            cfg = kl.KimiLinearConfig(experts_held=(first, 1), **SIZES)
            part = share_of(whole, first, 1)["layers"][1]
            y, c = ex.moe(cfg, part, h, live)
            total += np.asarray(y) - np.asarray(shared)
            held_picks += int(c["expert_tokens"].sum())
        assert held_picks == 37 * 2              # every pick is some chip's
        assert np.abs(total + np.asarray(shared) - uncut).max() <= 5e-6
        y, _ = ex.moe(WHOLE, lp, h, live)
        assert np.abs(np.asarray(y) - uncut).max() <= 2e-6


def test_a_share_reads_its_products_pick_by_pick(whole):
    """A layer that holds a SHARE makes k gathers of T rows (no config key says
    so: `held < E` does); a layer that holds every expert keeps the one gather
    of k T rows; the share's sum is the one gather's, to the bit."""
    part = share_of(whole, *HELD)["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(5), (24, 64), jnp.float32)
    live = jnp.arange(24) % 5 != 0
    ys, pos, w = _layer_products(CFG, part, x, live)
    gathers = lambda **kw: str(jax.make_jaxpr(
        lambda ys: ex._weighted_sum(ys, pos, w, live, **kw))(ys)).count("GatherScatterMode.CLIP")
    assert (gathers(), gathers(share=True)) == (1, CFG.experts_per_tok)
    y, _ = ex.moe(CFG, part, x, live)
    one = ex._sum_end(ex._weighted_sum(ys, pos, w, live),
                      _shared(part, x), None, x.dtype)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(one))


def _shared(lp, x):
    return ex.swiglu_hidden(x, lp["shared_gate"], lp["shared_up"]) @ lp["shared_down"]


def _layer_products(cfg, lp, x, live):
    """What `moe` hands its combine on a share: the routed buffer, the picks'
    positions and weights."""
    from paddle_tpu.ops.grouped_swiglu import row_tile_for
    first, held = ex.held_experts(cfg)
    T, k = x.shape[0], cfg.experts_per_tok
    picks, w = ex.route(cfg, lp, x)
    picks = picks - first
    mine = live[:, None] & (picks >= 0) & (picks < held)
    w = jnp.where(mine, w, 0)
    ys, pos, _ = ex._lay_out(lp, x, picks, mine, held,
                             row_tile_for(T * k, cfg.n_routed_experts), T * k,
                             -(-T * k * held // cfg.n_routed_experts), False)
    return ys, pos, w


@pytest.mark.parametrize("live_last", [False, True])
@pytest.mark.parametrize("poison", [np.nan, np.inf])
def test_a_pick_held_elsewhere_reads_nothing_of_the_buffer(poison, live_last):
    """The buffer's last row is of a tile no expert owns: never written, and
    NaN on the chip where float32 state had lain. A pick past the buffer
    (weight 0) must add 0 whatever that row holds: under a share it is a
    select, for every model that holds one."""
    ys = jnp.arange(12.0).reshape(6, 2).at[5].set(poison)
    pos = jnp.asarray([[0, 6], [6, 2], [6, 6]], jnp.int32)
    w = jnp.asarray([[0.5, 0.0], [0.0, 2.0], [0.0, 0.0]], jnp.float32)
    live = jnp.asarray([True, True, live_last])
    got = np.asarray(ex._weighted_sum(ys, pos, w, live, share=True))
    np.testing.assert_array_equal(got, [[0.0, 0.5], [8.0, 10.0], [0.0, 0.0]])
    assert not np.isfinite(np.asarray(ex._weighted_sum(ys, pos, w, live))).all()


# -- the latent mixer: the rotation is an argument ------------------------------------

def _moonlight(**extra):
    return ml.MoonlightConfig(vocab_size=211, hidden=64, layers=3, heads=4,
                              kv_lora_rank=32, qk_nope_head_dim=16,
                              qk_rope_head_dim=8, v_head_dim=16, intermediate=96,
                              moe_intermediate=32, n_routed_experts=8,
                              n_shared_experts=1, experts_per_tok=2, max_pos=64,
                              **extra)


def _digest(cfg):
    params = jax.eval_shape(lambda k: ml.init_params(cfg, k, jnp.float32),
                            jax.random.PRNGKey(0))
    text = str(jax.make_jaxpr(lambda p, t: ml.forward_logits(p, cfg, t))(
        params, jax.ShapeDtypeStruct((9,), jnp.int32)))
    return hashlib.sha256(text.encode()).hexdigest()[:16], text


def test_the_rotation_off_is_moonlights_program_without_its_rotation():
    on, text_on = _digest(_moonlight())
    explicit, _ = _digest(_moonlight(mla_use_nope=False))
    off, text_off = _digest(_moonlight(mla_use_nope=True))
    assert on == explicit != off
    assert " cos " in text_on or "cos " in text_on
    assert "cos " not in text_off and "sin " not in text_off


def test_without_rotation_the_latent_layer_ignores_positions(params):
    lp = params["layers"][3]
    x = jax.random.normal(jax.random.PRNGKey(2), (6, 64), jnp.float32)
    a = _latent.project(CFG, lp, x, jnp.arange(6))
    b = _latent.project(CFG, lp, x, jnp.arange(6) + 17)
    for u, v in zip(a, b):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))
    rotating = _moonlight()
    lp_m = ml.init_params(rotating, jax.random.PRNGKey(0), jnp.float32)["layers"][1]
    a = _latent.project(rotating, lp_m, x, jnp.arange(6))
    b = _latent.project(rotating, lp_m, x, jnp.arange(6) + 17)
    assert float(jnp.abs(a[1] - b[1]).max()) > 1e-3          # q_rope moved
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))


# -- the step's kernel, interpreted ----------------------------------------------------

def test_the_step_kernel_is_the_recurrence_and_spares_a_frozen_slot():
    key = jax.random.split(jax.random.PRNGKey(1), 6)
    S, H, D = 5, 4, 16
    arena = jax.random.normal(key[0], (2, 1, 9, H, D, D))
    ids = jnp.asarray([3, 1, 7, 2, 5])
    done = jnp.asarray([False, True, False, False, True])
    q, k, v, g, beta = (jax.random.normal(key[1], (S, H, D)),
                        jax.random.normal(key[2], (S, H, D)),
                        jax.random.normal(key[3], (S, H, D)),
                        -jnp.exp(jax.random.normal(key[4], (S, H, D))),
                        jax.nn.sigmoid(jax.random.normal(key[5], (S, H))))
    o, new = ks.kda_step_blocks(arena, 1, ids, done, q, k, v, g, beta)
    S_want, o_want = _delta.kda_step(arena[1, 0, ids], q, k, v, g, beta)
    live = np.asarray(~done)
    assert float(jnp.abs(o - o_want)[live].max()) <= 1e-5
    assert float(jnp.abs(new[1, 0, ids[live]] - S_want[live]).max()) <= 1e-5
    untouched = jnp.asarray([1, 5, 4, 6, 8])         # frozen slots' and nobody's
    assert bool((new[0] == arena[0]).all())
    assert bool((new[1, 0, untouched] == arena[1, 0, untouched]).all())
    with pytest.raises(ValueError, match="float32"):
        ks.kda_step_blocks(arena.astype(jnp.bfloat16), 1, ids, done, q, k, v, g, beta)


def test_the_decode_step_through_the_kernel_is_the_decode_step(params):
    kv = SlotKVCache(CFG, 2, 48, jnp.float32, block_size=BS)
    for seed in (1, 2):
        kv.map_slot(kv.alloc(), tokens_of(seed, 6), 20)
    arena = tuple(0.1 * jax.random.normal(jax.random.PRNGKey(i), a.shape, a.dtype)
                  for i, a in enumerate(kv.arena))
    pt = jnp.asarray(kv.page_table)
    args = (jnp.asarray([3, 4]), arena, pt, jnp.asarray([6, 6]),
            jnp.asarray([False, False]))
    want, arena_x, _ = kl.decode_step_pages(params, CFG, *args, recurrence="xla")
    got, arena_k, _ = kl.decode_step_pages(params, CFG, *args, recurrence="kernel")
    assert float(jnp.abs(got - want).max()) <= 1e-5
    assert float(jnp.abs(arena_k[1][:, :, 1:] - arena_x[1][:, :, 1:]).max()) <= 1e-5
    assert kl.recurrence_path(CFG) == "xla"                   # the CPU


@pytest.mark.parametrize("path,kept_in", [("xla", "float32"), ("kernel", "float32"),
                                          ("xla", "bfloat16")])
def test_the_steps_two_halves_are_the_step_and_the_reference(params, path, kept_in):
    """`kda_step_inputs` then `kda_state_update` ARE a KDA layer's step (what
    `decode_step_pages` runs, and what the cell's limit 4 runs on the engine's
    own blocks): the state that comes back is the reference's float32
    `kda_step` on the same block and operands to float32 rounding, by either
    path; kept in bfloat16 it is a thousandth off, which is how limit 4 tells."""
    li = 1                                            # a KDA layer
    lp, n = params["layers"][li], 3
    key = jax.random.split(jax.random.PRNGKey(9), 3)
    state = (0.1 * jax.random.normal(key[0], (1, 1, n + 1) + CFG.state_shape)
             ).astype(kept_in)
    conv = 0.1 * jax.random.normal(key[1], (1, 1, n + 1) + CFG.conv_shape)
    u = jax.random.normal(key[2], (n, CFG.hidden))
    ids, done = jnp.arange(1, n + 1), jnp.asarray([False, True, False])
    arenas = {kl.STATE: state, kl.CONV: conv}
    q, k, v, g, beta, z, arenas = kl.kda_step_inputs(CFG, lp, u, arenas, 0, ids, done)
    o, arenas = kl.kda_state_update(arenas, 0, ids, done, q, k, v, g, beta, path)
    want_S, want_o = jax.vmap(ref.kda_step)(state[0, 0, ids].astype(jnp.float32),
                                            q, k, v, g, beta)
    got_S = arenas[kl.STATE][0, 0, ids].astype(jnp.float32)
    size = lambda a: float(jnp.sqrt(jnp.sum(a * a)))
    error = size((got_S - want_S)[::2]) / size(want_S[::2])   # the live slots
    if kept_in == "float32":
        assert error < 1e-6 and float(jnp.abs(o - want_o)[::2].max()) < 1e-5
    else:
        assert 1e-4 < error < 1e-2
    # the frozen slot's block and history are as they were; scratch took its writes
    assert bool((arenas[kl.STATE][0, 0, 2] == state[0, 0, 2]).all())
    assert bool((arenas[kl.CONV][0, 0, 2] == conv[0, 0, 2]).all())
    # the same two calls are the layer
    y, _ = kl._kda_decode(CFG, lp, u, {kl.STATE: state, kl.CONV: conv}, 0, ids, ids,
                          done, path)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(kl._kda_gate(CFG, lp, o, z)))
