"""granite-4.0-h-small's block (paddle_tpu.models.granite_hybrid) at a small
size on the CPU: a Mamba-2 state-space mixer whose per-slot state and
convolution history are STATE GROUPS of the one cache manager
(models/_recurrent.py, shared with Kimi-Linear's delta-rule layers), one
position-free grouped-query attention layer among them, softmax-routed
experts of which the chip holds a SHARE, and the family's four multipliers.

The reference is benchmarks/reference/granite_hybrid_ref.py (float32,
highest precision, the recurrence TOKEN BY TOKEN, independent of the
program), given the same held range. Pinned here: the chunked dual form
against the recurrence, with and without a state carried in; the served path
against the reference through the state blocks and the pages, for a prompt
that ends mid-bucket and one that fills its bucket; the step's kernel,
interpreted, on a float32 state arena beside a bfloat16 history with a
frozen slot; the published routing rule against `_experts.route`'s
"softmax" rule; the shares adding up; the attention layer unrotated at the
published scale; each multiplier; every refusal by name."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from reference import granite_hybrid_ref as ref              # noqa: E402

from paddle_tpu.models import _experts as ex                 # noqa: E402
from paddle_tpu.models import _grouped                       # noqa: E402
from paddle_tpu.models import _recurrent                     # noqa: E402
from paddle_tpu.models import granite_hybrid as gh           # noqa: E402
from paddle_tpu.ops import ssd_step as ss                    # noqa: E402
from paddle_tpu.serving import (ServingConfig, ServingEngine,  # noqa: E402
                                SlotKVCache)
from paddle_tpu.serving.model import (cache_groups, require_features,  # noqa: E402
                                      serving_model, state_groups)

BS, E, HELD = 4, 8, (4, 4)
KINDS = ["mamba", "attention", "mamba", "mamba"]
SIZES = dict(vocab_size=96, hidden=64, layers=4, heads=4, kv_heads=2,
             layer_types=KINDS, mamba_heads=8, mamba_head_dim=16,
             mamba_state=16, mamba_chunk=8, moe_intermediate=32,
             shared_intermediate=48, n_routed_experts=E, experts_per_tok=3,
             max_pos=64, init_range=0.08)
CFG = gh.GraniteHybridConfig(experts_held=HELD, vocab_slice=(96, 96, 768),
                             **SIZES)
WHOLE = gh.GraniteHybridConfig(**SIZES)
# the same model under the published keys, as the reference reads them
REF_CFG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_d_conv": 4, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "num_experts_per_tok": 3, "embedding_multiplier": 12,
    "logits_scaling": 16, "residual_multiplier": 0.22,
    "attention_multiplier": 0.0078125, "layer_types": KINDS,
    "experts_held_first": HELD[0]}
# the logits are the published ones, after / 16: their spread is 0.05 here
LOGIT_ATOL = 5e-6


def tokens_of(seed, n):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n) \
        .astype(np.int32)


@pytest.fixture(scope="module")
def whole():
    """Every expert's weights; a share's tree is a slice of it."""
    return gh.init_params(WHOLE, jax.random.PRNGKey(0), jnp.float32)


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

def test_config_kinds_by_the_published_list():
    full = gh.GraniteHybridConfig()
    assert [i for i, t in enumerate(full.layer_types) if t == "attention"] \
        == [5, 15, 25, 35]
    assert full.state_shape == (128, 64, 128) and full.conv_width == 8448
    assert full.mamba_inner == 8192
    specs = full.cache_specs()
    assert [s.name for s in specs] == ["full", "ssm", "conv"]
    assert specs[0].layers == 4 and specs[1].layers == 36
    assert specs[1].state_shape == (128, 64, 128) and specs[1].dtype == "float32"
    assert specs[2].state_shape == (1, 198, 128)       # 3 x 8448 in whole lanes
    att = full.attention
    assert att.attention_scale == 0.0078125 and not hasattr(att, "position_free")
    assert (att.heads, att.kv_heads, att.head_dim) == (32, 8, 128)
    assert [CFG.kind(i) for i in range(4)] == KINDS
    assert [CFG.index_in_group(i) for i in range(4)] == [0, 0, 1, 2]
    with pytest.raises(ValueError, match="mamba_n_groups"):
        gh.GraniteHybridConfig(mamba_groups=8)
    with pytest.raises(ValueError, match="not 128 heads"):
        gh.GraniteHybridConfig(mamba_expand=3)


def test_init_makes_a_layer_by_its_kind_and_only_the_held_experts(params, whole):
    mamba, attention = params["layers"][0], params["layers"][1]
    assert mamba["w_in"].shape == (64, 2 * 128 + 2 * 16 + 8)
    assert mamba["conv_w"].shape == (4, 160) and mamba["conv_b"].shape == (160,)
    assert "wq" not in mamba and "w_in" not in attention
    assert attention["wk"].shape == (64, 2 * 16)
    assert mamba["w_gate"].shape == (4, 64, 32) and mamba["router"].shape == (64, 8)
    assert whole["layers"][0]["w_gate"].shape == (8, 64, 32)
    assert mamba["shared_gate"].shape == (64, 48)
    assert "head" not in params                       # the head is the embedding
    np.testing.assert_allclose(np.exp(np.asarray(mamba["a_log"])), np.arange(1, 9),
                               rtol=1e-6)
    step = np.log1p(np.exp(np.asarray(mamba["dt_bias"])))     # softplus
    assert (step >= 0.001 * 0.999).all() and (step <= 0.1 * 1.001).all()


# -- the recurrence: one position, and in chunks ---------------------------------------

def _operands(seed, T, H=8, P=16, N=16):
    key = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(key[0], (T, H, P))
    # steps from 0.001 to e: a head's decay runs from almost 1 to exp(-20)
    dt = jnp.exp(jax.random.uniform(key[1], (T, H), minval=-7.0, maxval=1.0))
    A = -jnp.arange(1, H + 1, dtype=jnp.float32)
    return x, dt, A, jax.random.normal(key[2], (T, N)), jax.random.normal(key[3], (T, N)), \
        jax.random.normal(key[4], (H, P, N))


def _token_by_token(x, dt, A, B, C, S0):
    def step(S, row):
        return gh.ssd_step(S, row[0], row[1], A, row[2], row[3])
    return jax.lax.scan(step, S0, (x, dt, B, C))


@pytest.mark.parametrize("carried", [False, True])
@pytest.mark.parametrize("chunk", [8, 64])
@pytest.mark.parametrize("length", [1, 7, 8, 9, 100])
def test_the_chunked_form_is_the_recurrence(length, chunk, carried):
    x, dt, A, B, C, S0 = _operands(length, length)
    S0 = S0 if carried else None
    y, S = gh.ssd_chunked(x, dt, A, B, C, S0, chunk=chunk)
    S_want, y_want = _token_by_token(x, dt, A, B, C,
                                     jnp.zeros_like(_operands(0, 1)[5]) if S0 is None else S0)
    assert float(jnp.abs(y - y_want).max()) <= 2e-5 * float(jnp.abs(y_want).max() + 1)
    assert float(jnp.abs(S - S_want).max()) <= 2e-5 * float(jnp.abs(S_want).max() + 1)


def test_a_padded_row_leaves_the_state_as_it_was():
    x, dt, A, B, C, S0 = _operands(3, 16)
    dt = jnp.where((jnp.arange(16) < 11)[:, None], dt, 0.0)
    _, padded = gh.ssd_chunked(x, dt, A, B, C, S0, chunk=8)
    _, cut = gh.ssd_chunked(x[:11], dt[:11], A, B[:11], C[:11], S0, chunk=8)
    assert float(jnp.abs(padded - cut).max()) <= 1e-6 * float(jnp.abs(cut).max())


def test_the_programs_step_is_the_references():
    x, dt, A, B, C, S0 = _operands(5, 1)
    S, y = gh.ssd_step(S0, x[0], dt[0], A, B[0], C[0])
    S_ref, y_ref = ref.ssd_step(S0, x[0], dt[0], A, B[0], C[0])
    assert float(jnp.abs(S - S_ref).max()) <= 1e-6 and float(jnp.abs(y - y_ref).max()) <= 1e-5


# -- against the reference ----------------------------------------------------------------

@pytest.mark.parametrize("length", [5, 16, 41])
def test_forward_matches_the_reference(params, length):
    seq = tokens_of(length, length)
    got = np.asarray(gh.forward_logits(params, CFG, jnp.asarray(seq)))
    assert np.abs(got - reference_logits(params, seq)).max() <= LOGIT_ATOL


def test_forward_of_the_uncut_model_matches_the_uncut_reference(whole):
    seq = tokens_of(3, 20)
    got = np.asarray(gh.forward_logits(whole, WHOLE, jnp.asarray(seq)))
    want = reference_logits(whole, seq, dict(REF_CFG, experts_held_first=0))
    assert np.abs(got - want).max() <= LOGIT_ATOL


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_a_wrong_program_is_another_function(params, wrong):
    seq = tokens_of(11, 30)
    true = reference_logits(params, seq)
    other = reference_logits(params, seq, wrong=wrong, prompt_len=13, bucket=16)
    moved = np.abs(other - true).max(-1)
    # the quiet ones: a state or a scan's operands a thousandth off, and an attention layer that
    # under seeded weights at scale 1/128 is nearly a mean over the rows
    assert moved.max() > {"state_bf16": 1e-6, "scan_bf16": 1e-6, "rotary": 1e-5,
                          "attn_scale": 1e-5}.get(wrong, 1e-4), wrong
    if wrong in ("conv_reset", "bucket_end"):
        # the hand-over's: nothing before the first generated position moves
        assert moved[:13].max() == 0.0 and moved[13] > 1e-5


@pytest.mark.parametrize("key,wrong_value", [
    ("embedding_multiplier", 1), ("residual_multiplier", 1.0),
    ("attention_multiplier", 16 ** -0.5), ("logits_scaling", 1)])
def test_each_multiplier_is_the_published_one(params, key, wrong_value):
    """The served logits are the reference's under the published multiplier
    and another function's under any other."""
    seq = tokens_of(7, 24)
    got = np.asarray(gh.forward_logits(params, CFG, jnp.asarray(seq)))
    other = reference_logits(params, seq, dict(REF_CFG, **{key: wrong_value}))
    assert np.abs(got - reference_logits(params, seq)).max() <= LOGIT_ATOL
    assert np.abs(got - other).max() > 100 * LOGIT_ATOL, key


@pytest.mark.parametrize("p_len", [11, 16])
def test_prefill_then_decode_through_the_state_blocks_and_the_pages(params, p_len):
    """A prompt of 11 in a bucket of 16 (the state and the history written are
    those at row 11) and one of 16 that fills it: five steps on, the logits
    are the reference's."""
    kv = SlotKVCache(CFG, 2, 48, jnp.float32, block_size=BS)
    prompt = tokens_of(5, p_len)
    slot = kv.alloc()
    row, _ = kv.map_slot(slot, prompt, p_len + 6)
    padded = np.zeros((1, 16), np.int32)
    padded[0, :p_len] = prompt
    logits, arena, c = gh.prefill_pages(params, CFG, jnp.asarray(padded), 0,
                                        jnp.int32(p_len), kv.arena, jnp.asarray(row))
    assert int(c["ssd_prefill_rows"]) == p_len * 3
    seq = list(prompt)
    want = reference_logits(params, seq)
    assert np.abs(np.asarray(logits[0]) - want[-1]).max() <= LOGIT_ATOL
    pt = jnp.asarray(kv.page_table)
    for _ in range(5):
        seq.append(int(jnp.argmax(logits[0])))
        logits, arena, c = gh.decode_step_pages(
            params, CFG, jnp.asarray([seq[-1], 0]), arena, pt,
            jnp.asarray([len(seq) - 1, 0]), jnp.asarray([False, True]))
        want = reference_logits(params, seq)
        assert np.abs(np.asarray(logits[0]) - want[-1]).max() <= LOGIT_ATOL
        assert int(c["ssd_state_steps"]) == 3
        assert int(c["decode_rows_full"]) == len(seq)


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
    _, after, _ = gh.decode_step_pages(params, CFG, jnp.asarray([3, 4, 5]), arena,
                                       pt, jnp.asarray([6, 6, 0]), done)
    for group in (1, 2):
        col = kv.group_layout[group].start
        frozen, live = int(rows[1][col]), int(rows[0][col])
        assert bool((after[group][:, :, frozen] == arena[group][:, :, frozen]).all())
        assert not bool((after[group][:, :, live] == arena[group][:, :, live]).all())
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
    clear = top[:, -1] - top[:, -2] > 1e-4
    assert clear.sum() >= new - 4
    assert (np.argmax(logits, -1) == np.asarray(req.tokens))[clear].all()
    st = eng.stats()
    assert st["model"] == "granite-4.0-h-small"
    assert st["experts_held"] == {"first": 4, "count": 4, "of": 8}
    assert st["vocab_slice"] == {"first": 96, "rows": 96, "of": 768}
    assert st["decode_attention"] == {"full": "gather"}
    assert st["ssd_state_steps"] == 3 * (new - 1)
    assert st["ssd_prefill_rows"] == 3 * p_len
    assert st["moe_picks_routed"] == 3 * st["router_tokens"]
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


@pytest.mark.parametrize("decode_chunk,late", [(1, False), (5, False), (5, True)])
def test_one_stream_whatever_the_chunk_and_the_admission(params, decode_chunk, late):
    assert _streams(params, decode_chunk, late) == _streams(params, 4)


def test_engine_stats_name_the_state_and_the_groups(params):
    eng = _engine(params)
    st = eng.stats()
    assert st["state"] == {"groups": ["ssm", "conv"], "blocks_total": 6,
                           "blocks_used": 0, "peak_blocks_used": 0,
                           "bytes_a_slot": 3 * 8 * 16 * 16 * 4 + 3 * 3 * 160 * 4,
                           "recurrence_path": "xla",
                           "prefill_recurrence_path": "xla",
                           "prefill_chunk_rows": 8}
    assert [g["name"] for g in st["groups"]] == ["full", "ssm", "conv"]
    assert st["prefill_attention"]["groups"] == {"full": "gather"}
    layout = cache_groups(serving_model(CFG), CFG, 48, BS)
    assert [(g.spec.name, g.pages) for g in layout] == [("full", 12), ("ssm", 1), ("conv", 1)]
    assert [s.name for s in state_groups(serving_model(CFG), CFG)] == ["ssm", "conv"]
    eng.close()


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
    assert "the state group 'ssm'" in str(err.value) and says in str(err.value)
    assert not serving_model(CFG).features
    require_features(serving_model(CFG), ServingConfig(), CFG)


# -- the router and the shares ----------------------------------------------------------

def test_the_published_routing_rule_is_the_softmax_rule():
    """Published: the k largest LOGITS, a softmax over those k. Served:
    `_experts.route`'s "softmax" rule (softmax over all, the k largest,
    divided by their sum). The same picks and, to rounding, the same weights
    (ties excluded: random float32 logits have none)."""
    x = jax.random.normal(jax.random.PRNGKey(2), (300, 64), jnp.float32)
    lp = {"router": 0.5 * jax.random.normal(jax.random.PRNGKey(3), (64, 72))}
    cfg = gh.GraniteHybridConfig(hidden=64, mamba_heads=8, mamba_head_dim=16, heads=4,
                                 kv_heads=2, layers=10, vocab_size=96)
    assert (cfg.n_routed_experts, cfg.experts_per_tok) == (72, 10)
    picks, w = ex.route(cfg, lp, x)
    logits = jnp.dot(x, lp["router"], precision="highest")
    best, want = jax.lax.top_k(logits, 10)
    assert float((best[:, :-1] - best[:, 1:]).min()) > 0        # no tie
    np.testing.assert_array_equal(np.asarray(picks), np.asarray(want))
    np.testing.assert_allclose(np.asarray(w), np.asarray(jax.nn.softmax(best, -1)),
                               rtol=2e-6)
    dense = ref.router(x, lp["router"], {"experts_per_tok": 10})
    np.testing.assert_allclose(
        np.asarray(jnp.take_along_axis(dense, picks, -1)), np.asarray(w), rtol=2e-6)


def test_the_shares_add_up_to_the_uncut_layer(whole):
    """Two chips hold half the experts each (as the deployment's 36 + 36). A
    chip's layer gives its routed part plus what both compute alike (the
    shared feed-forward); the two routed parts and the shared one ONCE are the
    uncut reference's layer."""
    lp = whole["layers"][2]
    x = jax.random.normal(jax.random.PRNGKey(3), (37, 64), jnp.float32)
    live = jnp.ones((37,), bool)
    with jax.default_matmul_precision("highest"):
        routed, shared, _ = ref.ffn(x, lp, REF_CFG, held=(0, E))
        uncut = np.asarray(routed + shared)
        u = ref.rms_norm(x, lp["norm2"], 1e-5)
        total = np.zeros_like(uncut)
        held_picks = 0
        for first in (0, E // 2):
            cfg = gh.GraniteHybridConfig(experts_held=(first, E // 2), **SIZES)
            part = share_of(whole, first, E // 2)["layers"][2]
            y, c = ex.moe(cfg, part, u, live)
            total += np.asarray(y) - np.asarray(shared)
            held_picks += int(c["expert_tokens"].sum())
        assert held_picks == 37 * 3              # every pick is some chip's
        assert np.abs(total + np.asarray(shared) - uncut).max() <= 5e-6
        y, _ = ex.moe(WHOLE, lp, u, live)
        assert np.abs(np.asarray(y) - uncut).max() <= 2e-6


# -- the attention layer: no rotation, the published scale -------------------------------

def test_the_attention_layer_rotates_nothing_and_scales_as_published(params):
    """In the served programs' jaxprs: no cosine and no sine anywhere (a
    rotation has both), and the scores are multiplied by 0.0078125, never by
    head_dim^-0.5 (0.25 here)."""
    kv = SlotKVCache(CFG, 2, 48, jnp.float32, block_size=BS)
    pt = jnp.asarray(kv.page_table)
    step = str(jax.make_jaxpr(lambda p, a: gh.decode_step_pages(
        p, CFG, jnp.zeros((2,), jnp.int32), a, pt, jnp.ones((2,), jnp.int32)))(
            params, kv.arena))
    prefill = str(jax.make_jaxpr(lambda p, a: gh.prefill_pages(
        p, CFG, jnp.zeros((1, 8), jnp.int32), 0, jnp.int32(5), a, pt[0]))(
            params, kv.arena))
    for text in (step, prefill):
        assert " cos " not in text and " sin " not in text
        assert "0.0078125" in text and "0.25" not in text
    # models/_grouped.py's key: the default is what every other model has
    plain = _grouped.GroupedConfig(
        vocab_size=96, hidden=64, layers=4, heads=4, kv_heads=2, head_dim=16,
        moe_intermediate=32, n_routed_experts=8, experts_per_tok=2, rms_eps=1e-5,
        rope_theta=1e4, max_pos=64, init_range=0.02, name="plain")
    assert plain.attention_scale is None
    q = jax.random.normal(jax.random.PRNGKey(0), (6, 4, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (6, 2, 16))
    by_default = _grouped.attend_rows(plain, q, k, k, "full", False)
    scaled = _grouped.attend_rows(CFG.attention, q * (0.25 / 0.0078125), k, k, "full", False)
    np.testing.assert_allclose(np.asarray(by_default), np.asarray(scaled), atol=1e-5)


def test_the_paged_kernel_takes_the_published_scale_on_its_queries(params):
    """The grouped paged kernel scales by head_dim^-0.5; the rest of the
    published scale rides on q: the kernel's step (interpreted) is the
    gather's."""
    wide = dict(SIZES, hidden=128, heads=2, kv_heads=1, mamba_heads=16)
    cfg = gh.GraniteHybridConfig(**wide)            # head_dim 64: K|V rows of 128
    p = gh.init_params(cfg, jax.random.PRNGKey(1), jnp.float32)
    kv = SlotKVCache(cfg, 2, 48, jnp.float32, block_size=BS)
    for seed in (1, 2):
        kv.map_slot(kv.alloc(), tokens_of(seed, 6), 20)
    arena = tuple(0.1 * jax.random.normal(jax.random.PRNGKey(i), a.shape, a.dtype)
                  for i, a in enumerate(kv.arena))
    args = (jnp.asarray([3, 4]), arena, jnp.asarray(kv.page_table), jnp.asarray([6, 6]),
            jnp.asarray([False, False]))
    want, _, _ = gh.decode_step_pages(p, cfg, *args, attention={"full": "gather"})
    got, _, _ = gh.decode_step_pages(p, cfg, *args, attention={"full": "paged_kernel"})
    assert float(jnp.abs(got - want).max()) <= 1e-5


# -- the step's kernel, interpreted ----------------------------------------------------

def test_the_step_kernel_is_the_recurrence_and_spares_a_frozen_slot():
    key = jax.random.split(jax.random.PRNGKey(1), 6)
    S, H, P, N = 5, 8, 8, 128
    arena = jax.random.normal(key[0], (2, 1, 9, H, P, N))
    ids = jnp.asarray([3, 1, 7, 2, 5])
    done = jnp.asarray([False, True, False, False, True])
    x = jax.random.normal(key[1], (S, H, P))
    dt = jnp.exp(jax.random.uniform(key[2], (S, H), minval=-7.0, maxval=0.0))
    A = -jnp.arange(1, H + 1, dtype=jnp.float32)
    B, C = jax.random.normal(key[3], (S, N)), jax.random.normal(key[4], (S, N))
    S_want, y_want = gh.ssd_step(arena[1, 0, ids], x, dt, A, B, C)
    live = np.asarray(~done)
    untouched = jnp.asarray([1, 5, 4, 6, 8])         # frozen slots' and nobody's
    y, new = ss.ssd_step_blocks(arena, 1, ids, done, x, dt, jnp.exp(dt * A), B, C)
    assert float(jnp.abs(y - y_want)[live].max()) <= 1e-4
    assert float(jnp.abs(new[1, 0, ids[live]] - S_want[live]).max()) <= 1e-5
    assert bool((new[0] == arena[0]).all())
    assert bool((new[1, 0, untouched] == arena[1, 0, untouched]).all())
    with pytest.raises(ValueError, match="float32"):
        ss.ssd_step_blocks(arena.astype(jnp.bfloat16), 1, ids, done, x, dt,
                           jnp.exp(dt * A), B, C)


@pytest.mark.parametrize("S,H,P,N", [(5, 8, 8, 128), (3, 128, 64, 128),
                                     (4, 5, 64, 128)])
def test_the_step_kernel_writes_the_float32_update_and_y_holds_float32(S, H, P, N):
    """The interpreted kernel at a toy block (half of one MXU product's 16
    heads), at the PUBLISHED block (128 heads of 64 x 128: 64 products of a head
    pair) and at an odd head count (the last product one head): the state it
    writes EQUALS `state * decay + dx * B` in float32 in that order, element
    for element and with no tolerance. XLA's CPU backend, which interprets the
    kernel here, is free to carry either product unrounded into the sum (a fused
    multiply-add) and chooses by the loop it emits, so an element may be any of
    the three float32 sums that order allows; the chip has no such instruction
    and tools/bench_ssd_step.py holds the one there. `y` is the float64
    contraction of that state to float32 rounding, and no block but the live
    slots' is touched."""
    key = jax.random.split(jax.random.PRNGKey(S + H), 6)
    arena = jax.random.normal(key[0], (2, 1, S + 3, H, P, N))
    ids = 1 + jax.random.permutation(key[5], S + 2)[:S]
    done = jnp.arange(S) % 3 == 1
    x = jax.random.normal(key[1], (S, H, P))
    dt = jnp.exp(jax.random.uniform(key[2], (S, H), minval=-7.0, maxval=0.0))
    decay = jnp.exp(-dt * jnp.arange(1, H + 1) / H)
    B, C = jax.random.normal(key[3], (S, N)), jax.random.normal(key[4], (S, N))
    y, new = ss.ssd_step_blocks(arena, 1, ids, done, x, dt, decay, B, C)
    live = np.asarray(~done)
    kept, fell = (np.asarray(a)[live] for a in (
        arena[1, 0, ids] * decay[..., None, None],
        (x * dt[..., None])[..., None] * B[:, None, None, :]))
    wide = lambda a, b: (np.asarray(a, np.float64)[live]     # a product, unrounded
                         * np.asarray(b, np.float64)[live])
    got = np.asarray(new[1, 0, ids])[live]
    assert ((got == kept + fell)
            | (got == (wide(arena[1, 0, ids], decay[..., None, None]) + fell
                       ).astype(np.float32))
            | (got == (kept + wide((x * dt[..., None])[..., None],
                                   B[:, None, None, :])).astype(np.float32))).all()
    y64 = np.einsum("shpn,sn->shp", got.astype(np.float64),
                    np.asarray(C, np.float64)[live])
    assert y.shape == (S, H, P) and y.dtype == jnp.float32
    assert np.linalg.norm(np.asarray(y)[live] - y64) <= 1e-6 * np.linalg.norm(y64)
    untouched = np.setdiff1d(np.arange(1, S + 3), np.asarray(ids)[live])
    assert bool((new[1, 0, untouched] == arena[1, 0, untouched]).all())
    assert bool((new[0] == arena[0]).all())


@pytest.mark.parametrize("path,kept_in", [("xla", "float32"), ("kernel", "float32"),
                                          ("xla", "bfloat16")])
def test_the_steps_two_halves_are_the_step_and_the_reference(params, path, kept_in):
    """`ssd_step_inputs` then `ssd_state_update` ARE a mamba layer's step (what
    `decode_step_pages` runs, and what the cell's state-step limit runs on the
    engine's own blocks), on a float32 state arena beside a BFLOAT16 history:
    the state that comes back is the reference's float32 `ssd_step` on the same
    block and operands to float32 rounding, by either path; kept in bfloat16 it
    is a thousandth off, which is how the limit tells."""
    lp, n = params["layers"][0], 3
    key = jax.random.split(jax.random.PRNGKey(9), 3)
    state = (0.1 * jax.random.normal(key[0], (1, 1, n + 1) + CFG.state_shape)
             ).astype(kept_in)
    conv = (0.1 * jax.random.normal(
        key[1], (1, 1, n + 1) + _recurrent.history_shape(3, CFG.conv_width))
        ).astype(jnp.bfloat16)
    u = jax.random.normal(key[2], (n, CFG.hidden)).astype(jnp.bfloat16)
    lp = dict(lp, w_in=lp["w_in"].astype(jnp.bfloat16))
    ids, done = jnp.arange(1, n + 1), jnp.asarray([False, True, False])
    arenas = {gh.SSM: state, gh.CONV: conv}
    x, dt, B, C, z, arenas = gh.ssd_step_inputs(CFG, lp, u, arenas, 0, ids, done)
    y, arenas = gh.ssd_state_update(lp, arenas, 0, ids, done, x, dt, B, C, path)
    A = -jnp.exp(lp["a_log"])
    want_S, want_y = jax.vmap(ref.ssd_step, (0, 0, 0, None, 0, 0))(
        state[0, 0, ids].astype(jnp.float32), x, dt, A, B, C)
    got_S = arenas[gh.SSM][0, 0, ids].astype(jnp.float32)
    size = lambda a: float(jnp.sqrt(jnp.sum(a * a)))
    error = size((got_S - want_S)[::2]) / size(want_S[::2])   # the live slots
    if kept_in == "float32":
        assert error < 1e-6 and float(jnp.abs(y - want_y)[::2].max()) < 1e-5
    else:
        assert 1e-4 < error < 1e-2
    # the frozen slot's block and history are as they were; scratch took its writes
    assert bool((arenas[gh.SSM][0, 0, 2] == state[0, 0, 2]).all())
    assert bool((arenas[gh.CONV][0, 0, 2] == conv[0, 0, 2]).all())
    # a live slot's history moved one row on: its last row is the new one
    assert arenas[gh.CONV].dtype == jnp.bfloat16
    assert not bool((arenas[gh.CONV][0, 0, 1] == conv[0, 0, 1]).all())


def test_the_decode_step_through_the_kernel_is_the_decode_step(params):
    kv = SlotKVCache(CFG, 2, 48, jnp.float32, block_size=BS)
    for seed in (1, 2):
        kv.map_slot(kv.alloc(), tokens_of(seed, 6), 20)
    arena = tuple(0.1 * jax.random.normal(jax.random.PRNGKey(i), a.shape, a.dtype)
                  for i, a in enumerate(kv.arena))
    pt = jnp.asarray(kv.page_table)
    args = (jnp.asarray([3, 4]), arena, pt, jnp.asarray([6, 6]),
            jnp.asarray([False, False]))
    want, arena_x, _ = gh.decode_step_pages(params, CFG, *args, recurrence="xla")
    got, arena_k, _ = gh.decode_step_pages(params, CFG, *args, recurrence="kernel")
    assert float(jnp.abs(got - want).max()) <= 1e-5
    assert float(jnp.abs(arena_k[1][:, :, 1:] - arena_x[1][:, :, 1:]).max()) <= 1e-5
    assert gh.recurrence_path(CFG) == "xla"                   # the CPU


# -- compiled for the chip, without the chip ------------------------------------------

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


@pytest.mark.parametrize("frozen", [False, True])
def test_the_step_kernel_compiles_for_a_described_v5e(one_chip, frozen):
    """The published widths (128 heads of 64 x 128 float32, a 4 MB block a
    slot) over the cell's 96 slots and nine layers, without and with frozen
    slots sent to scratch: the lane-sliced column reads, the decay's 48 KB of
    scalar memory, the 64 transposed-operand products at `HIGHEST` with their
    one-row stores and the 16 MB of double-buffered blocks are Mosaic's to
    refuse, and nothing of the arena's size is made beside the call."""
    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    S, H, P, N = 96, 128, 64, 128

    def step(arena, ids, done, x, dt, decay, B, C):
        return ss.ssd_step_blocks(arena, 3, ids, done if frozen else None, x, dt,
                                  decay, B, C)

    real = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        compiled = jax.jit(step, donate_argnums=(0,)).lower(
            shape(9, 1, S + 1, H, P, N), shape(S, dtype=jnp.int32),
            shape(S, dtype=jnp.bool_), shape(S, H, P), shape(S, H), shape(S, H),
            shape(S, N), shape(S, N)).compile()
    finally:
        jax.default_backend = real
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1
    # the arena leaves the call as the buffer it came in
    assert compiled.memory_analysis().temp_size_in_bytes < (16 << 20)
