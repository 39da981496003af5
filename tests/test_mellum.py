"""Mellum2-12B-A2.5B-Instruct's block (paddle_tpu.models.mellum) at a small
size on the CPU: grouped KV heads, window layers beside full ones in a
cache of two groups, softmax top-k experts.

The reference is benchmarks/reference/mellum_ref.py (float32, highest
precision, no cache, independent of the program). Pinned here: the served
path against it at every served position, below and beyond the window and
across a ring wrap; the ring's bound on a slot's rows; a frozen slot's
writes; admission against BOTH pools; the banded flash forward and the
grouped paged kernel (interpreted) against `mha_reference` under the same
mask; the softmax routing rule by hand; and the programs of the models
that have ONE cache group, which this PR must leave as the parent's."""

import hashlib
import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from reference import mellum_ref as ref                      # noqa: E402

from paddle_tpu.models import _experts as ex                 # noqa: E402
from paddle_tpu.models import _grouped as gr                 # noqa: E402
from paddle_tpu.models import mellum as mm                   # noqa: E402
from paddle_tpu.models import moonlight as ml                # noqa: E402
from paddle_tpu.ops.flash_attention import (flash_causal_rows,  # noqa: E402
                                            mha_reference)
from paddle_tpu.ops.paged_attention import paged_attention   # noqa: E402
from paddle_tpu.serving import (ServingConfig, ServingEngine,  # noqa: E402
                                SlotKVCache)
from paddle_tpu.serving.model import (CacheSpec, cache_groups,  # noqa: E402
                                      ring_pages, serving_model)

WINDOW, BS = 8, 4
YARN = {"type": "yarn", "factor": 4, "original_max_position_embeddings": 16,
        "beta_fast": 32, "beta_slow": 1}
CFG = mm.MellumConfig(vocab_size=211, hidden=64, layers=8, heads=4,
                      kv_heads=1, head_dim=16, moe_intermediate=32,
                      n_routed_experts=8, experts_per_tok=2,
                      sliding_window=WINDOW, max_pos=64, rope_scaling=YARN,
                      init_range=0.08)
# the same model under the published keys, as the reference reads them
REF_CFG = {
    "head_dim": 16, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 1, "num_hidden_layers": 8,
    "layer_types": list(CFG.layer_types), "sliding_window": WINDOW,
    "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000.0,
                           "factor": 4, "beta_fast": 32, "beta_slow": 1,
                           "original_max_position_embeddings": 16,
                           "attention_factor": 0.1 * math.log(4) + 1.0},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000.0}}}
LOGIT_ATOL = 2e-4
RING = ring_pages(WINDOW, BS)                       # 3 blocks a slot


def tokens_of(seed, n):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n) \
        .astype(np.int32)


@pytest.fixture(scope="module")
def params():
    return mm.init_params(CFG, jax.random.PRNGKey(3), jnp.float32)


def reference_logits(params, seq):
    return np.asarray(ref.sequence_logits(params, REF_CFG, list(seq)))


# -- the config and its cache groups -------------------------------------------

def test_layer_kinds_and_cache_groups():
    assert CFG.layer_types == (gr.WINDOW,) * 3 + (gr.FULL,) \
        + (gr.WINDOW,) * 3 + (gr.FULL,)
    assert [CFG.index_in_group(i) for i in range(8)] == [0, 1, 2, 0, 3, 4, 5, 1]
    full, window = serving_model(CFG).cache_spec(CFG)
    assert full == CacheSpec(2, 1, 32, None, "full")
    assert window == CacheSpec(6, 1, 32, WINDOW, "window")
    layout = cache_groups(serving_model(CFG), CFG, 48, BS)
    assert [(g.start, g.pages) for g in layout] == [(0, 12), (12, RING)]
    assert layout[1].columns == slice(12, 15)
    # the published depth: 7 full layers and 21 window layers, 3 : 1
    big = mm.MellumConfig()
    assert (big.heads, big.kv_heads, big.group, big.layers) == (32, 4, 8, 28)
    assert sum(t == gr.FULL for t in big.layer_types) == 7
    assert big.serving_model() is mm.MELLUM_SERVING_MODEL
    with pytest.raises(ValueError, match="window layers alone"):
        mm.MellumConfig(layers=2, layer_types=[gr.WINDOW] * 2)
    with pytest.raises(ValueError, match="primary"):
        cache_groups(type("M", (), {"cache_spec": lambda self, cfg: (
            CacheSpec(1, 1, 8, 4, "w"),)})(), None, 16, 4)


def test_a_config_of_full_layers_alone_is_one_group():
    cfg = mm.MellumConfig(vocab_size=211, hidden=64, layers=2, heads=4,
                          kv_heads=2, head_dim=16, moe_intermediate=32,
                          n_routed_experts=8, experts_per_tok=2,
                          layer_types=[gr.FULL] * 2, max_pos=64)
    assert serving_model(cfg).cache_spec(cfg) == CacheSpec(2, 2, 32, None,
                                                           "full")
    kv = SlotKVCache(cfg, 2, 32, jnp.float32, block_size=4)
    assert not isinstance(kv.arena, tuple) and kv.table_width == kv.max_pages
    assert "groups" not in kv.occupancy()


# -- the whole sequence against the reference ----------------------------------

@pytest.mark.parametrize("length", [5, WINDOW, 3 * WINDOW + 5])
def test_forward_matches_the_reference(params, length):
    seq = tokens_of(length, length)
    got = np.asarray(mm.forward_logits(params, CFG, jnp.asarray(seq)))
    assert np.abs(got - reference_logits(params, seq)).max() <= LOGIT_ATOL


def test_the_window_counts_its_own_position(params):
    """Position i attends i - window < j <= i: a key `window` back is out.
    Changing token 0 moves position WINDOW - 1 in a window layer and not
    position WINDOW; with every layer a window layer (the full layers'
    mask given a window too) nothing past the window sees it at all."""
    cfg = dict(REF_CFG, layer_types=["sliding_attention"] * 8)
    a = tokens_of(1, 20)
    b = a.copy()
    b[0] = (b[0] + 1) % CFG.vocab_size
    la = np.asarray(ref.sequence_logits(params, cfg, list(a)))
    lb = np.asarray(ref.sequence_logits(params, cfg, list(b)))
    # 8 window layers reach 8 x (WINDOW - 1) positions back at most; one
    # layer's reach is what the mask decides
    one = dict(cfg, num_hidden_layers=1, layer_types=["sliding_attention"])
    p1 = dict(params, layers=params["layers"][:1])
    la1 = np.asarray(ref.sequence_logits(p1, one, list(a)))
    lb1 = np.asarray(ref.sequence_logits(p1, one, list(b)))
    assert np.abs(la1[WINDOW - 1] - lb1[WINDOW - 1]).max() > 1e-6
    assert np.abs(la1[WINDOW:] - lb1[WINDOW:]).max() == 0.0
    assert np.abs(la - lb).max() > 0


# -- routing ---------------------------------------------------------------------

def test_softmax_routing_against_a_hand_written_top_k(params):
    lp = params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(9), (7, CFG.hidden), jnp.float32)
    picks, w = ex.route(CFG, lp, x)
    logits = np.asarray(x, np.float64) @ np.asarray(lp["router"], np.float64)
    for t in range(7):
        p = np.exp(logits[t] - logits[t].max())
        p /= p.sum()
        best = np.argsort(-p)[:CFG.experts_per_tok]
        assert list(np.asarray(picks[t])) == list(best)
        np.testing.assert_allclose(np.asarray(w[t]), p[best] / p[best].sum(),
                                   rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)
    # the other rule is the latent block's still: a config without the
    # field is sigmoid + bias + factor
    assert not hasattr(ml.MoonlightConfig(), "router_scoring")


def test_the_expert_layer_is_moonlights_without_a_shared_expert(params):
    lp = params["layers"][1]
    assert "shared_gate" not in lp and "router_bias" not in lp
    x = jax.random.normal(jax.random.PRNGKey(4), (6, CFG.hidden), jnp.float32)
    live = jnp.asarray([True] * 5 + [False])
    text = str(jax.make_jaxpr(lambda x: ex.moe(CFG, lp, x, live))(x))
    y, c = ex.moe(CFG, lp, x, live)
    assert int(c["router_tokens"]) == 5
    assert int(c["expert_tokens"].sum()) == 5 * CFG.experts_per_tok
    picks, w = ex.route(CFG, lp, x)
    by_hand = np.zeros((6, CFG.hidden), np.float32)
    for t in range(5):
        for e, we in zip(np.asarray(picks[t]), np.asarray(w[t])):
            by_hand[t] += we * np.asarray(ex.swiglu(
                x[t][None], lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e]))[0]
    np.testing.assert_allclose(np.asarray(y), by_hand, atol=1e-5)
    assert "logistic" in text                        # the experts' silu
    assert float(jnp.abs(y[5]).max()) == 0.0         # a row that is not live


# -- through the pages -----------------------------------------------------------

_PREFILL = jax.jit(lambda params, *a: mm.prefill_pages(params, CFG, *a))
_PATHS = {"gather": {"full": "gather", "window": "gather"},
          "paged_kernel": {"full": "paged_kernel", "window": "paged_kernel"}}
_DECODE = {name: jax.jit(lambda params, *a, path=path: mm.decode_step_pages(
    params, CFG, *a, attention=path)) for name, path in _PATHS.items()}


def _prefill(params, arena, kv, slot, prompt, bucket):
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(prompt)] = prompt
    logits, arena, counters = _PREFILL(
        params, jnp.asarray(padded), jnp.int32(0), jnp.int32(len(prompt)),
        arena, jnp.asarray(kv.page_table[slot]))
    return np.asarray(logits[0]), arena, counters


@pytest.mark.parametrize("attention", ["gather", "paged_kernel"])
def test_prefill_then_decode_steps_match_the_reference(params, attention):
    """Three slots: prompts shorter than the window (5), equal to it (8)
    and several times it (27, its ring of 3 pages already wrapped twice by
    the prefill), the second FROZEN through the steps; 14 steps, so the
    short prompt's decode leaves the window and its ring wraps. Every
    step's logits of every live slot against the reference's full
    forward, the frozen slot's blocks untouched in BOTH groups, and the
    rows attended counted exactly."""
    steps = 14
    kv = SlotKVCache(CFG, 3, 48, jnp.float32, block_size=BS)
    arena = kv.arena
    prompts = {0: tokens_of(20, 5), 1: tokens_of(21, WINDOW),
               2: tokens_of(22, 27)}
    seqs = {s: list(p) + list(tokens_of(50 + s, steps))
            for s, p in prompts.items()}
    want = {s: reference_logits(params, seq) for s, seq in seqs.items()}
    for s, prompt in prompts.items():
        assert kv.alloc() == s
        row, hit = kv.map_slot(s, prompt, len(prompt) + steps)
        assert hit == 0 and row.shape == (12 + RING,)
        logits, arena, _ = _prefill(params, arena, kv, s, prompt, 32)
        assert np.abs(logits - want[s][len(prompt) - 1]).max() <= LOGIT_ATOL
        # the ring holds its bound whatever the slot's length; the full
        # group holds every page
        rows = kv.group_rows(s)
        assert rows["window"] <= WINDOW + BS
        assert rows["full"] == kv.blocks_for(len(prompt) + steps) * BS
    assert kv.group_rows(2) == {"full": 44, "window": 12}
    pt = jnp.asarray(kv.page_table)
    done = jnp.asarray([False, True, False])
    ts = jnp.asarray([len(prompts[s]) for s in range(3)], jnp.int32)
    frozen = [np.asarray(a)[:, :, kv.page_table[1][cols][
        kv.page_table[1][cols] > 0]] for a, cols in
        zip(arena, (slice(0, 12), slice(12, 15)))]
    scratch = [np.asarray(a)[:, :, 0] for a in arena]
    rows_full = rows_window = 0
    for i in range(steps):
        tok = jnp.asarray([seqs[s][len(prompts[s]) + i] for s in range(3)],
                          jnp.int32)
        logits, arena, c = _DECODE[attention](params, tok, arena, pt, ts, done)
        for s in (0, 2):
            at = len(prompts[s]) + i
            assert np.abs(np.asarray(logits[s]) - want[s][at]).max() \
                <= LOGIT_ATOL, (attention, s, at)
            rows_full += 2 * (at + 1)
            rows_window += 6 * min(at + 1, WINDOW)
        assert int(c["decode_rows_full"]) == sum(
            2 * (len(prompts[s]) + i + 1) for s in (0, 2))
        assert int(c["decode_rows_window"]) == sum(
            6 * min(len(prompts[s]) + i + 1, WINDOW) for s in (0, 2))
        ts = ts + 1
    # the frozen slot's own blocks are as its prefill left them, in both
    # groups; the kernel writes nothing for it, the gather scratch alone
    for a, before, cols, zero in zip(arena, frozen, (slice(0, 12),
                                                     slice(12, 15)), scratch):
        blocks = kv.page_table[1][cols]
        np.testing.assert_array_equal(
            np.asarray(a)[:, :, blocks[blocks > 0]], before)
        if attention == "paged_kernel":
            np.testing.assert_array_equal(np.asarray(a)[:, :, 0], zero)
    if attention == "gather":
        assert any(np.abs(np.asarray(a)[:, :, 0]).max() > 0 for a in arena)


def test_a_prefill_writes_the_window_group_only_what_its_ring_holds(params):
    """A prompt of 27 rows (7 pages): the full group's pages all written,
    the window group's ring holds pages 4, 5, 6 at entries 1, 2, 0; no
    other block of the window arena is touched but scratch."""
    kv = SlotKVCache(CFG, 2, 48, jnp.float32, block_size=BS)
    kv.alloc()
    prompt = tokens_of(5, 27)
    row, _ = kv.map_slot(0, prompt, 40)
    _, arena, _ = _prefill(params, kv.arena, kv, 0, prompt, 32)
    window = np.asarray(arena[1])
    ring = row[12:15]
    touched = {int(b) for b in range(window.shape[2])
               if np.abs(window[:, :, b]).max() > 0}
    assert touched <= {0} | {int(b) for b in ring}
    # page t of the prompt lies in ring entry t % 3: compare K|V of a layer
    # with the same rows written by a prompt of whole pages from row 16 on
    x = jnp.asarray(params["wte"])[jnp.asarray(prompt)]
    q, k, v = mm._project(CFG, params["layers"][0], x, jnp.arange(27),
                          "window")
    for t in (4, 5, 6):
        n = min(BS, 27 - t * BS)
        got = window[0, 0, ring[t % RING], 0, :n]
        np.testing.assert_allclose(
            got, np.concatenate([k, v], -1)[t * BS:t * BS + n, 0], atol=1e-6)


# -- the engine -------------------------------------------------------------------

def _engine(params, **kw):
    kw.setdefault("num_slots", 3)
    kw.setdefault("prefill_buckets", (8, 16, 32))
    kw.setdefault("max_len", 48)
    kw.setdefault("block_size", BS)
    return ServingEngine(params, CFG, ServingConfig(**kw))


@pytest.mark.parametrize("p_len,new", [(3, 12), (WINDOW, 20), (27, 15),
                                       (5, 30)])
def test_the_engine_serves_the_references_greedy_tokens(params, p_len, new):
    """Through submit -> scheduler -> cache -> decode_loop: prompts below,
    at and beyond the window, every request crossing it or wrapping its
    ring; each served token is the argmax of the reference's logits on
    prompt + tokens (where the reference's top two are apart)."""
    eng = _engine(params)
    req = eng.submit(tokens_of(p_len + new, p_len), max_new_tokens=new)
    eng.run_until_drained()
    out = req.output()
    assert len(req.tokens) == new
    logits = reference_logits(params, out)[p_len - 1:-1]
    top = np.sort(logits, -1)
    clear = top[:, -1] - top[:, -2] > 1e-3
    assert clear.sum() >= new - 2
    assert (np.argmax(logits, -1) == np.asarray(req.tokens))[clear].all()
    st = eng.stats()
    assert st["model"] == "Mellum2-12B-A2.5B-Instruct"
    assert st["decode_attention"] == {"full": "gather", "window": "gather"}
    assert st["prefill_attention"]["groups"] == {"full": "gather",
                                                 "window": "gather"}
    assert st["prefix_cache"].startswith("off") and st["prefix_hits"] == 0
    assert [g["name"] for g in st["groups"]] == ["full", "window"]
    assert sum(st["expert_tokens"]) == CFG.experts_per_tok * st["router_tokens"]
    assert st["decode_rows_full"] > 0 and st["decode_rows_window"] > 0
    assert st["moe_combine_kernel_passes"] == 0          # the CPU gathers
    assert st["compiled_executables"] <= 3 + 2
    eng.close()


def test_the_ring_never_holds_more_than_window_plus_block(params):
    """Occupancy while serving: whatever the slots' lengths, a slot's
    window group holds at most window + block rows and the full group all
    of them; both pools' gauges move and come back."""
    eng = _engine(params)
    reqs = [eng.submit(tokens_of(70 + i, n), max_new_tokens=m)
            for i, (n, m) in enumerate([(30, 16), (6, 20), (12, 9)])]
    seen = 0
    while not all(r.finished for r in reqs):
        eng.step()
        for slot in range(3):
            rows = eng.kv.group_rows(slot)
            assert rows["window"] <= WINDOW + BS
            if rows["full"]:
                seen = max(seen, rows["full"])
                assert rows["window"] == min(rows["full"], WINDOW + BS)
        full, window = eng.stats()["groups"]
        assert window["blocks_used"] <= 3 * RING
        assert full["blocks_used"] <= full["blocks_total"]
    assert seen == 48                                   # 30 + 16 -> 12 pages
    full, window = eng.stats()["groups"]
    assert (full["blocks_used"], window["blocks_used"]) == (0, 0)
    assert window["peak_blocks_used"] == 3 * RING == window["blocks_total"]
    assert full["peak_blocks_used"] == 12 + 7 + 6
    assert eng.stats()["pool_bytes"] == full["pool_bytes"] + window["pool_bytes"]
    eng.close()


@pytest.mark.parametrize("short", ["full", "window"])
def test_admission_queues_when_either_pool_is_short(params, short):
    """Two requests that each fit, on a pool that holds one of them: the
    second waits in the queue until the first has finished and is then
    served whole, whichever group's pool was the short one."""
    blocks = {"full": (12 + 1, 3 * RING + 1), "window": (3 * 12 + 1, RING + 2)}
    eng = _engine(params, kv_blocks=blocks[short])
    a = eng.submit(tokens_of(1, 20), max_new_tokens=12)
    b = eng.submit(tokens_of(2, 18), max_new_tokens=10)
    eng.step()
    assert a.state == "running" and b.state == "queued"
    assert eng.stats()["active_slots"] == 1 and eng.stats()["free_slots"] == 2
    eng.run_until_drained()
    assert len(a.tokens) == 12 and len(b.tokens) == 10
    want = reference_logits(params, b.output())[17:-1]
    top = np.sort(want, -1)
    clear = top[:, -1] - top[:, -2] > 1e-3
    assert (np.argmax(want, -1) == np.asarray(b.tokens))[clear].all()
    eng.close()
    # a request no pool could ever hold is refused at the door
    tiny = _engine(params, kv_blocks=(3, 2))
    with pytest.raises(ValueError, match="blocks"):
        tiny.submit(tokens_of(3, 20), max_new_tokens=12)
    tiny.close()


@pytest.mark.parametrize("option", ["kv_dtype", "speculate_k", "prefill_chunk",
                                    "mesh_shape", "max_adapters",
                                    "weight_dtype", "preempt"])
def test_what_the_block_does_not_implement_is_refused_at_construction(
        params, option):
    value = {"kv_dtype": "int8", "speculate_k": 2, "prefill_chunk": 8,
             "mesh_shape": (2,), "max_adapters": 2, "weight_dtype": "int8",
             "preempt": True}[option]
    extra = {"adapter_rank": 4} if option == "max_adapters" else {}
    with pytest.raises(ValueError, match="does not implement|cache groups"):
        _engine(params, **{option: value}, **extra)


def test_migration_of_a_model_with_two_groups_is_refused(params):
    from paddle_tpu.serving.migration import MigrationError
    eng = _engine(params)
    req = eng.submit(tokens_of(4, 6), max_new_tokens=8)
    eng.step()
    with pytest.raises(MigrationError, match="cache groups"):
        eng.migrate_out(req)
    with pytest.raises(MigrationError, match="cache groups"):
        eng.migrate_in(None)
    eng.run_until_drained()
    assert len(req.tokens) == 8
    eng.close()


# -- the kernels, interpreted -------------------------------------------------------

def _band_reference(q, k, v, scale, window):
    rows, group = q.shape[0], q.shape[1] // k.shape[1]
    i = jnp.arange(rows)
    mask = i[None, :] <= i[:, None]
    if window is not None:
        mask = mask & (i[:, None] - i[None, :] < window)
    bias = jnp.where(mask, 0.0, -1e30)[None, None]
    return mha_reference(q[None], jnp.repeat(k, group, 1)[None],
                         jnp.repeat(v, group, 1)[None], bias=bias,
                         sm_scale=scale)[0]


@pytest.mark.parametrize("rows,window", [(256, None), (256, 100), (2048, 1024),
                                         (2048, 700), (1536, 128)])
def test_banded_flash_forward_with_shared_kv_heads(rows, window):
    """4 query heads over 2 KV heads, one tile and tiles of 512: a query
    tile visits only the KV tiles that touch its window."""
    ks = jax.random.split(jax.random.PRNGKey(rows), 3)
    q = jax.random.normal(ks[0], (rows, 4, 32))
    k = jax.random.normal(ks[1], (rows, 2, 32))
    v = jax.random.normal(ks[2], (rows, 2, 32))
    got = flash_causal_rows(q, k, v, 0.2, window=window)
    want = _band_reference(q, k, v, 0.2, window)
    assert float(jnp.abs(got - want).max()) <= 2e-6


@pytest.mark.parametrize("window,visits", [(1024, 93), (None, 136)])
def test_the_band_visits_the_tiles_of_its_window_alone(window, visits):
    """The grid of a 16,384-row layer is (heads, visits): a window layer
    walks 93 (query tile, KV tile) pairs of 512 a head (1 + 2 + 30 x 3:
    the 9 tiles of 128 a 1,024-row window touches), a full layer the
    triangle's 136 pairs of 1,024 (528 of 512: tiles of 1,024 from 8,192
    rows); no step of either only fetches."""
    from paddle_tpu.ops.flash_attention import causal_rows_tiles
    q = jnp.zeros((16384, 8, 128), jnp.bfloat16)
    k = jnp.zeros((16384, 1, 128), jnp.bfloat16)
    text = str(jax.make_jaxpr(
        lambda q, k: flash_causal_rows(q, k, k, 0.1, window=window))(q, k))
    assert f"grid=(8, {visits})" in text
    assert causal_rows_tiles(16384, None, window) == (visits, visits)


@pytest.mark.parametrize("mode", ["full", "ring"])
def test_grouped_paged_kernel_against_mha_reference(mode):
    """3 slots, 2 KV heads of 4 query heads each, pages of 4 rows: a full
    table walked from 0, and a ring of 3 blocks walked from ts - 7 with
    the table read modulo its width; the step's own row written through
    the live page."""
    S, kvh, g, hd = 3, 2, 4, 16
    rng = np.random.default_rng(0)
    P = 12 if mode == "full" else RING
    arena = jnp.asarray(rng.normal(size=(2, 1, 40, kvh, BS, 2 * hd)),
                        jnp.float32)
    table = rng.permutation(np.arange(1, 40))[:S * P].reshape(S, P) \
        .astype(np.int32)
    ts = np.asarray([5, 23, 40], np.int32)
    q, k, v = (jnp.asarray(rng.normal(size=(S, n, hd)), jnp.float32)
               for n in (kvh * g, kvh, kvh))
    lo = np.maximum(ts - WINDOW + 1, 0) if mode == "ring" else np.zeros(3, int)
    got, after = paged_attention(
        q, k, v, arena, 1, jnp.asarray(table), jnp.asarray(ts),
        jnp.asarray([False, False, True]),
        lo=jnp.asarray(lo, jnp.int32) if mode == "ring" else None)
    a = np.array(arena)
    for s in range(2):
        t = int(ts[s])
        a[1, 0, table[s, (t // BS) % P], :, t % BS] = np.concatenate(
            [k[s], v[s]], -1)
        pos = np.arange(lo[s], t + 1)
        rows = np.stack([a[1, 0, table[s, (p // BS) % P], :, p % BS]
                         for p in pos], 0)                  # (n, kvh, 2hd)
        want = mha_reference(
            jnp.asarray(q[s])[None, None], jnp.repeat(
                jnp.asarray(rows[None, :, :, :hd]), g, 2),
            jnp.repeat(jnp.asarray(rows[None, :, :, hd:]), g, 2))[0, 0]
        assert float(jnp.abs(got[s] - want).max()) <= 2e-6
    assert float(jnp.abs(got[2]).max()) == 0.0            # frozen: zeros,
    np.testing.assert_array_equal(np.asarray(after), a)   # and no write



def _one_a_call(q, k, v, arena, table, ts, done, lo):
    """The same slots, ONE A CALL in their order on the same arena: what a
    slot gets and writes when no other slot's pages are anywhere near."""
    outs = []
    for s in range(len(ts)):
        one = slice(s, s + 1)
        out, arena = paged_attention(
            q[one], k[one], v[one], arena, 1, table[one], ts[one], done[one],
            lo=None if lo is None else lo[one])
        outs.append(out)
    return jnp.concatenate(outs, 0), arena


# (page-table width, window or None, ts a slot, the frozen slots): pages of
# 4 rows, so a group of the walk is 16 rows and a full table of 12 pages
# three groups; the window of 40 rows has a ring of 11 blocks (three groups
# too) that positions past 44 have wrapped, and lo = ts - 39 begins
# mid-page wherever ts + 1 is no multiple of 4
_STREAMS = {
    # every cut of a walk: one row, a page, a page and a row, a group, a
    # group and a row, two groups, the whole table; frozen slots first,
    # last and between live ones
    "every_length": (12, None, [9, 0, 3, 4, 30, 15, 16, 31, 32, 7, 47, 20],
                     [0, 4, 9, 11]),
    "one_live_slot": (12, None, [5, 23, 40, 9], [0, 1, 3]),
    "no_live_slot": (12, None, [5, 23, 40], [0, 1, 2]),
    "few_pages_beside_many": (12, None, [2, 47, 1, 44, 6, 46], []),
    "ring_wrapped_mid_page": (11, 40, [97, 45, 12, 63, 130, 38, 81, 99],
                              [2, 6]),
    "ring_of_one_group": (RING, WINDOW, [5, 23, 40, 9, 2, 30], [4]),
}


@pytest.mark.parametrize("case", sorted(_STREAMS))
def test_the_grouped_walk_streams_across_slots_and_leaks_nothing(case):
    """The walk's page groups are ONE stream across the live slots of a
    call: every slot's output and the WHOLE arena against a float64 gather,
    and bit for bit against the same slots run one a call."""
    P, window, ts, frozen = _STREAMS[case]
    S, kvh, g, hd = len(ts), 2, 4, 16
    rng = np.random.default_rng(len(case))
    blocks = 1 + S * P
    arena = jnp.asarray(rng.normal(size=(2, 1, blocks, kvh, BS, 2 * hd)),
                        jnp.float32)
    table = (1 + rng.permutation(S * P)).reshape(S, P).astype(np.int32)
    ts = np.asarray(ts, np.int32)
    done = np.isin(np.arange(S), frozen)
    lo = None if window is None else np.maximum(ts - window + 1, 0) \
        .astype(np.int32)
    q, k, v = (jnp.asarray(rng.normal(size=(S, n, hd)), jnp.float32)
               for n in (kvh * g, kvh, kvh))
    slots = (jnp.asarray(table), jnp.asarray(ts), jnp.asarray(done))
    bound = None if lo is None else jnp.asarray(lo)
    got, after = paged_attention(q, k, v, arena, 1, *slots, lo=bound)
    alone, after_alone = _one_a_call(q, k, v, arena, *slots, bound)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(alone))
    np.testing.assert_array_equal(np.asarray(after), np.asarray(after_alone))

    a = np.array(arena)
    for s in np.flatnonzero(~done):
        t = int(ts[s])
        a[1, 0, table[s, (t // BS) % P], :, t % BS] = np.concatenate(
            [k[s], v[s]], -1)
    np.testing.assert_array_equal(np.asarray(after), a)   # frozen: no write
    for s in range(S):
        if done[s]:
            assert float(jnp.abs(got[s]).max()) == 0.0   # and zeros
            continue
        pos = np.arange(0 if lo is None else lo[s], ts[s] + 1)
        rows = a[1, 0, table[s, (pos // BS) % P], :, pos % BS] \
            .astype(np.float64)                           # (n, kvh, 2hd)
        qs = np.asarray(q[s], np.float64).reshape(kvh, g, hd)
        sc = np.einsum("kgd,nkd->kgn", qs, rows[..., :hd]) / math.sqrt(hd)
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        want = np.einsum("kgn,nkd->kgd", pr / pr.sum(-1, keepdims=True),
                         rows[..., hd:]).reshape(kvh * g, hd)
        assert np.abs(np.asarray(got[s]) - want).max() <= 2e-6


# -- the models with one cache group: the parent's programs ---------------------

# sha256 (16 hex) of str(jax.make_jaxpr(...)) at the sizes below, computed on
# the parent commit (2310bf9, PR 32) by the same code under tests/conftest.py: a model with one
# cache group is served by the program it had, and the kernels GPT and the
# latent block run are traced as they were. PR 34 re-pinned the two
# `kernel.flash_causal_rows*` digests on its own final tree: the forward's
# grid became a walk of visits read from scalar-prefetch operands (one visit
# at 256 rows, ten at 2,048), so both jaxprs changed; the six programs (the
# CPU's gather path) and the small-path `fwd_bwd` did not. PR 37 re-pinned the
# four programs of the latent block on its own final tree: their expert layer
# lays the routed rows out by counting (models/moonlight.py::_moe: no sort, one
# scatter, gathers by position), so every program that has one changed; the
# GPT pair and the five kernels did not. PR 39 re-pinned the same four on its
# own final tree: the expert layer counts one thing more (`combine_kernel_passes`,
# one more output of each program and one more sum a layer); the LAYER without
# that counter traces what the parent's traced at these sizes, below the size
# from which its combine is a kernel (tests/test_routed_combine.py pins that).
# Xing's pair moved once more in PR 39: its mixer hands its coefficients on
# with the token axis last (`hc_coefficients`; the same values, no transpose)
PARENT = {
    "moonlight.prefill": "ae0b61f53b4a37cc",
    "moonlight.decode": "0094c59aec2ba357",
    "xing.prefill": "bc235cb67316d178",
    "xing.decode": "407cd2e8c13894df",
    "gpt.prefill": "3ac9276678295d6b",
    "gpt.decode": "0f3268c7f5194142",
    "kernel.paged_attention": "aee9f347c35d6388",
    "kernel.latent_paged_attention": "de5b060cb2ed656e",
    "kernel.flash_causal_rows": "2df3b31c10b0753f",
    "kernel.flash_causal_rows.2048": "d261309d8fd22c3e",
    "kernel.flash_attention.fwd_bwd": "a46a861a21af2956",
}
_SIZES = dict(vocab_size=211, hidden=64, layers=3, heads=4, kv_lora_rank=32,
              qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
              intermediate=96, moe_intermediate=32, n_routed_experts=8,
              n_shared_experts=1, experts_per_tok=2, max_pos=64)


def _digest(fn, *args):
    return hashlib.sha256(str(jax.make_jaxpr(fn)(*args)).encode()) \
        .hexdigest()[:16]


def _served(cfg, params, which):
    model = serving_model(cfg)
    kv = SlotKVCache(cfg, 3, 48, jnp.float32, block_size=4)
    arena, pt = kv.arena, jnp.asarray(kv.page_table)
    if which == "prefill":
        return _digest(
            lambda p, a, t, pg: model.prefill(p, cfg, t, jnp.int32(0),
                                              jnp.int32(9), a, pg),
            params, arena, jnp.zeros((1, 16), jnp.int32), pt[0])
    return _digest(
        lambda p, a, t, ts, d: model.decode_step(p, cfg, t, a, pt, ts, d),
        params, arena, jnp.zeros((3,), jnp.int32), jnp.ones((3,), jnp.int32),
        jnp.zeros((3,), bool))


def _one_group_model(name):
    if name == "gpt":
        import paddle_tpu as pt
        from paddle_tpu.models import gpt_decode as gd
        from paddle_tpu.models.gpt import GPTConfig, gpt_lm_program
        cfg = GPTConfig(vocab_size=97, hidden=32, layers=2, heads=4,
                        max_pos=64, dropout=0.0, attn_impl="xla")
        _, startup, _ = gpt_lm_program(cfg, 8, is_test=True)
        exe, scope = pt.Executor(), pt.Scope()
        with pt.scope_guard(scope):
            exe.run(startup)
            return cfg, gd.collect_gpt_params(scope, cfg)
    extra = {} if name == "moonlight" else dict(
        q_lora_rank=16, hc_mult=4, hc_sinkhorn_iters=6,
        name="Xing4.0-29B-A4B",
        rope_scaling={"type": "yarn", "factor": 4, "beta_fast": 32,
                      "original_max_position_embeddings": 32, "beta_slow": 1,
                      "mscale": 1, "mscale_all_dim": 1})
    cfg = ml.MoonlightConfig(**_SIZES, **extra)
    return cfg, ml.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)


@pytest.mark.parametrize("program", [k for k in PARENT
                                     if not k.startswith("kernel.")])
def test_one_group_models_are_served_by_the_parents_program(program):
    name, which = program.split(".")
    cfg, p = _one_group_model(name)
    assert _served(cfg, p, which) == PARENT[program]


@pytest.mark.parametrize("kernel", [k for k in PARENT
                                    if k.startswith("kernel.")])
def test_group_one_without_a_bound_is_the_parents_kernel(kernel):
    from paddle_tpu.ops.flash_attention import flash_attention
    from paddle_tpu.ops.paged_attention import latent_paged_attention
    S, H, hd, bs = 3, 4, 8, 4
    pt_ = jnp.zeros((S, 6), jnp.int32)
    ts, done = jnp.ones((S,), jnp.int32), jnp.zeros((S,), bool)
    if kernel == "kernel.paged_attention":
        got = _digest(
            lambda q, a: paged_attention(q, q, q, a, 1, pt_, ts, done),
            jnp.zeros((S, H, hd), jnp.float32),
            jnp.zeros((2, 1, 20, H, bs, 2 * hd), jnp.float32))
    elif kernel == "kernel.latent_paged_attention":
        got = _digest(
            lambda q, r, a: latent_paged_attention(q, r, a, 1, pt_, ts, done),
            jnp.zeros((S, H, 128), jnp.float32),
            jnp.zeros((S, 128), jnp.float32),
            jnp.zeros((2, 1, 20, 1, bs, 128), jnp.float32))
    elif kernel == "kernel.flash_causal_rows":
        got = _digest(lambda q: flash_causal_rows(q, q, q, 0.2),
                      jnp.zeros((256, H, 32), jnp.float32))
    elif kernel == "kernel.flash_causal_rows.2048":
        got = _digest(lambda q: flash_causal_rows(q, q, q, 0.2),
                      jnp.zeros((2048, 2, 32), jnp.float32))
    else:
        got = _digest(
            lambda q: jax.grad(lambda q: flash_attention(
                q, q, q, None, True, 0.2, True).sum())(q),
            jnp.zeros((2, 256, 2, 32), jnp.float32))
    assert got == PARENT[kernel]
