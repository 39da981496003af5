"""SDAR-30B-A3B-Chat's block and its generation by diffusion over blocks
(paddle_tpu.models.sdar, the third body of serving/decode_loop.py) at a
small size on the CPU.

The reference is benchmarks/reference/sdar_ref.py (float32, highest
precision, no cache, independent of the program): its `generate` is the
published loop, its `replay` the passes behind a served stream. Pinned
here: the served tokens AND the pass that fixed each against it for
prompts that end on, one past and three past a block's edge; one stream
at every chunk size and under late admission; the trim of a committed
block by a budget and by an eos, where the host retires; two-pass blocks
under a confident head, by the counters; the block-row paged kernel
(interpreted) against its gather form; the block-causal flash forward
against the masked attention; what the engine refuses; and Mellum's and
command-a's programs, which this PR must leave as the parent's."""

import hashlib
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from reference import sdar_ref as ref                        # noqa: E402

from paddle_tpu.models import command_a as ca                # noqa: E402
from paddle_tpu.models import mellum as mm                   # noqa: E402
from paddle_tpu.models import sdar                           # noqa: E402
from paddle_tpu.models._decoder import masked_attention      # noqa: E402
from paddle_tpu.ops.flash_attention import flash_causal_rows  # noqa: E402
from paddle_tpu.ops.paged_attention import paged_attention   # noqa: E402
from paddle_tpu.serving import (ServingConfig, ServingEngine,  # noqa: E402
                                SlotKVCache)
from paddle_tpu.serving.decode_loop import (MASKED, PROMPT,  # noqa: E402
                                            DecodeCarry, open_block)
from paddle_tpu.serving.model import (DIFFUSION_COUNTERS,    # noqa: E402
                                      serving_model)

B, MASK = 4, 210
CFG = sdar.SdarConfig(vocab_size=211, hidden=64, layers=2, heads=4,
                      kv_heads=2, head_dim=16, moe_intermediate=32,
                      n_routed_experts=8, experts_per_tok=2, max_pos=64,
                      mask_token_id=MASK, init_range=0.08)
# the same model under the published keys, as the reference reads them
REF_CFG = {
    "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_experts_per_tok": 2, "norm_topk_prob": True, "rms_norm_eps": 1e-6,
    "rope_theta": 1000000.0,
    "assumed": {"generation": {
        "block_length": B, "denoising_steps": 4, "confidence_threshold": 0.9,
        "remasking_strategy": "low_confidence_dynamic",
        "mask_token_id": MASK}}}
LOGIT_ATOL = 2e-4
SIZES = dict(num_slots=3, prefill_buckets=(8, 16), max_len=48, block_size=4)


def tokens_of(seed, n):
    return np.random.default_rng(seed).integers(0, 200, n).astype(np.int32)


@pytest.fixture(scope="module")
def params():
    # a key that CARRIES its generator: `framework/executor.py` switches the
    # process's default to rbg at its first run, and a raw `PRNGKey(3)` then
    # draws other weights when a test of the executor shared this worker
    # first (PR 50: `test_an_eos_inside_a_block..` found no fresh token)
    return sdar.init_params(CFG, jax.random.key(3, impl="threefry2x32"),
                            jnp.float32)


def engine_of(params, cfg=CFG, **sizes):
    return ServingEngine(params, cfg, ServingConfig(**dict(SIZES, **sizes)))


def served(params, specs, cfg=CFG, **sizes):
    """[(tokens, fixed_at)] of requests (prompt, max_new[, eos]) served
    together."""
    eng = engine_of(params, cfg, **sizes)
    reqs = [eng.submit(s[0], s[1], eos_id=s[2] if len(s) > 2 else None)
            for s in specs]
    eng.run_until_drained()
    assert all(r.state == "finished" for r in reqs)
    return [(list(r.tokens), list(r.fixed_at)) for r in reqs], eng


# -- the model against the reference ---------------------------------------------

def test_forward_logits_are_the_references_under_the_block_causal_mask(
        params):
    seq = tokens_of(0, 23)
    got = np.asarray(sdar.forward_logits(params, CFG, jnp.asarray(seq)))
    want = np.asarray(ref.sequence_logits(params, REF_CFG, list(seq)))
    assert np.abs(got - want).max() <= LOGIT_ATOL
    # the mask is the block's: position 4 sees 5..7, position 3 does not
    # see 4
    other = seq.copy()
    other[7] = (other[7] + 1) % 200
    moved = np.asarray(sdar.forward_logits(params, CFG, jnp.asarray(other)))
    assert np.abs(moved[4] - got[4]).max() > 1e-4
    assert np.abs(moved[:4] - got[:4]).max() == 0.0


@pytest.mark.parametrize("remainder", [0, 1, 3])
def test_served_tokens_and_passes_are_the_published_loops(params, remainder):
    """Prefill of the prompt's whole blocks, then block passes through the
    pages: the tokens, and the pass of its block at which each was fixed,
    are `sdar_ref.generate`'s; and at every replayed pass the served token
    is the reference's best within LOGIT_ATOL."""
    prompt = tokens_of(10 + remainder, 8 + remainder)
    [(toks, fixed)], _ = served(params, [(prompt, 11)])
    want_toks, want_fixed = ref.generate(params, REF_CFG, list(prompt), 11)
    assert toks == want_toks and fixed == want_fixed
    assert set(fixed) <= {0, 1, 2, 3}
    blocks = ref.replay(params, REF_CFG, list(prompt), toks, fixed,
                        logits=True)
    # whole served blocks: a first one of B - remainder tokens, then fours
    assert [int((b["fixed_at"] >= 0).sum()) for b in blocks] == \
        [(B - remainder) % B or B] + [B] * (len(blocks) - 1)
    for block in blocks:
        for s in range(len(block["best"])):
            at = np.flatnonzero(block["fixed_at"] == s)
            assert np.all(block["best"][s, at] - block["served"][s, at]
                          <= LOGIT_ATOL)
            assert np.all(np.isneginf(block["logits"][s, :, MASK]))


@pytest.mark.parametrize("remainder", [0, 1, 3])
def test_a_tokens_confidence_is_its_probability_at_the_pass_that_fixed_it(
        params, remainder):
    """Beside `fixed_at` a request keeps each token's confidence: the
    softmax probability (the mask token's logit at -inf) the pass that fixed
    it gave it, which is the replay's `conf` at that pass and position."""
    prompt = tokens_of(20 + remainder, 8 + remainder)
    eng = engine_of(params)
    req = eng.submit(prompt, 10)
    eng.run_until_drained()
    assert len(req.confidence) == len(req.fixed_at) == 10
    blocks = ref.replay(params, REF_CFG, list(prompt), list(req.tokens),
                        list(req.fixed_at))
    want = np.concatenate([
        np.exp(b["conf"][b["fixed_at"][b["fixed_at"] >= 0],
                         np.flatnonzero(b["fixed_at"] >= 0)])
        for b in blocks])
    got = np.asarray(req.confidence[:want.size])
    assert want.size >= 7 and np.all((got > 0) & (got < 1))
    assert np.abs(got / want - 1).max() <= 1e-3


def test_a_sampled_tokens_confidence_is_under_its_temperature(params):
    """A sampled slot's confidence is softmax(l / T)'s probability of the
    token it drew: below the greedy one's where another token was drawn,
    and never the arg-max's of T = 1 by construction."""
    eng = engine_of(params)
    prompt = tokens_of(31, 8)
    cold = eng.submit(prompt, 8)
    warm = eng.submit(prompt, 8, temperature=0.8, seed=5)
    eng.run_until_drained()
    assert len(warm.confidence) == 8 and len(cold.confidence) == 8
    assert all(0 < c < 1 for c in warm.confidence)
    assert list(warm.tokens) != list(cold.tokens)
    assert min(warm.confidence) < min(cold.confidence)


@pytest.mark.parametrize("remainder", [0, 1, 3])
def test_block_passes_through_the_pages_give_the_replays_logits(
        params, remainder):
    """`prefill_pages` + `block_step_pages` by hand: the logits of the pass
    that sees the first block as it opens are `replay`'s pass 0."""
    prompt = tokens_of(20 + remainder, 8 + remainder)
    whole = len(prompt) // B * B
    kv = SlotKVCache(CFG, 2, 48, jnp.float32, block_size=4)
    slot = kv.alloc()
    pages, _ = kv.map_slot(slot, prompt, len(prompt) + 8)
    padded = np.zeros((1, 16), np.int32)
    padded[0, :len(prompt)] = prompt
    _, arena, _ = sdar.prefill_pages(
        params, CFG, jnp.asarray(padded), jnp.int32(0),
        jnp.int32(len(prompt)), kv.arena, jnp.asarray(pages))
    toks, fixed = open_block(SDAR.diffusion(CFG), prompt[whole:])
    assert list(fixed) == [PROMPT] * remainder + [MASKED] * (B - remainder)
    pt = jnp.zeros((2, len(pages)), jnp.int32).at[slot].set(pages)
    logits, _, counters = sdar.block_step_pages(
        params, CFG, jnp.stack([jnp.asarray(toks)] * 2), arena, pt,
        jnp.asarray([whole, whole], jnp.int32),
        jnp.asarray([False, True]).at[slot].set(False)
        .at[1 - slot].set(True))
    [(served_toks, served_fixed)], _ = served(params, [(prompt, 8)])
    first = ref.replay(params, REF_CFG, list(prompt), served_toks,
                       served_fixed, logits=True)[0]
    got = np.array(logits[slot])[:, :MASK]
    assert np.abs(got - first["logits"][0][:, :MASK]).max() <= LOGIT_ATOL
    assert int(counters["decode_rows_full"]) == (whole + B) * CFG.layers


SDAR = serving_model(CFG)


# -- one stream whatever the schedule ----------------------------------------------

SPECS = [(tokens_of(1, 9), 10), (tokens_of(2, 8), 8), (tokens_of(3, 3), 6),
         (tokens_of(4, 11), 9), (tokens_of(5, 6), 13)]


@pytest.fixture(scope="module")
def streams(params):
    return served(params, SPECS, decode_chunk=8)[0]


@pytest.mark.parametrize("chunk", [1, 5, 7])
def test_streams_are_identical_at_every_chunk_size(params, streams, chunk):
    # five requests on three slots: the last two are admitted LATE, into
    # slots whose blocks another request left
    got, eng = served(params, SPECS, decode_chunk=chunk)
    assert got == streams
    assert eng.stats()["blocks_used"] == 0


def test_seeded_streams_do_not_depend_on_the_chunk_or_the_company(params):
    def run(chunk, alone):
        eng = engine_of(params, decode_chunk=chunk)
        reqs = [eng.submit(p, n, temperature=0.8, seed=7 + i)
                for i, (p, n) in enumerate(SPECS[:1] if alone else SPECS)]
        eng.run_until_drained()
        return list(reqs[0].tokens), list(reqs[0].fixed_at)
    a = run(8, False)
    assert a == run(3, False) == run(5, True)
    assert len(a[0]) == SPECS[0][1]


def test_a_budget_that_is_no_multiple_of_the_block_trims_the_last_block(
        params):
    prompt = tokens_of(6, 9)                       # 3 of the first block free
    [(toks, fixed)], eng = served(params, [(prompt, 10)])
    longer, _ = ref.generate(params, REF_CFG, list(prompt), 11)
    assert len(toks) == len(fixed) == 10 and toks == longer[:10]
    stats = eng.stats()
    # 3 + 4 + 3 tokens: three blocks committed, the last one trimmed
    assert stats["blocks_committed"] == 3
    assert stats["diffusion"]["tokens_per_pass"] == \
        pytest.approx(10 / stats["block_passes"])


def test_an_eos_inside_a_block_trims_it_where_the_host_retires(params):
    prompt = tokens_of(7, 8)
    [(free, _)], _ = served(params, [(prompt, 12)])
    # an eos that the free stream first holds INSIDE a block
    at = next(i for i in range(1, 12) if i % B != B - 1
              and free[i] not in free[:i])
    [(toks, fixed)], eng = served(params, [(prompt, 12, free[at])])
    assert toks == free[:at + 1] and len(fixed) == at + 1
    assert eng.stats()["blocks_used"] == 0
    assert eng.scheduler.active_count == 0


def test_a_confident_head_commits_blocks_in_two_passes(params):
    """A head scaled so that every confidence clears 0.9: pass 0 fixes every
    masked position by the threshold, pass 1 commits."""
    sharp = dict(params, head=params["head"] * 400.0)
    prompt = tokens_of(8, 8)
    [(toks, fixed)], eng = served(sharp, [(prompt, 12)])
    stats = eng.stats()
    assert fixed == [0] * 12
    assert stats["blocks_committed"] == 3 and stats["block_passes"] == 6
    assert stats["tokens_fixed_by_threshold"] == 12
    assert stats["tokens_fixed_by_rank"] == 0
    assert stats["diffusion"]["passes_per_block"] == 2.0
    assert stats["diffusion"]["tokens_per_pass"] == 2.0
    want, want_fixed = ref.generate(sharp, REF_CFG, list(prompt), 12)
    assert toks == want and fixed == want_fixed


def test_flat_logits_take_the_static_schedule_and_the_counters_say_so(
        params):
    [(toks, fixed)], eng = served(params, [(tokens_of(9, 8), 8)])
    stats = eng.stats()
    assert sorted(fixed[:4]) == sorted(fixed[4:]) == [0, 1, 2, 3]
    assert stats["blocks_committed"] == 2 and stats["block_passes"] == 10
    assert stats["tokens_fixed_by_rank"] == 8
    assert stats["tokens_fixed_by_threshold"] == 0
    assert stats["diffusion"]["passes_per_block"] == 5.0
    assert stats["diffusion"]["block_length"] == 4
    assert stats["diffusion"]["mean_time_to_first_block"] == \
        stats["mean_ttft"] > 0
    assert stats["decode_rows_full"] == CFG.layers * 5 * ((8 + 4) + (12 + 4))
    assert stats["prefix_cache"].startswith("off")
    assert stats["model"] == "SDAR-30B-A3B-Chat"
    assert stats["decode_attention"] == {"full": "gather"}


def test_another_remasking_rule_is_refused_at_construction():
    with pytest.raises(ValueError, match="low_confidence_dynamic"):
        sdar.SdarConfig(**{**_sizes(), "remasking": "low_confidence_static"})


def _sizes():
    return dict(vocab_size=211, hidden=64, layers=2, heads=4, kv_heads=2,
                head_dim=16, moe_intermediate=32, n_routed_experts=8,
                experts_per_tok=2, max_pos=64, mask_token_id=MASK,
                init_range=0.08)


# -- the carry, the interface, what is refused --------------------------------------

def test_the_carry_has_a_block_only_for_block_diffusion():
    plain = DecodeCarry.idle(3)
    assert plain.block is None
    assert len(jax.tree_util.tree_leaves(plain)) == 6
    held = DecodeCarry.idle(3, block_length=4)
    toks, fixed, sure, passes = held.block
    assert toks.shape == fixed.shape == sure.shape == (3, 4)
    assert passes.shape == (3,) and sure.dtype == jnp.float32
    assert int(fixed.min()) == int(fixed.max()) == MASKED
    assert len(jax.tree_util.tree_leaves(held)) == 10


def test_the_model_declares_block_diffusion_and_its_counters():
    assert SDAR.features == frozenset({"block_diffusion"})
    assert SDAR.diffusion(CFG) == {
        "block_length": 4, "denoising_steps": 4, "confidence_threshold": 0.9,
        "remasking": "low_confidence_dynamic", "mask_token_id": MASK}
    names = SDAR.counter_names(CFG)
    assert set(DIFFUSION_COUNTERS) <= set(names)
    assert "decode_rows_full" in names and "decode_rows_window" not in names
    assert serving_model(mm.MellumConfig(
        vocab_size=64, hidden=32, layers=4, heads=2, kv_heads=1, head_dim=16,
        moe_intermediate=32, n_routed_experts=4, experts_per_tok=2,
        sliding_window=8, max_pos=64)).diffusion(None) is None
    with pytest.raises(NotImplementedError, match="block passes"):
        SDAR.decode_step(None, CFG, None, None, None, None, None)


@pytest.mark.parametrize("option", [
    dict(weight_dtype="int8"), dict(kv_dtype="int8"),
    dict(max_adapters=2, adapter_rank=4), dict(speculate_k=2),
    dict(mesh_shape=(2,)), dict(prefill_chunk=8), dict(preempt=True)])
def test_the_engine_refuses_what_a_block_pass_has_not(params, option):
    with pytest.raises(ValueError, match="does not implement"):
        engine_of(params, **option)


@pytest.mark.parametrize("bad", [
    dict(block_length=3), dict(denoising_steps=3), dict(remasking="random"),
    dict(mask_token_id=211)])
def test_the_config_refuses_what_the_loop_cannot_run(bad):
    with pytest.raises(ValueError):
        sdar.SdarConfig(**{**_sizes(), **bad})


def test_migration_is_refused_and_prompts_share_no_pages(params):
    from paddle_tpu.serving.migration import MigrationError
    eng = engine_of(params)
    prompt = tokens_of(11, 12)
    a = eng.submit(prompt, 4)
    eng.step()
    with pytest.raises(MigrationError, match="diffusion over blocks"):
        eng.migrate_out(a)
    b = eng.submit(prompt, 4)                      # the same prompt again
    eng.run_until_drained()
    assert a.tokens == b.tokens and a.fixed_at == b.fixed_at
    assert eng.stats()["prefix_hits"] == 0


def test_fixed_at_rides_the_last_sse_frame_and_the_json_body(params):
    import json
    import urllib.request
    import paddle_tpu as pt
    server = pt.server.serve(params, CFG, pt.server.ServerConfig(
        port=0, replicas=1, serving=ServingConfig(**SIZES)))
    try:
        prompt = [int(t) for t in tokens_of(12, 9)]
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/v1/generate",
            json.dumps({"prompt": prompt, "max_new_tokens": 7,
                        "stream": False}).encode(),
            {"Content-Type": "application/json"})
        body = json.loads(urllib.request.urlopen(req, timeout=120).read())
        want = ref.generate(params, REF_CFG, prompt, 7)
        assert (body["tokens"], body["fixed_at"]) == want
        assert len(body["confidence"]) == 7
        assert all(0 < c < 1 for c in body["confidence"])
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/v1/generate",
            json.dumps({"prompt": prompt, "max_new_tokens": 7}).encode(),
            {"Content-Type": "application/json"})
        frames = urllib.request.urlopen(req, timeout=120).read().decode() \
            .strip().split("\n\n")
        assert frames[-1].startswith("event: done")
        done = json.loads(frames[-1].split("data: ", 1)[1])
        assert done["fixed_at"] == want[1] and done["tokens"] == 7
        assert done["confidence"] == body["confidence"]
        assert [json.loads(f.split("data: ", 1)[1])["token"]
                for f in frames[:-1]] == want[0]
    finally:
        server.shutdown(drain=False)


def test_a_block_commit_is_a_span_beside_the_decode_iterations(params):
    from paddle_tpu.observability.tracer import get_tracer
    tracer = get_tracer()
    tracer.enable()
    try:
        [(toks, fixed)], _ = served(params, [(tokens_of(13, 9), 7)])
        events = tracer.snapshot()
    finally:
        tracer.disable()
        tracer.clear()
    events = [e if isinstance(e, dict) else e._asdict() for e in events]
    commits = [e for e in events if e["name"] == "serving/block_commit"]
    iters = [e for e in events if e["name"] == "serving/decode_iter"]
    assert [c["args"]["tokens"] for c in commits] == [3, 4]
    assert sum((c["args"]["fixed_at"] for c in commits), []) == fixed
    assert [c["args"]["block"] for c in commits] == [0, 1]
    assert len(iters) == 7


# -- the kernels, interpreted -------------------------------------------------------

@pytest.mark.parametrize("block", [1, 4, 8])
@pytest.mark.parametrize("group", [1, 8, 16])
def test_block_row_kernel_against_the_gather(block, group):
    """`paged_attention` with block rows (interpreted) against the gather
    form of `_attend_block`: B new rows written through the live page, B x
    group queries a KV head over ts + B rows, a frozen slot untouched."""
    rng = np.random.default_rng(block * 100 + group)
    S, kvh, hd, bs, P = 3, 2, 8, 8, 5
    cfg = sdar.SdarConfig(**{**_sizes(), "heads": kvh * group,
                             "kv_heads": kvh, "head_dim": hd,
                             "block_length": block,
                             "denoising_steps": 1})
    arena = jnp.asarray(rng.normal(size=(2, 1, 20, kvh, bs, 2 * hd)),
                        jnp.float32)
    # every slot its own blocks (a page belongs to one sequence)
    table = jnp.asarray(rng.permutation(np.arange(1, 20))[:S * P]
                        .reshape(S, P), jnp.int32)
    ts = jnp.asarray([8, 24, 16], jnp.int32)
    done = jnp.asarray([False, False, True])
    q = jnp.asarray(rng.normal(size=(S, block, kvh * group, hd)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(S, block, kvh, hd)), jnp.float32)
            for _ in range(2))
    got, after = paged_attention(q, k, v, arena, 1, table, ts, done)
    want, gathered = sdar._attend_block(cfg, q, k, v, arena, 1, table, ts,
                                        done, "gather")
    assert float(jnp.abs(got[:2] - want[:2]).max()) <= 2e-6
    assert float(jnp.abs(got[2]).max()) == 0.0            # frozen: zeros
    # the gather sends a frozen slot's rows to scratch block 0; the kernel
    # writes nothing of it: every other block is the same
    np.testing.assert_array_equal(np.asarray(after)[:, :, 1:],
                                  np.asarray(gathered)[:, :, 1:])
    np.testing.assert_array_equal(np.asarray(after)[:, :, 0],
                                  np.asarray(arena)[:, :, 0])


# (ts a slot, the frozen slots): pages of 8 rows and B = 4, so a block sits
# at a page's first (ts % 8 == 0) or last (ts % 8 == 4) block position; a
# group of the walk is 4 pages and the table of 9 pages three groups
_BLOCK_STREAMS = {
    "first_block_position": ([0, 8, 32, 64, 24, 40], []),
    "last_block_position": ([4, 12, 28, 68, 36, 60], []),
    "frozen_first_last_and_between": ([8, 4, 36, 16, 64, 28, 68, 0],
                                      [0, 3, 4, 7]),
    "one_live_slot": ([8, 60, 16], [0, 2]),
    "no_live_slot": ([8, 60, 16], [0, 1, 2]),
    "few_pages_beside_many": ([0, 68, 4, 64, 8, 60], []),
}


@pytest.mark.parametrize("case", sorted(_BLOCK_STREAMS))
def test_block_rows_stream_across_slots_and_leak_nothing(case):
    """B = 4 block rows through the walk that is ONE stream of page groups
    across a call's live slots: the outputs and the WHOLE arena against the
    gather, and bit for bit against the same slots run one a call."""
    ts, frozen = _BLOCK_STREAMS[case]
    S, B, kvh, group, hd, bs, P = len(ts), 4, 2, 8, 8, 8, 9
    rng = np.random.default_rng(len(case))
    cfg = sdar.SdarConfig(**{**_sizes(), "heads": kvh * group,
                             "kv_heads": kvh, "head_dim": hd,
                             "block_length": B, "denoising_steps": 1})
    arena = jnp.asarray(rng.normal(size=(2, 1, 1 + S * P, kvh, bs, 2 * hd)),
                        jnp.float32)
    table = jnp.asarray(1 + rng.permutation(S * P).reshape(S, P), jnp.int32)
    ts = jnp.asarray(ts, jnp.int32)
    done = jnp.asarray(np.isin(np.arange(S), frozen))
    q = jnp.asarray(rng.normal(size=(S, B, kvh * group, hd)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(S, B, kvh, hd)), jnp.float32)
            for _ in range(2))
    got, after = paged_attention(q, k, v, arena, 1, table, ts, done)
    outs, alone = [], arena
    for s in range(S):
        one = slice(s, s + 1)
        out, alone = paged_attention(q[one], k[one], v[one], alone, 1,
                                     table[one], ts[one], done[one])
        outs.append(out)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.concatenate(outs, 0)))
    np.testing.assert_array_equal(np.asarray(after), np.asarray(alone))
    want, gathered = sdar._attend_block(cfg, q, k, v, arena, 1, table, ts,
                                        done, "gather")
    live = ~np.asarray(done)
    if live.any():
        assert float(jnp.abs(got - want)[live].max()) <= 2e-6
    assert not np.asarray(got)[~live].any()               # frozen: zeros
    # the gather sends a frozen slot's rows to scratch block 0; the kernel
    # writes nothing of it: every other block is the same
    np.testing.assert_array_equal(np.asarray(after)[:, :, 1:],
                                  np.asarray(gathered)[:, :, 1:])
    np.testing.assert_array_equal(np.asarray(after)[:, :, 0],
                                  np.asarray(arena)[:, :, 0])


def test_block_rows_refuse_a_bound_and_a_block_that_straddles_a_page():
    q = jnp.zeros((2, 4, 4, 8), jnp.float32)
    k = jnp.zeros((2, 4, 2, 8), jnp.float32)
    pt_, ts = jnp.zeros((2, 3), jnp.int32), jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError, match="straddle"):
        paged_attention(q, k, k, jnp.zeros((1, 1, 4, 2, 6, 16), jnp.float32),
                        0, pt_, ts)
    with pytest.raises(ValueError, match="no `lo`"):
        paged_attention(q, k, k, jnp.zeros((1, 1, 4, 2, 8, 16), jnp.float32),
                        0, pt_, ts, lo=ts)


@pytest.mark.parametrize("rows,length", [(256, 256), (2048, 1000),
                                         (2048, 2048)])
def test_block_causal_flash_forward_against_the_masked_attention(rows,
                                                                 length):
    rng = np.random.default_rng(rows + length)
    q = jnp.asarray(rng.normal(size=(rows, 4, 32)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(rows, 2, 32)), jnp.float32)
            for _ in range(2))
    got = flash_causal_rows(q, k, v, 0.2, length=jnp.int32(length), block=4)
    i = jnp.arange(rows)
    want = masked_attention(q, jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1),
                             i[None, :] <= (i[:, None] | 3), 0.2)
    assert float(jnp.abs(got[:length] - want[:length]).max()) <= 3e-6
    assert float(jnp.abs(got[length:]).max()) == 0.0 if length < rows \
        else True
    with pytest.raises(ValueError, match="power of two"):
        flash_causal_rows(q, k, v, 0.2, block=3)


# -- Mellum's and command-a's programs: the parent's --------------------------------

# sha256 (16 hex) of str(jax.make_jaxpr(...)) at the sizes below, computed on
# the parent commit (4e4ac55, PR 39) by the same code: the programs that share
# `_grouped_kernel`, `flash_causal_rows`, `_moe` and the loop with this model
# trace what they traced, through the gather (what the CPU serves) and through
# the kernels (`.kernel`: the decode step with the grouped paged kernel forced,
# where B = 1 must be the kernel it was). command-a's three were computed
# again at PR 45, whose review made a layer that holds a SHARE of the experts
# combine by a select, pick by pick (models/_experts._weighted_sum: a pick held
# elsewhere no longer multiplies the routed buffer's never-written last row
# by 0); Mellum, which holds every expert, keeps the parent's. The three that
# hold `_grouped_kernel` (`kernel.paged_attention_grouped` and the two
# `.decode.kernel`) were computed again at PR 53 (parent d089719) on its own
# final tree: the kernel's walk became ONE stream of page groups across
# slots (an SMEM state of three, a `while` to the next live slot), so its
# jaxpr changed; the five rows without it pass with the hash they had.
PARENT = {
    "command_a.decode": "87a4936e25c1afe3",
    "command_a.decode.kernel": "ae8b8522df04089f",
    "command_a.prefill": "f58604d02312aebd",
    "kernel.flash_causal_rows.window": "f29109e6f9230434",
    "kernel.paged_attention_grouped": "10f00b62d70ba722",
    "mellum.decode": "47cc377bf4679650",
    "mellum.decode.kernel": "7f16176521ad27bc",
    "mellum.prefill": "4150004fe0d428a5",
}
_MELLUM = dict(vocab_size=211, hidden=64, layers=4, heads=4, kv_heads=1,
               head_dim=16, moe_intermediate=32, n_routed_experts=8,
               experts_per_tok=2, sliding_window=8, max_pos=64,
               rope_scaling={"type": "yarn", "factor": 4, "beta_fast": 32,
                             "original_max_position_embeddings": 16,
                             "beta_slow": 1})
_COMMAND_A = dict(vocab_size=96, hidden=64, layers=4, heads=8, kv_heads=2,
                  head_dim=16, moe_intermediate=32, n_routed_experts=16,
                  n_shared_experts=2, experts_per_tok=4, experts_held=(4, 4),
                  vocab_slice=(0, 96, 768), sliding_window=8, max_pos=64)


def _digest(fn, *args):
    return hashlib.sha256(str(jax.make_jaxpr(fn)(*args)).encode()) \
        .hexdigest()[:16]


def _program(name):
    model_name, _, which = name.partition(".")
    if model_name == "kernel":
        S, H, hd, bs = 3, 4, 8, 4
        pt_ = jnp.zeros((S, 6), jnp.int32)
        ts, done = jnp.ones((S,), jnp.int32), jnp.zeros((S,), bool)
        if which == "paged_attention_grouped":
            return _digest(
                lambda q, k, a: paged_attention(q, k, k, a, 1, pt_, ts, done,
                                                lo=ts),
                jnp.zeros((S, 2 * H, hd), jnp.float32),
                jnp.zeros((S, H, hd), jnp.float32),
                jnp.zeros((2, 1, 20, H, bs, 2 * hd), jnp.float32))
        return _digest(
            lambda q, k: flash_causal_rows(q, k, k, 0.2, window=512,
                                           length=jnp.int32(700)),
            jnp.zeros((2048, 4, 32), jnp.float32),
            jnp.zeros((2048, 2, 32), jnp.float32))
    mod, cfg = (mm, mm.MellumConfig(**_MELLUM)) if model_name == "mellum" \
        else (ca, ca.CommandAConfig(**_COMMAND_A))
    p = mod.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    model = serving_model(cfg)
    kv = SlotKVCache(cfg, 3, 48, jnp.float32, block_size=4)
    arena, pt = kv.arena, jnp.asarray(kv.page_table)
    step = (jnp.zeros((3,), jnp.int32), jnp.ones((3,), jnp.int32),
            jnp.zeros((3,), bool))
    if which == "prefill":
        return _digest(
            lambda p, a, t, pg: model.prefill(p, cfg, t, jnp.int32(0),
                                              jnp.int32(9), a, pg),
            p, arena, jnp.zeros((1, 16), jnp.int32), pt[0])
    if which == "decode":
        return _digest(
            lambda p, a, t, ts, d: model.decode_step(p, cfg, t, a, pt, ts, d),
            p, arena, *step)
    return _digest(
        lambda p, a, t, ts, d: mod.decode_step_pages(
            p, cfg, t, a, pt, ts, d, attention={"full": "paged_kernel",
                                                "window": "paged_kernel"}),
        p, arena, *step)


@pytest.mark.parametrize("program", sorted(PARENT))
def test_programs_that_share_code_with_the_block_pass_are_the_parents(
        program):
    assert _program(program) == PARENT[program]
