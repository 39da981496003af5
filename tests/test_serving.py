"""Continuous-batching serving engine (paddle_tpu.serving).

Pins the subsystem's three contracts: (1) greedy continuous-batched
decode is TOKEN-IDENTICAL to the sequential gpt_generate path for
concurrent prompts of different lengths, through slot reuse; (2) the
number of compiled executables is bounded by the configured shape
buckets, O(buckets) not O(requests) — asserted via the scheduler's
compile-counter hook; (3) overload SHEDS at the admission door instead
of queueing unboundedly. All CPU-fast on the tiny GPT."""

import threading

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models.gpt import GPTConfig, gpt_lm_program
from paddle_tpu.models import gpt_decode as gd
from paddle_tpu.serving import (EngineOverloadError, FaultPlan,
                                InjectedFault, ServingConfig,
                                ServingEngine, ShapeBuckets, SlotKVCache)
from paddle_tpu.serving.decode_loop import DecodeCarry, decode_chunk


def tiny_cfg():
    return GPTConfig(vocab_size=97, hidden=32, layers=2, heads=4,
                     max_pos=64, dropout=0.0, attn_impl="xla")


@pytest.fixture(scope="module")
def trained():
    """(cfg, params) of a randomly initialised tiny GPT."""
    cfg = tiny_cfg()
    main, startup, fetches = gpt_lm_program(cfg, 8, is_test=True)
    exe = pt.Executor()
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup)
        params = gd.collect_gpt_params(scope, cfg)
    return cfg, params


def make_engine(trained, **kw):
    cfg, params = trained
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_queue", 16)
    kw.setdefault("prefill_buckets", (4, 8))
    kw.setdefault("max_len", 32)
    return ServingEngine(params, cfg, ServingConfig(**kw))


def sequential_ref(trained, prompt, max_new):
    cfg, params = trained
    return gd.gpt_generate(params, cfg, np.asarray(prompt)[None], max_new)[0]


# ---------------------------------------------------------------------------
# decode-primitive parity: the paged forms against the sequential reference
# ---------------------------------------------------------------------------

BS = 4          # block size of the paged pools built below


def paged_pool(trained, prompts, pages=8, bucket=None):
    """A block arena and page table with every prompt prefilled into
    its own slot's pages (slot s owns blocks 1 + s*pages ..; block 0 is
    scratch), each padded to `bucket` when given. Returns (arena, page
    table, [last-position logits (1, V)])."""
    import jax.numpy as jnp
    cfg, params = trained
    shape, _ = gd.paged_arena_shapes(
        cfg.layers, len(prompts) * pages + 1, cfg.heads, BS,
        cfg.hidden // cfg.heads)
    arena = jnp.zeros(shape, jnp.float32)
    pt_ = jnp.arange(1, len(prompts) * pages + 1,
                     dtype=jnp.int32).reshape(len(prompts), pages)
    logits = []
    for slot, prompt in enumerate(prompts):
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        padded = np.zeros((1, bucket or prompt.size), np.int32)
        padded[0, :prompt.size] = prompt
        lg, arena = gd.gpt_prefill_pages(
            params, cfg, jnp.asarray(padded), 0, prompt.size, arena,
            pt_[slot])
        logits.append(lg)
    return arena, pt_, logits


def slot_rows(arena, pt_, slot, n):
    """A slot's first n K and V rows of every layer, in the sequential
    cache's layout (layers, 2, heads, n, hd)."""
    import jax.numpy as jnp
    return np.stack([np.stack([np.asarray(x[:, :n]) for x in gd._kv_gather(
        arena, li, pt_[slot], jnp.float32)]) for li in range(arena.shape[0])])


def carry_of(tokens, ts, remaining, done=None, spec=None):
    """A greedy DecodeCarry with no eos over len(tokens) slots."""
    import jax.numpy as jnp
    n = len(tokens)
    return DecodeCarry(
        jnp.asarray(tokens, jnp.int32), jnp.asarray(ts, jnp.int32),
        jnp.zeros((n,), bool) if done is None else jnp.asarray(done),
        jnp.asarray(remaining, jnp.int32), jnp.zeros((n,), jnp.float32),
        jnp.full((n,), -1, jnp.int32), spec)


def test_prefill_padded_matches_prefill(trained):
    """Padding the prompt to a bucket changes neither the last-real-
    position logits nor the real K/V rows: gpt_prefill_pages of a
    right-padded suffix against the sequential gpt_prefill."""
    cfg, params = trained
    rng = np.random.RandomState(0)
    toks = np.asarray(rng.randint(0, cfg.vocab_size, (2, 5)), np.int32)
    ref_logits, ref_cache = gd.gpt_prefill(params, cfg, toks, max_len=16)
    arena, pt_, logits = paged_pool(trained, list(toks), bucket=8)
    for slot in range(2):
        np.testing.assert_allclose(np.asarray(logits[slot][0]),
                                   np.asarray(ref_logits[slot]),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            slot_rows(arena, pt_, slot, 5),
            np.asarray(ref_cache[:, :, slot, :, :5]), rtol=1e-5, atol=1e-5)


def test_decode_step_slots_matches_per_sequence_steps(trained):
    """The slot-batched paged step at per-slot positions reproduces two
    independent gpt_decode_step calls at different t: their logits and
    the K/V rows they leave behind."""
    import jax.numpy as jnp
    cfg, params = trained
    rng = np.random.RandomState(1)
    a = np.asarray(rng.randint(0, cfg.vocab_size, (1, 3)), np.int32)
    b = np.asarray(rng.randint(0, cfg.vocab_size, (1, 6)), np.int32)
    _, ca = gd.gpt_prefill(params, cfg, a, max_len=16)
    _, cb = gd.gpt_prefill(params, cfg, b, max_len=16)
    ta, tb = np.int32(7), np.int32(11)   # next tokens to feed
    la, ca2 = gd.gpt_decode_step(params, cfg, jnp.asarray([ta]), ca, 3)
    lb, cb2 = gd.gpt_decode_step(params, cfg, jnp.asarray([tb]), cb, 6)

    arena, pt_, _ = paged_pool(trained, [a[0], b[0]])        # slots 0,1
    logits, arena2 = gd.gpt_decode_step_pages(
        params, cfg, jnp.asarray([ta, tb]), arena, pt_,
        jnp.asarray([3, 6], jnp.int32))
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(la[0]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(logits[1]), np.asarray(lb[0]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(slot_rows(arena2, pt_, 0, 4),
                               np.asarray(ca2[:, :, 0, :, :4]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(slot_rows(arena2, pt_, 1, 7),
                               np.asarray(cb2[:, :, 0, :, :7]),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# greedy parity + slot reuse + compile bound
# ---------------------------------------------------------------------------

def test_greedy_parity_three_prompts_two_slots(trained):
    """3 concurrent prompts of different lengths through 2 slots (forces
    queueing + slot reuse): token-identical to sequential gpt_generate."""
    rng = np.random.RandomState(2)
    cfg, _ = trained
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (3, 5, 7)]
    eng = make_engine(trained, num_slots=2)
    outs = eng.generate(prompts, max_new_tokens=6)
    for p, o in zip(prompts, outs):
        np.testing.assert_array_equal(o, sequential_ref(trained, p, 6))
    s = eng.stats()
    assert s["completed"] == 3 and s["active_slots"] == 0
    assert s["free_slots"] == 2


def test_eight_concurrent_compile_count_bounded(trained):
    """≥8 concurrent requests with varied prompt lengths: greedy outputs
    match the sequential path AND the number of distinct compiled
    executables stays bounded by the shape buckets (the acceptance
    criterion's compile-counter assertion)."""
    rng = np.random.RandomState(3)
    cfg, _ = trained
    lens = [2, 3, 4, 5, 6, 7, 8, 3, 5, 7]          # 10 requests, 2 buckets
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in lens]
    eng = make_engine(trained, num_slots=8, prefill_buckets=(4, 8))
    outs = eng.generate(prompts, max_new_tokens=5)
    for p, o in zip(prompts, outs):
        np.testing.assert_array_equal(o, sequential_ref(trained, p, 5))
    # executables: one prefill per BUCKET (not per request/length), one
    # fused decode chunk, one admission sampler
    events = eng.scheduler.compile_events
    assert eng.scheduler.compile_count <= len(eng.buckets) + 2, events
    assert eng.stats()["compiled_executables"] == eng.scheduler.compile_count
    assert {e for e in events if e.startswith("prefill")} \
        <= {"prefill:L4", "prefill:L8"}
    assert events.count("decode_chunk") == 1


def test_slot_reuse_many_requests_few_slots(trained):
    """More requests than slots: retirement frees slots for the backlog
    and every request completes with its full budget."""
    rng = np.random.RandomState(4)
    cfg, _ = trained
    prompts = [rng.randint(0, cfg.vocab_size, (2 + i % 3,)).astype(np.int32)
               for i in range(5)]
    eng = make_engine(trained, num_slots=2, max_queue=8)
    reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.run_until_drained()
    assert all(r.finished for r in reqs)
    assert all(len(r.tokens) == 4 for r in reqs)
    s = eng.stats()
    assert s["admitted"] == 5 and s["completed"] == 5
    assert s["free_slots"] == 2 and s["queue_depth"] == 0


def test_mixed_lengths_and_budgets_interleave(trained):
    """Requests with different max_new budgets retire at different steps
    without stalling the batch; late submissions join mid-flight."""
    rng = np.random.RandomState(5)
    cfg, _ = trained
    eng = make_engine(trained, num_slots=3)
    a = eng.submit(rng.randint(0, cfg.vocab_size, (3,)), max_new_tokens=2)
    b = eng.submit(rng.randint(0, cfg.vocab_size, (5,)), max_new_tokens=7)
    eng.step()                       # both admitted, one decode
    c = eng.submit(rng.randint(0, cfg.vocab_size, (4,)), max_new_tokens=3)
    eng.run_until_drained()
    for r in (a, b, c):
        assert r.finished
        np.testing.assert_array_equal(
            r.output(), sequential_ref(trained, r.prompt, r.max_new_tokens))


# ---------------------------------------------------------------------------
# admission control / overload
# ---------------------------------------------------------------------------

def test_overload_sheds_instead_of_queueing(trained):
    """Beyond max_queue the engine rejects-with-overload; the queue never
    grows past the bound and the shed counter records the rejects."""
    cfg, _ = trained
    eng = make_engine(trained, num_slots=1, max_queue=2)
    p = np.asarray([1, 2, 3], np.int32)
    eng.submit(p, max_new_tokens=3)
    eng.submit(p, max_new_tokens=3)
    with pytest.raises(EngineOverloadError):
        eng.submit(p, max_new_tokens=3)
    with pytest.raises(EngineOverloadError):
        eng.submit(p, max_new_tokens=3)
    s = eng.stats()
    assert s["shed"] == 2 and s["queue_depth"] == 2
    eng.run_until_drained()
    assert eng.stats()["completed"] == 2     # shed requests never ran


def test_overload_error_carries_structured_fields(trained):
    """EngineOverloadError exposes queue depth / running count / a
    retry-after hint as FIELDS (the HTTP tier and bench tooling read
    state, never parse messages). The hint is the queue-wait p50 once
    requests have flowed; before any sample exists (cold engine) it is
    the documented conservative DEFAULT_RETRY_AFTER_S, never None — so
    429 Retry-After headers are always well-formed."""
    eng = make_engine(trained, num_slots=1, max_queue=1)
    p = np.asarray([1, 2, 3], np.int32)
    eng.submit(p, max_new_tokens=2)
    with pytest.raises(EngineOverloadError) as ei:
        eng.submit(p, max_new_tokens=2)
    assert ei.value.queue_depth == 1
    assert ei.value.running == 0             # nothing admitted yet
    # no queue-wait samples yet -> the documented cold-engine default
    assert ei.value.retry_after_s == pt.serving.DEFAULT_RETRY_AFTER_S
    assert eng.metrics.queue_wait_p50() is None
    eng.run_until_drained()                  # completes the queued one
    eng.submit(p, max_new_tokens=8)
    eng.step()                               # admit: occupies the slot
    eng.submit(p, max_new_tokens=2)          # queue full again
    with pytest.raises(EngineOverloadError) as ei:
        eng.submit(p, max_new_tokens=2)
    assert ei.value.queue_depth == 1
    assert ei.value.running == 1             # the admitted request
    # the hint now comes from the completed request's queue wait
    assert ei.value.retry_after_s == eng.metrics.queue_wait_p50()
    assert ei.value.retry_after_s is not None
    assert ei.value.retry_after_s >= 0
    eng.run_until_drained()


def test_submit_validation(trained):
    eng = make_engine(trained)               # buckets (4, 8), max_len 32
    with pytest.raises(ValueError, match="bucket"):
        eng.submit(np.arange(9, dtype=np.int32), max_new_tokens=2)
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(np.arange(8, dtype=np.int32), max_new_tokens=30)
    with pytest.raises(ValueError, match="empty"):
        eng.submit(np.zeros((0,), np.int32), max_new_tokens=2)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(np.asarray([1], np.int32), max_new_tokens=0)
    assert eng.stats()["submitted"] == 0     # rejected before the queue


def test_eos_retires_early(trained):
    """A sequence hitting eos frees its slot before its budget is spent."""
    cfg, _ = trained
    # find a prompt whose greedy stream has a token FIRST APPEARING past
    # position 0 — using it as eos pins early retirement mid-budget
    rng = np.random.RandomState(7)
    k = None
    for _ in range(20):
        p = rng.randint(0, cfg.vocab_size, (3,)).astype(np.int32)
        gen = list(sequential_ref(trained, p, 6)[3:])
        k = next((i for i in range(1, len(gen))
                  if gen[i] not in gen[:i]), None)
        if k is not None:
            break
    assert k is not None, "no usable greedy stream found"
    eos = int(gen[k])
    eng = make_engine(trained)
    req = eng.submit(p, max_new_tokens=6, eos_id=eos)
    eng.run_until_drained()
    assert req.finished
    assert req.tokens[-1] == eos and len(req.tokens) == k + 1
    assert eng.stats()["free_slots"] == eng.kv.num_slots


def test_cancel_queued_and_running(trained):
    cfg, _ = trained
    eng = make_engine(trained, num_slots=1)
    p = np.asarray([1, 2, 3], np.int32)
    a = eng.submit(p, max_new_tokens=8)
    b = eng.submit(p, max_new_tokens=8)
    eng.step()                               # a running, b queued
    assert eng.cancel(b) and b.state == "cancelled"
    n_a = len(a.tokens)
    assert eng.cancel(a) and a.state == "cancelled"
    assert not eng.cancel(a)                 # already cancelled
    eng.run_until_drained()                  # driver applies the cancel
    assert eng.kv.free_count == 1
    assert eng.stats()["completed"] == 0
    assert len(a.tokens) == n_a              # no emissions after cancel


def test_generate_longer_than_queue_flows_through(trained):
    """generate() with more prompts than max_queue interleaves submits
    with steps instead of shedding its own batch."""
    rng = np.random.RandomState(8)
    cfg, _ = trained
    prompts = [rng.randint(0, cfg.vocab_size, (2 + i % 4,)).astype(np.int32)
               for i in range(7)]
    eng = make_engine(trained, num_slots=2, max_queue=2)
    outs = eng.generate(prompts, max_new_tokens=3)
    assert eng.stats()["shed"] == 0
    for p, o in zip(prompts, outs):
        np.testing.assert_array_equal(o, sequential_ref(trained, p, 3))


def test_oversized_bucket_rejected_at_construction(trained):
    with pytest.raises(ValueError, match="exceed max_len"):
        make_engine(trained, prefill_buckets=(8, 64), max_len=32)


# ---------------------------------------------------------------------------
# streaming + sampling + metrics
# ---------------------------------------------------------------------------

def test_streaming_callback_sees_every_token_in_order(trained):
    cfg, _ = trained
    p = np.asarray([3, 1, 4], np.int32)
    got = []
    eng = make_engine(trained)
    req = eng.submit(p, max_new_tokens=5,
                     on_token=lambda r, tok: got.append((r, tok)))
    eng.run_until_drained()
    assert [t for _, t in got] == req.tokens
    assert all(r is req for r, _ in got)
    np.testing.assert_array_equal(req.output(),
                                  sequential_ref(trained, p, 5))


def test_sampled_stream_deterministic_per_seed(trained):
    cfg, _ = trained
    p = np.asarray([2, 7], np.int32)

    def run(seed):
        eng = make_engine(trained, top_k=5)
        (out,) = eng.generate([p], max_new_tokens=6, temperature=0.8,
                              seed=seed)
        return out

    a, b = run(11), run(11)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (8,)
    assert all(0 <= t < cfg.vocab_size for t in a)


def test_request_metrics_fake_clock():
    from paddle_tpu.serving.metrics import RequestMetrics
    t = [0.0]
    rm = RequestMetrics(clock=lambda: t[0])
    rm.mark_submitted()
    t[0] = 1.0
    rm.mark_admitted()
    t[0] = 1.5
    rm.mark_token()                          # first token
    t[0] = 2.0
    rm.mark_token()
    t[0] = 2.5
    rm.mark_token()
    rm.mark_finished()
    d = rm.to_dict()
    assert d["queue_wait"] == 1.0
    assert d["ttft"] == 1.5
    assert d["tpot"] == pytest.approx(0.5)   # (2.5 - 1.5) / 2
    assert d["total"] == 2.5 and d["tokens_out"] == 3


def test_engine_metrics_populated(trained):
    cfg, _ = trained
    eng = make_engine(trained)
    eng.generate([np.asarray([1, 2], np.int32)], max_new_tokens=4)
    s = eng.stats()
    assert s["mean_ttft"] > 0 and s["mean_tpot"] > 0
    assert s["mean_queue_wait"] >= 0
    assert s["tokens_out"] == 4 and s["prefills"] == 1
    # 3 post-prefill tokens fit inside ONE fused chunk dispatch
    # (decode_chunk defaults to 8): a single collected decode step
    assert s["decode_steps"] == 1
    assert s["dispatches"] >= 1
    # amortization series: the one live dispatch carried all 3 tokens
    assert s["mean_tokens_per_dispatch"] == pytest.approx(3.0)


# ---------------------------------------------------------------------------
# decode fast path: fused chunks, donation, overlap pipeline
# ---------------------------------------------------------------------------

def test_chunk_kernel_matches_repeated_slot_steps(trained):
    """decode_loop.decode_chunk over the GPT's paged step (greedy, no
    finishes) is exactly `chunk` consecutive gpt_decode_step_pages +
    argmax iterations: same token block, same arena, same positions —
    the fusion changes dispatch count, not math."""
    import jax
    import jax.numpy as jnp
    cfg, params = trained
    rng = np.random.RandomState(9)
    a = rng.randint(0, cfg.vocab_size, (3,)).astype(np.int32)
    b = rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
    arena, pt_, _ = paged_pool(trained, [a, b])
    tokens = jnp.asarray([5, 9], jnp.int32)
    ts = jnp.asarray([3, 6], jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(0), 2)

    block, arena_f, _, carry, counters = decode_chunk(
        gd.GPT_SERVING_MODEL, params, cfg, arena, pt_, keys,
        carry_of(tokens, ts, [10, 10]), 4)

    ref_arena, ref_tok, ref_ts = arena, tokens, ts
    ref_rows = []
    for _ in range(4):
        logits, ref_arena = gd.gpt_decode_step_pages(
            params, cfg, ref_tok, ref_arena, pt_, ref_ts)
        ref_tok = jnp.argmax(logits, -1).astype(jnp.int32)
        ref_ts = ref_ts + 1
        ref_rows.append(np.asarray(ref_tok))
    np.testing.assert_array_equal(np.asarray(block), np.stack(ref_rows))
    np.testing.assert_array_equal(np.asarray(carry.tokens), ref_rows[-1])
    np.testing.assert_array_equal(np.asarray(carry.ts), np.asarray(ref_ts))
    np.testing.assert_allclose(np.asarray(arena_f), np.asarray(ref_arena),
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(carry.done).any()
    np.testing.assert_array_equal(np.asarray(carry.remaining), [6, 6])
    assert counters is None


def test_chunk_kernel_freezes_exhausted_slot(trained):
    """A slot whose budget runs out mid-chunk rides along frozen: its
    column repeats the final token, ts stops advancing, and the OTHER
    slot's stream is untouched by the freeze."""
    import jax
    import jax.numpy as jnp
    cfg, params = trained
    rng = np.random.RandomState(10)
    a = rng.randint(0, cfg.vocab_size, (4,)).astype(np.int32)
    b = rng.randint(0, cfg.vocab_size, (4,)).astype(np.int32)
    arena, pt_, _ = paged_pool(trained, [a, b])
    block, _, _, carry, _ = decode_chunk(
        gd.GPT_SERVING_MODEL, params, cfg, arena, pt_,
        jax.random.split(jax.random.PRNGKey(1), 2),
        carry_of([5, 9], [4, 4], [2, 10]), 5)     # slot 0 freezes at 2
    col0 = np.asarray(block)[:, 0]
    assert (col0[2:] == col0[1]).all()             # frozen repeats
    assert np.asarray(carry.ts)[0] == 4 + 2        # advanced twice only
    assert np.asarray(carry.done).tolist() == [True, False]
    # slot 1 unaffected: matches a solo unfrozen run of the same chunk
    arena1, pt1, _ = paged_pool(trained, [b])
    solo, *_ = decode_chunk(
        gd.GPT_SERVING_MODEL, params, cfg, arena1, pt1,
        jax.random.split(jax.random.PRNGKey(2), 1),
        carry_of([9], [4], [10]), 5)
    np.testing.assert_array_equal(np.asarray(block)[:, 1],
                                  np.asarray(solo)[:, 0])


def test_chunked_parity_ten_concurrent_all_chunk_sizes(trained):
    """Acceptance pin: ≥10 concurrent requests through few slots are
    token-identical to the sequential gpt_generate path at decode_chunk
    1, 3, and 8 (chunk boundaries landing mid-stream and off-budget),
    and the fused chunk loop adds exactly ONE executable."""
    rng = np.random.RandomState(11)
    cfg, _ = trained
    lens = [2, 3, 4, 5, 6, 7, 8, 3, 5, 7]
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in lens]
    refs = [sequential_ref(trained, p, 6) for p in prompts]
    for chunk in (1, 3, 8):
        eng = make_engine(trained, num_slots=4, decode_chunk=chunk)
        outs = eng.generate(prompts, max_new_tokens=6)
        for p, o, ref in zip(prompts, outs, refs):
            np.testing.assert_array_equal(o, ref)
        events = eng.scheduler.compile_events
        assert events.count("decode_chunk") == 1, events
        assert eng.scheduler.compile_count <= len(eng.buckets) + 2


def test_mid_chunk_eos_retires_early(trained):
    """EOS emitted mid-chunk freezes the slot in-graph and retires it
    host-side at exactly the EOS token — the frozen repeats after it in
    the same block are never emitted."""
    cfg, _ = trained
    rng = np.random.RandomState(7)
    k = None
    for _ in range(20):
        p = rng.randint(0, cfg.vocab_size, (3,)).astype(np.int32)
        gen = list(sequential_ref(trained, p, 12)[3:])
        k = next((i for i in range(1, len(gen))
                  if gen[i] not in gen[:i]), None)
        if k is not None and k % 8 != 7:     # NOT on the chunk boundary
            break
    assert k is not None, "no usable greedy stream found"
    eos = int(gen[k])
    eng = make_engine(trained, decode_chunk=8)
    req = eng.submit(p, max_new_tokens=12, eos_id=eos)
    eng.run_until_drained()
    assert req.finished
    assert req.tokens[-1] == eos and len(req.tokens) == k + 1
    assert eng.stats()["free_slots"] == eng.kv.num_slots


def test_cancel_mid_chunk_discards_post_cancel_tokens(trained):
    """cancel() between pipeline ticks drops the slot before the next
    collect: tokens the in-flight dispatch already produced for the
    request are discarded, the slot frees, and a follow-up request
    through the SAME slot still matches the sequential path."""
    cfg, _ = trained
    rng = np.random.RandomState(12)
    eng = make_engine(trained, num_slots=1, decode_chunk=4)
    a = eng.submit(rng.randint(0, cfg.vocab_size, (3,)).astype(np.int32),
                   max_new_tokens=20)
    eng.step()                 # admit + launch (overlap: not collected)
    eng.step()                 # launch k+1, collect k
    n_a = len(a.tokens)
    assert eng.cancel(a) and a.state == "cancelled"
    eng.run_until_drained()    # driver applies the cancel, drains
    assert len(a.tokens) == n_a            # nothing after the cancel
    assert eng.kv.free_count == 1
    p2 = rng.randint(0, cfg.vocab_size, (5,)).astype(np.int32)
    (out,) = eng.generate([p2], max_new_tokens=6)
    np.testing.assert_array_equal(out, sequential_ref(trained, p2, 6))


def test_retire_admit_across_chunk_boundary(trained):
    """One slot, several queued requests with budgets that end mid-chunk:
    each retirement frees the slot for the next admission at a chunk
    boundary, and every stream stays sequential-identical through the
    slot reuse."""
    rng = np.random.RandomState(13)
    cfg, _ = trained
    prompts = [rng.randint(0, cfg.vocab_size, (2 + i,)).astype(np.int32)
               for i in range(3)]
    budgets = [5, 3, 6]                      # none a multiple of chunk=4
    eng = make_engine(trained, num_slots=1, decode_chunk=4)
    reqs = [eng.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, budgets)]
    eng.run_until_drained()
    for r, p, m in zip(reqs, prompts, budgets):
        assert r.finished and len(r.tokens) == m
        np.testing.assert_array_equal(r.output(),
                                      sequential_ref(trained, p, m))


def test_overlap_off_matches_overlap_on(trained):
    """The double-buffered pipeline changes when blocks are fetched,
    never what they contain: overlap on/off produce identical streams."""
    rng = np.random.RandomState(14)
    cfg, _ = trained
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (3, 5, 7, 4)]
    outs = {}
    for overlap in (True, False):
        eng = make_engine(trained, num_slots=2, decode_chunk=3,
                          overlap=overlap)
        outs[overlap] = eng.generate(prompts, max_new_tokens=7)
        if overlap:
            # overlap really pipelines: while active, collects lag
            # launches by one dispatch (asserted indirectly: the final
            # drain leaves at most one uncollected garbage dispatch)
            assert eng.scheduler.inflight_count <= 1
        else:
            assert eng.scheduler.inflight_count == 0
    for a, b in zip(outs[True], outs[False]):
        np.testing.assert_array_equal(a, b)


def test_sampled_stream_identical_across_chunk_sizes(trained):
    """Sampled (temperature/top-k) streams are chunk-size invariant: the
    per-slot key advances once per decode iteration whatever the fusion
    factor, so request seeds reproduce exactly."""
    cfg, _ = trained
    p = np.asarray([2, 7, 1], np.int32)

    def run(chunk):
        eng = make_engine(trained, top_k=5, decode_chunk=chunk)
        (out,) = eng.generate([p], max_new_tokens=9, temperature=0.8,
                              seed=23)
        return out

    a, b, c = run(1), run(4), run(8)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a, c)


def test_kv_pool_donated_in_place(trained):
    """Buffer donation pin: the pool array consumed by a decode dispatch
    is invalidated (XLA reused its buffer in place) — decode does NOT
    materialize a fresh pool copy per chunk. CPU/TPU backends both
    support donation; this would start failing loudly if the
    donate_argnums wiring regressed to copying."""
    cfg, _ = trained
    eng = make_engine(trained, decode_chunk=2)
    eng.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=8)
    eng.step()                               # admit + first launch
    stale = eng.kv.kv                        # output future of launch k
    eng.step()                               # launch k+1 donates it
    with pytest.raises(RuntimeError):
        np.asarray(stale)                    # deleted: donated away
    eng.run_until_drained()                  # engine itself is unharmed
    assert eng.stats()["completed"] == 1


def test_admit_stages_every_prompt_in_its_own_buffer(trained):
    """No admission waits for its prefill any more, so two prompts of
    ONE bucket admitted in one tick cannot share a host staging buffer
    (the second would refill it before the first prefill's copy is
    made): each pads into a buffer of its own, the pad zeroed."""
    cfg, _ = trained
    eng = make_engine(trained, num_slots=2)
    sched = eng.scheduler
    rng = np.random.RandomState(15)
    three, four = (rng.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
                   for n in (3, 4))
    a, b = sched._staged(three, 4), sched._staged(four, 4)
    assert a is not b and a.shape == b.shape == (1, 4)
    assert a[0].tolist() == three.tolist() + [0] and a.dtype == np.int32
    assert b[0].tolist() == four.tolist()
    reqs = [eng.submit(p, max_new_tokens=2) for p in (three, four)]
    eng.step()                               # both admitted in one tick
    assert eng.stats()["first_tokens"] == 2
    eng.run_until_drained()
    for req, p in zip(reqs, (three, four)):
        assert req.output().tolist() == sequential_ref(trained, p,
                                                       2).tolist()


def test_dispatch_amortization_metrics(trained):
    """serving_dispatches_total / tokens-per-dispatch make the chunk
    amortization measurable: at decode_chunk=8 a 2-slot engine needs
    FAR fewer dispatches than tokens, and the registry carries the
    series for scrapes."""
    from paddle_tpu.observability import get_registry
    rng = np.random.RandomState(16)
    cfg, _ = trained
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (3, 5)]
    eng = make_engine(trained, num_slots=2, decode_chunk=8)
    eng.generate(prompts, max_new_tokens=17)
    s = eng.stats()
    assert s["tokens_out"] == 2 * 17
    # 16 post-prefill tokens per request, 8 per dispatch, 2 slots ride
    # together: 2 live dispatches + pipeline tail
    assert s["dispatches"] * 8 >= 16         # enough capacity dispatched
    assert s["dispatches"] <= 6              # amortized, not per-token
    assert s["mean_tokens_per_dispatch"] >= 8
    snap = get_registry().snapshot()
    series = snap["serving_dispatches_total"]["series"]
    row = next(r for r in series
               if r["labels"].get("engine") == s["engine_label"])
    assert row["value"] == s["dispatches"]
    eng.close()


# ---------------------------------------------------------------------------
# paged pool: capacity, prefix cache, copy-on-write, donation
# ---------------------------------------------------------------------------

def test_paged_arena_packs_beyond_slab_capacity(trained):
    """Acceptance pin: mixed short/long admission packs >= 2x the
    concurrent requests a slab of the SAME arena bytes could hold. 8
    allocatable blocks of 8 positions = 64 positions = 2 slab slots at
    max_len 32; the paged pool runs 6 requests concurrently in the same
    bytes because each maps only the pages its prompt+budget needs."""
    rng = np.random.RandomState(21)
    cfg, _ = trained
    eng = make_engine(trained, num_slots=6, prefill_buckets=(4, 16),
                      block_size=8, kv_blocks=9)       # 8 + scratch
    slab_slots = (8 * 8) // eng.kv.max_len             # what a slab held
    assert slab_slots == 2
    long_p = rng.randint(0, cfg.vocab_size, (12,)).astype(np.int32)
    shorts = [rng.randint(0, cfg.vocab_size, (3,)).astype(np.int32)
              for _ in range(5)]
    reqs = [eng.submit(long_p, max_new_tokens=8)]      # 20 pos = 3 blocks
    reqs += [eng.submit(p, max_new_tokens=4) for p in shorts]  # 1 each
    eng.step()                                         # admit everything
    assert eng.kv.active_count == 6 >= 2 * slab_slots
    s = eng.stats()
    assert s["blocks_used"] == 8 and s["blocks_total"] == 8
    eng.run_until_drained()
    assert all(r.finished for r in reqs)
    np.testing.assert_array_equal(
        reqs[0].output(), sequential_ref(trained, long_p, 8))
    for r, p in zip(reqs[1:], shorts):
        np.testing.assert_array_equal(r.output(),
                                      sequential_ref(trained, p, 4))
    assert eng.stats()["peak_blocks_used"] == 8
    assert eng.stats()["blocks_used"] == 0             # all pages freed


def test_prefix_cache_hit_decode_token_identical_to_cold(trained):
    """Acceptance pin: a prompt re-admitted after its prefix blocks went
    to the LRU pool maps them back (prefix_hits > 0) and its stream is
    token-identical to the cold run AND to the sequential path."""
    rng = np.random.RandomState(22)
    cfg, _ = trained
    p = rng.randint(0, cfg.vocab_size, (10,)).astype(np.int32)
    eng = make_engine(trained, prefill_buckets=(4, 16), block_size=4)
    (cold,) = eng.generate([p], max_new_tokens=6)
    assert eng.kv.prefix_hits == 0 and eng.kv.prefix_misses == 2
    assert eng.kv.blocks_cached == 2                   # LRU-warm prefix
    (warm,) = eng.generate([p], max_new_tokens=6)
    assert eng.kv.prefix_hits == 2                     # shared, not redone
    np.testing.assert_array_equal(warm, cold)
    np.testing.assert_array_equal(warm, sequential_ref(trained, p, 6))
    s = eng.stats()
    assert s["prefix_hits"] == 2 and s["prefix_misses"] == 2
    # registry carries the series for scrapes
    from paddle_tpu.observability import get_registry
    snap = get_registry().snapshot()
    row = next(r for r in
               snap["serving_prefix_cache_hits_total"]["series"]
               if r["labels"].get("engine") == s["engine_label"])
    assert row["value"] == 2
    eng.close()
    # close() retires the paged-pool series with the rest of the
    # engine's labels — no ghost rows for a dead engine
    snap = get_registry().snapshot()
    for fam in ("serving_prefix_cache_hits_total",
                "serving_prefix_cache_misses_total",
                "serving_kv_blocks_total", "serving_kv_blocks_used",
                "serving_kv_blocks_cached"):
        assert not any(r["labels"].get("engine") == s["engine_label"]
                       for r in snap.get(fam, {}).get("series", []))


def test_prefix_cache_off_never_shares(trained):
    """ServingConfig(prefix_cache=False) disables sharing: identical
    prompts re-prefill cold every time, streams unchanged."""
    rng = np.random.RandomState(23)
    cfg, _ = trained
    p = rng.randint(0, cfg.vocab_size, (10,)).astype(np.int32)
    eng = make_engine(trained, prefill_buckets=(4, 16), block_size=4,
                      prefix_cache=False)
    (a,) = eng.generate([p], max_new_tokens=6)
    (b,) = eng.generate([p], max_new_tokens=6)
    assert eng.kv.prefix_hits == 0 and eng.kv.blocks_cached == 0
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a, sequential_ref(trained, p, 6))


def test_cow_isolation_shared_prefix_divergent_tails(trained):
    """Copy-on-write pin: two CONCURRENT requests sharing a prefix then
    diverging never see each other's K/V — the shared full blocks are
    mapped into both page tables (refcounted) while each divergent tail
    lives in private blocks, and both streams match the sequential
    path exactly."""
    rng = np.random.RandomState(24)
    cfg, _ = trained
    pre = rng.randint(0, cfg.vocab_size, (8,)).astype(np.int32)
    x = np.concatenate([pre, [3]]).astype(np.int32)
    y = np.concatenate([pre, [11]]).astype(np.int32)
    eng = make_engine(trained, num_slots=2, prefill_buckets=(4, 16),
                      block_size=4)
    rx = eng.submit(x, max_new_tokens=7)
    ry = eng.submit(y, max_new_tokens=7)
    eng.step()                                         # both admitted
    assert eng.kv.active_count == 2
    assert eng.kv.prefix_hits == 2                     # y mapped x's prefix
    pt = eng.kv.page_table
    np.testing.assert_array_equal(pt[0][:2], pt[1][:2])  # shared blocks
    assert pt[0][2] != pt[1][2]                        # private tails
    eng.run_until_drained()
    np.testing.assert_array_equal(rx.output(),
                                  sequential_ref(trained, x, 7))
    np.testing.assert_array_equal(ry.output(),
                                  sequential_ref(trained, y, 7))


def test_prefix_hits_stay_within_bucket_compile_bound(trained):
    """Prefix hits shrink the prefill SUFFIX into smaller buckets but
    never add executables beyond the bucket set: compile count stays
    O(buckets) + admit + 1 chunk loop through cold AND warm admissions
    (the page table adds zero per-request compiles)."""
    rng = np.random.RandomState(25)
    cfg, _ = trained
    p = rng.randint(0, cfg.vocab_size, (10,)).astype(np.int32)
    eng = make_engine(trained, prefill_buckets=(4, 16), block_size=4)
    eng.generate([p], max_new_tokens=5)                # cold: bucket 16
    eng.generate([p], max_new_tokens=5)                # warm: bucket 4
    events = eng.scheduler.compile_events
    assert {e for e in events if e.startswith("prefill")} \
        <= {"prefill:L4", "prefill:L16"}
    assert events.count("decode_chunk") == 1
    assert eng.scheduler.compile_count <= len(eng.buckets) + 2


def test_arena_and_page_table_donated_in_place(trained):
    """Donation pin for the paged pool: the arena consumed by a decode
    dispatch and the page table consumed by an admission prefill are
    both invalidated (XLA reused their buffers in place) — stale
    references raise instead of silently reading dead memory."""
    cfg, _ = trained
    eng = make_engine(trained, decode_chunk=2)
    eng.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=8)
    eng.step()                               # admit + first launch
    stale_arena = eng.kv.kv                  # output future of launch k
    stale_pt = eng.scheduler._pt             # page table after admit
    eng.step()                               # launch k+1 donates arena
    with pytest.raises(RuntimeError):
        np.asarray(stale_arena)              # deleted: donated away
    # the chunk READS the page table (no donation there); admission is
    # where it is updated — and donated
    eng.submit(np.asarray([4, 5], np.int32), max_new_tokens=2)
    eng.step()                               # prefill donates + rewrites
    with pytest.raises(RuntimeError):
        np.asarray(stale_pt)
    eng.run_until_drained()                  # engine itself is unharmed
    assert eng.stats()["completed"] == 2


def test_pages_exhausted_queues_then_flows(trained):
    """An arena too small for every submitted request at once admits by
    PAGES: head-of-line requests wait for retirements to free blocks,
    then flow through FIFO — no deadlock, no shed, streams exact."""
    rng = np.random.RandomState(26)
    cfg, _ = trained
    prompts = [rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
               for _ in range(4)]
    # 4 requests x 2 blocks each, arena of 4 blocks: 2 concurrent max
    eng = make_engine(trained, num_slots=4, prefill_buckets=(4, 8),
                      block_size=8, kv_blocks=5, max_len=16)
    reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.step()
    assert eng.kv.active_count == 2          # pages, not slots, bound it
    eng.run_until_drained()
    assert all(r.finished for r in reqs)
    assert eng.stats()["shed"] == 0
    for r, p in zip(reqs, prompts):
        np.testing.assert_array_equal(r.output(),
                                      sequential_ref(trained, p, 5))


def test_sampled_prefix_hit_stream_chunk_invariant(trained):
    """Seeded sampling with prefix-cache hits: the warm (shared-prefix)
    stream is identical to the cold one AND invariant across chunk
    sizes — mapping cached blocks instead of re-prefilling changes
    where K/V come from, never what gets sampled."""
    cfg, _ = trained
    rng = np.random.RandomState(28)
    p = rng.randint(0, cfg.vocab_size, (10,)).astype(np.int32)

    def run(chunk):
        eng = make_engine(trained, top_k=5, prefill_buckets=(4, 16),
                          block_size=4, decode_chunk=chunk)
        (cold,) = eng.generate([p], max_new_tokens=9, temperature=0.8,
                               seed=31)
        (warm,) = eng.generate([p], max_new_tokens=9, temperature=0.8,
                               seed=31)
        assert eng.kv.prefix_hits == 2
        np.testing.assert_array_equal(cold, warm)
        return warm

    a, b, c = run(1), run(4), run(8)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a, c)


def test_prefix_hit_near_full_context_pad_writes_stay_in_scratch(trained):
    """Regression pin: with a LARGE hit prefix and a small suffix
    bucket, the padded suffix runs past max_pages*block_size — a
    clamped page gather would collide pad writes with a real K/V row
    (position pfx, block max_pages-1, offset 0). Pad writes must land
    in the scratch block instead, keeping the warm stream exact."""
    rng = np.random.RandomState(29)
    cfg, _ = trained
    p = rng.randint(0, cfg.vocab_size, (30,)).astype(np.int32)
    eng = make_engine(trained, prefill_buckets=(8, 32), block_size=4,
                      max_len=32)
    (cold,) = eng.generate([p], max_new_tokens=2)
    (warm,) = eng.generate([p], max_new_tokens=2)
    # pfx = 28 (7 hit blocks), suffix 2 -> bucket 8: pad positions
    # reach 35 > 31 = last arena position
    assert eng.kv.prefix_hits == 7
    np.testing.assert_array_equal(warm, cold)
    np.testing.assert_array_equal(warm, sequential_ref(trained, p, 2))


def test_cancel_releases_pages_on_device(trained):
    """cancel() frees the slot's pages AND freezes the device-side slot
    through the release executable, so reallocated blocks are never
    dirtied by the cancelled slot's ride-along decode — a follow-up
    request reusing the freed pages stays sequential-identical."""
    rng = np.random.RandomState(27)
    cfg, _ = trained
    eng = make_engine(trained, num_slots=2, prefill_buckets=(4, 8),
                      block_size=4, kv_blocks=5, max_len=16,
                      decode_chunk=4)
    a = eng.submit(rng.randint(0, cfg.vocab_size, (4,)).astype(np.int32),
                   max_new_tokens=12)                  # 16 pos = 4 blocks
    eng.step()                               # admitted, chunk in flight
    assert eng.kv.blocks_used == 4
    assert eng.cancel(a)
    eng.step()                               # driver applies the cancel
    assert eng.kv.blocks_used == 0
    assert "release_slot" in eng.scheduler.compile_events
    # the freed pages immediately serve a new request, exactly
    p2 = rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
    (out,) = eng.generate([p2], max_new_tokens=8)
    np.testing.assert_array_equal(out, sequential_ref(trained, p2, 8))


# ---------------------------------------------------------------------------
# speculative decoding: draft/verify inside the fused chunk loop
# ---------------------------------------------------------------------------

def test_spec_chunk_kernel_commits_nonspec_stream(trained):
    """Loop pin: decode_loop.decode_chunk with speculate_k>0 over the
    GPT's verify pass commits EXACTLY the non-speculative stream —
    acceptance changes how many tokens each verify pass emits (the
    counts column), never which tokens — and the carry (ts/remaining)
    advances by the committed totals."""
    import jax
    import jax.numpy as jnp
    cfg, params = trained
    rng = np.random.RandomState(40)
    a = rng.randint(0, cfg.vocab_size, (3,)).astype(np.int32)
    b = rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
    arena, pt_, _ = paged_pool(trained, [a, b])
    keys = jax.random.split(jax.random.PRNGKey(0), 2)

    ref_block, *_ = decode_chunk(
        gd.GPT_SERVING_MODEL, params, cfg, arena, pt_, keys,
        carry_of([5, 9], [3, 6], [20, 20]), 6)
    ref = np.asarray(ref_block)                    # (6, 2)

    spec = (jnp.zeros((2,), jnp.int32),
            jnp.full((2, 65), -1, jnp.int32))      # ngram table T=64
    (block, counts), _, _, carry, _ = decode_chunk(
        gd.GPT_SERVING_MODEL, params, cfg, arena, pt_, keys,
        carry_of([5, 9], [3, 6], [20, 20], spec=spec), 6, speculate_k=3)
    block, counts = np.asarray(block), np.asarray(counts)
    for s in range(2):
        committed = [int(block[i, j, s]) for i in range(6)
                     for j in range(counts[i, s])]
        assert committed[:6] == list(ref[:, s])
        total = counts[:, s].sum()
        assert np.asarray(carry.ts)[s] == [3, 6][s] + total
        assert np.asarray(carry.remaining)[s] == 20 - total
    assert (counts >= 1).all() and (counts <= 4).all()


def test_spec_greedy_parity_all_chunk_sizes(trained):
    """Acceptance pin: speculation ON keeps ≥10 concurrent greedy
    streams token-identical to sequential gpt_generate at decode_chunk
    1, 4, and 8, and the speculative chunk loop still traces exactly
    ONE executable (compile count stays O(buckets) + admit + 1)."""
    rng = np.random.RandomState(41)
    cfg, _ = trained
    lens = [2, 3, 4, 5, 6, 7, 8, 3, 5, 7]
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in lens]
    refs = [sequential_ref(trained, p, 6) for p in prompts]
    for chunk in (1, 4, 8):
        eng = make_engine(trained, num_slots=4, decode_chunk=chunk,
                          speculate_k=3)
        outs = eng.generate(prompts, max_new_tokens=6)
        for o, ref in zip(outs, refs):
            np.testing.assert_array_equal(o, ref)
        events = eng.scheduler.compile_events
        assert events.count("decode_chunk") == 1, events
        assert eng.scheduler.compile_count <= len(eng.buckets) + 2
        eng.close()


def test_spec_seeded_stream_identical_on_off(trained):
    """Seeded sampling pin: temperature/top-k streams are identical
    with speculation on and off, at every speculate_k and chunk size —
    acceptance is exact-match against the sampler's own draw under the
    sequential key schedule, so the drafter can never change a sampled
    token either."""
    cfg, _ = trained
    p = np.asarray([2, 7, 1], np.int32)

    def run(k, chunk):
        eng = make_engine(trained, top_k=5, decode_chunk=chunk,
                          speculate_k=k)
        (out,) = eng.generate([p], max_new_tokens=9, temperature=0.8,
                              seed=23)
        eng.close()
        return out

    base = run(0, 4)
    for k in (1, 2, 4):
        for chunk in (1, 4):
            np.testing.assert_array_equal(base, run(k, chunk))


def test_spec_mid_chunk_eos_retires_early(trained):
    """EOS emitted mid-verify-run freezes the slot in-graph at exactly
    the EOS token with speculation on: the committed run ends there,
    the host retires at the same token, and nothing after it is
    emitted."""
    cfg, _ = trained
    rng = np.random.RandomState(7)      # same stream as the non-spec pin
    k = None
    for _ in range(20):
        p = rng.randint(0, cfg.vocab_size, (3,)).astype(np.int32)
        gen = list(sequential_ref(trained, p, 12)[3:])
        k = next((i for i in range(1, len(gen))
                  if gen[i] not in gen[:i]), None)
        if k is not None and k % 8 != 7:
            break
    assert k is not None, "no usable greedy stream found"
    eos = int(gen[k])
    eng = make_engine(trained, decode_chunk=8, speculate_k=3)
    req = eng.submit(p, max_new_tokens=12, eos_id=eos)
    eng.run_until_drained()
    assert req.finished
    assert req.tokens[-1] == eos and len(req.tokens) == k + 1
    assert eng.stats()["free_slots"] == eng.kv.num_slots
    eng.close()


def test_spec_prefix_cache_hit_stream_identical(trained):
    """Paged-path pin: prefix-cache hits with speculation on — the warm
    stream (drafter seeded only from the shrunken prompt SUFFIX) is
    identical to the cold run and to the sequential path; sharing
    changes where K/V come from and how much the drafter sees, never
    what commits."""
    rng = np.random.RandomState(42)
    cfg, _ = trained
    p = rng.randint(0, cfg.vocab_size, (10,)).astype(np.int32)
    eng = make_engine(trained, prefill_buckets=(4, 16), block_size=4,
                      speculate_k=2)
    (cold,) = eng.generate([p], max_new_tokens=6)
    (warm,) = eng.generate([p], max_new_tokens=6)
    assert eng.kv.prefix_hits == 2
    np.testing.assert_array_equal(warm, cold)
    np.testing.assert_array_equal(warm, sequential_ref(trained, p, 6))
    eng.close()


def test_spec_retire_admit_slot_reuse(trained):
    """Slot reuse under speculation: budgets ending mid-chunk through
    ONE slot — each retirement frees the slot, the next admission
    resets the drafter row (no n-gram leakage from the previous
    occupant can change tokens anyway: drafts are verified), and every
    stream stays sequential-identical."""
    rng = np.random.RandomState(43)
    cfg, _ = trained
    prompts = [rng.randint(0, cfg.vocab_size, (2 + i,)).astype(np.int32)
               for i in range(3)]
    budgets = [5, 3, 6]
    eng = make_engine(trained, num_slots=1, decode_chunk=4,
                      speculate_k=2)
    reqs = [eng.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, budgets)]
    eng.run_until_drained()
    for r, p, m in zip(reqs, prompts, budgets):
        assert r.finished and len(r.tokens) == m
        np.testing.assert_array_equal(r.output(),
                                      sequential_ref(trained, p, m))
    eng.close()


def test_spec_cancel_mid_chunk_discards_unverified(trained):
    """Satellite pin: cancel with speculation active discards BOTH the
    uncollected in-flight tokens and any speculated-but-unverified
    drafter state — the live_from walk skips the cancelled slot's
    (token, count) columns entirely, the release executable freezes it
    on device, and a follow-up request through the SAME slot (whose
    admission resets the drafter row) still matches the sequential
    path."""
    cfg, _ = trained
    rng = np.random.RandomState(44)
    eng = make_engine(trained, num_slots=1, decode_chunk=4,
                      speculate_k=3)
    a = eng.submit(rng.randint(0, cfg.vocab_size, (3,)).astype(np.int32),
                   max_new_tokens=20)
    eng.step()                 # admit + launch (overlap: not collected)
    eng.step()                 # launch k+1, collect k
    n_a = len(a.tokens)
    assert n_a < 20            # mid-stream, speculation or not
    assert eng.cancel(a) and a.state == "cancelled"
    eng.run_until_drained()    # driver applies the cancel, drains
    assert len(a.tokens) == n_a            # nothing after the cancel
    assert eng.kv.free_count == 1
    assert "release_slot" in eng.scheduler.compile_events
    p2 = rng.randint(0, cfg.vocab_size, (5,)).astype(np.int32)
    (out,) = eng.generate([p2], max_new_tokens=6)
    np.testing.assert_array_equal(out, sequential_ref(trained, p2, 6))
    eng.close()


def test_spec_acceptance_telemetry_repetitive_prompt(trained):
    """A repetitive prompt (tiled motif) makes the self-drafter earn
    its keep: >1 token committed per verify pass, and the telemetry is
    registry-visible — serving_spec_{proposed,accepted}_total counters,
    the per-pass acceptance histogram, and the /varz acceptance-ratio
    rollup all carry the engine's numbers."""
    from paddle_tpu.observability import get_registry
    from paddle_tpu.observability.debug_server import _serving_varz
    rng = np.random.RandomState(45)
    cfg, _ = trained
    motif = rng.randint(0, cfg.vocab_size, (4,))
    p = np.tile(motif, 2).astype(np.int32)
    eng = make_engine(trained, num_slots=1, prefill_buckets=(8,),
                      max_len=48, decode_chunk=8, speculate_k=4)
    (out,) = eng.generate([p], max_new_tokens=32)
    np.testing.assert_array_equal(out, sequential_ref(trained, p, 32))
    sched = eng.scheduler
    assert sched.spec_passes > 0
    assert sched.spec_proposed == 4 * sched.spec_passes
    assert sched.spec_accepted > sched.spec_passes  # >1 accepted/pass avg
    tokens_per_pass = (sched.spec_passes + sched.spec_accepted) \
        / sched.spec_passes
    assert tokens_per_pass > 2.0, tokens_per_pass
    s = eng.stats()
    assert s["spec_proposed"] == sched.spec_proposed
    assert s["spec_accepted"] == sched.spec_accepted
    assert s["mean_spec_accepted_run"] > 1.0
    snap = get_registry().snapshot()
    for fam, want in (("serving_spec_proposed_total",
                       sched.spec_proposed),
                      ("serving_spec_accepted_total",
                       sched.spec_accepted)):
        row = next(r for r in snap[fam]["series"]
                   if r["labels"].get("engine") == s["engine_label"])
        assert row["value"] == want
    hist = next(r for r in snap["serving_spec_accepted_run"]["series"]
                if r["labels"].get("engine") == s["engine_label"])
    assert hist["count"] == sched.spec_passes
    varz = _serving_varz(snap)["spec_accept_ratio"][s["engine_label"]]
    assert varz["spec_proposed"] == sched.spec_proposed
    assert varz["spec_accept_ratio"] == round(
        sched.spec_accepted / sched.spec_proposed, 4)
    eng.close()


def test_spec_dispatch_floor_preserved(trained):
    """Speculation only over-delivers: dispatches-per-token stays at or
    under the 1/chunk steady-state bound (each dispatch still carries
    at least `chunk` tokens per live slot), and acceptance REDUCES the
    dispatch count on drafter-friendly streams."""
    rng = np.random.RandomState(46)
    cfg, _ = trained
    motif = rng.randint(0, cfg.vocab_size, (4,))
    p = np.tile(motif, 2).astype(np.int32)
    counts = {}
    for k in (0, 4):
        eng = make_engine(trained, num_slots=1, prefill_buckets=(8,),
                          max_len=48, decode_chunk=8, speculate_k=k)
        (out,) = eng.generate([p], max_new_tokens=32)
        s = eng.stats()
        # launch bound: never more dispatches than the non-spec path
        # needs (31 decode tokens / 8 per dispatch, +1 tail overshoot)
        assert 1 <= s["dispatches"] <= -(-31 // 8) + 1
        counts[k] = s["dispatches"]
        eng.close()
    assert counts[4] < counts[0], counts


def test_spec_metrics_bucket_scaling():
    """Satellite pin: the tokens-per-dispatch histogram series is
    count-scaled by chunk * (1 + speculate_k) — an engine whose
    per-dispatch ceiling exceeds the base grid gets widened per-series
    buckets (so accepted runs don't all pile into +Inf), while the
    family-level layout stays shared and conflict-free; the acceptance
    histogram spans exactly 0..speculate_k."""
    from paddle_tpu.serving.metrics import (EngineMetrics, _count_buckets,
                                            _TPD_BASE)
    assert _count_buckets(512) == _TPD_BASE
    # 16 slots x chunk 8 x (1 + k=4) = 640 > 512: widened to 1024
    m = EngineMetrics(max_tokens_per_dispatch=16 * 8 * 5, speculate_k=4)
    tpd = m._hists["tokens_per_dispatch"]
    assert tpd._bounds[-1] == 1024 and tpd._bounds[0] == 1
    run = m._hists["spec_accepted_run"]
    assert run._bounds == (0, 1, 2, 3, 4)
    m.observe_dispatch_tokens(640)              # not in +Inf
    assert dict(tpd.cumulative_buckets())["1024"] == 1
    m.unregister()
    # a default engine in the SAME registry keeps the base layout —
    # no family-level bucket conflict between differently-sized engines
    m2 = EngineMetrics()
    assert m2._hists["tokens_per_dispatch"]._bounds == _TPD_BASE
    m2.unregister()


@pytest.mark.slow
def test_spec_long_acceptance_soak(trained):
    """Slow soak: many requests, mixed repetitive/random prompts, spec
    on — every stream sequential-identical over hundreds of verify
    passes, acceptance telemetry consistent (accepted <= proposed,
    histogram count == passes)."""
    rng = np.random.RandomState(47)
    cfg, _ = trained
    prompts = []
    for i in range(24):
        if i % 2:
            motif = rng.randint(0, cfg.vocab_size, (3,))
            prompts.append(np.tile(motif, 3)[:8].astype(np.int32))
        else:
            prompts.append(rng.randint(0, cfg.vocab_size, (5 + i % 4,))
                           .astype(np.int32))
    refs = [sequential_ref(trained, p, 20) for p in prompts]
    eng = make_engine(trained, num_slots=4, max_queue=24, max_len=32,
                      decode_chunk=8, speculate_k=3)
    outs = eng.generate(prompts, max_new_tokens=20)
    for o, ref in zip(outs, refs):
        np.testing.assert_array_equal(o, ref)
    sched = eng.scheduler
    assert sched.spec_passes > 100
    assert 0 <= sched.spec_accepted <= sched.spec_proposed
    assert sched.spec_proposed == 3 * sched.spec_passes
    eng.close()


# ---------------------------------------------------------------------------
# kv-cache manager units
# ---------------------------------------------------------------------------

def test_shape_buckets():
    b = ShapeBuckets([8, 4, 16])
    assert b.sizes == (4, 8, 16) and len(b) == 3 and b.max == 16
    assert b.bucket_for(1) == 4 and b.bucket_for(4) == 4
    assert b.bucket_for(5) == 8 and b.bucket_for(16) == 16
    with pytest.raises(ValueError, match="bucket"):
        b.bucket_for(17)
    with pytest.raises(ValueError):
        ShapeBuckets([])


def test_slot_kv_cache_alloc_free(trained):
    cfg, _ = trained
    kv = SlotKVCache(cfg, num_slots=2, max_len=16, block_size=4)
    # paged arena: num_blocks defaults to slab-equivalent capacity
    # (num_slots * pages-per-max_len) + the reserved scratch block 0
    assert kv.max_pages == 4 and kv.num_blocks == 2 * 4 + 1
    # a row's K and V side by side (gpt_decode.paged_arena_shapes)
    assert kv.kv.shape == (cfg.layers, 1, 9, cfg.heads, 4,
                           2 * (cfg.hidden // cfg.heads))
    assert kv.blocks_total == 8 and kv.blocks_used == 0
    a, b = kv.alloc(), kv.alloc()
    assert {a, b} == {0, 1} and kv.alloc() is None
    assert kv.free_count == 0 and kv.active_count == 2
    row, pfx = kv.map_slot(a, np.asarray([1, 2, 3], np.int32), 6)
    assert pfx == 0 and kv.length(a) == 3
    mapped = [x for x in row if x != 0]
    assert len(mapped) == 2 and kv.blocks_used == 2   # 6 positions, bs=4
    assert (row == kv.page_table[a]).all()
    kv.advance(a)
    assert kv.length(a) == 4
    kv.free(a)
    assert kv.free_count == 1 and kv.length(a) == 0
    assert kv.blocks_used == 0
    assert (kv.page_table[a] == 0).all()              # row back to scratch
    with pytest.raises(ValueError, match="double free"):
        kv.free(a)
    with pytest.raises(ValueError, match="out of range"):
        kv.free(7)
    with pytest.raises(ValueError, match="range"):
        kv.set_length(b, 17)
    assert kv.occupancy()["active_slots"] == 1
    assert kv.occupancy()["blocks_total"] == 8


def test_block_allocator_refcount_lru_eviction(trained):
    """Prefix-cache block lifecycle: shared blocks are refcounted, drop
    to the LRU pool when unreferenced, serve hits from there, and are
    evicted (oldest first) when a fresh allocation needs pages."""
    cfg, _ = trained
    kv = SlotKVCache(cfg, num_slots=4, max_len=16, block_size=4,
                     num_blocks=7)                     # 6 allocatable
    long = np.arange(1, 12, dtype=np.int32)            # 11 tokens: 2 full
    a = kv.alloc()
    row_a, pfx_a = kv.map_slot(a, long, 12)            # 3 blocks, cold
    assert pfx_a == 0 and kv.prefix_hits == 0 and kv.prefix_misses == 2
    b = kv.alloc()
    row_b, pfx_b = kv.map_slot(b, long, 12)            # shares 2 blocks
    assert pfx_b == 8 and kv.prefix_hits == 2
    assert list(row_b[:2]) == list(row_a[:2])          # same blocks mapped
    assert row_b[2] != row_a[2]                        # private tails
    assert kv.blocks_used == 4                         # 2 shared + 2 tails
    kv.free(a)
    # a's shared blocks stay referenced by b; only its tail frees
    assert kv.blocks_used == 3 and kv.blocks_cached == 0
    kv.free(b)
    # now unreferenced but still cached (LRU), not freed
    assert kv.blocks_used == 0 and kv.blocks_cached == 2
    c = kv.alloc()
    row_c, pfx_c = kv.map_slot(c, long, 12)            # hits from LRU
    assert pfx_c == 8 and kv.prefix_hits == 4
    assert list(row_c[:2]) == list(row_a[:2])
    kv.free(c)
    # a different prompt drains the free list (no eviction needed yet)
    d = kv.alloc()
    other = np.arange(50, 66, dtype=np.int32)          # 16 tokens: 4 blocks
    row_d, _ = kv.map_slot(d, other, 16)
    assert kv.blocks_used == 4 and kv.blocks_cached == 2
    # infeasible admission fails cleanly: no partial eviction, no leak
    e = kv.alloc()
    assert not kv.can_map(np.arange(3, dtype=np.int32), 9)   # 3 > 2 avail
    assert kv.map_slot(e, np.arange(3, dtype=np.int32), 9) is None
    assert kv.blocks_cached == 2 and kv.blocks_used == 4
    # a feasible one EVICTS the cached prefix blocks under pressure
    row_e, _ = kv.map_slot(e, np.asarray([7, 8, 9], np.int32), 8)
    assert kv.blocks_cached == 0 and kv.blocks_used == 6
    kv.free(e)
    kv.free(d)
    # the evicted prefix no longer hits: a fresh `long` maps cold
    f = kv.alloc()
    _, pfx_f = kv.map_slot(f, long, 12)
    assert pfx_f == 0 and kv.prefix_hits == 4          # unchanged


# ---------------------------------------------------------------------------
# create_engine entry point + PredictorPool thread-safety
# ---------------------------------------------------------------------------

def test_create_engine_from_saved_model(trained, tmp_path):
    """inference.create_engine loads a saved GPT dir through the
    Predictor machinery and serves it with sequential-path parity."""
    cfg = tiny_cfg()
    with pt.unique_name_guard():
        main, startup, fetches = gpt_lm_program(cfg, 8, is_test=True)
    exe = pt.Executor()
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup)
        params = gd.collect_gpt_params(scope, cfg)
        pt.io.save_inference_model(str(tmp_path), ["tokens"],
                                   [fetches["logits"]], exe,
                                   main_program=main)
    eng = pt.inference.create_engine(
        str(tmp_path), cfg,
        serving=ServingConfig(num_slots=2, prefill_buckets=(4, 8),
                              max_len=32))
    rng = np.random.RandomState(6)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (3, 6)]
    outs = eng.generate(prompts, max_new_tokens=4)
    for p, o in zip(prompts, outs):
        ref = gd.gpt_generate(params, cfg, p[None], 4)[0]
        np.testing.assert_array_equal(o, ref)


def test_predictor_pool_exclusive_acquire(tmp_path):
    """acquire() hands each predictor to at most one thread at a time and
    times out (sheds) rather than queueing forever."""
    main, startup = pt.Program(), pt.Program()
    with pt.unique_name_guard(), pt.program_guard(main, startup):
        x = pt.layers.data("x", [4])
        y = pt.layers.fc(x, 4)
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        pt.io.save_inference_model(str(tmp_path), ["x"], [y], exe,
                                   main_program=main)
    pool = pt.inference.PredictorPool(pt.inference.Config(str(tmp_path)),
                                      size=2)
    assert pool.size() == 2
    in_use, peak, errs = [0], [0], []
    lock = threading.Lock()

    def worker():
        try:
            for _ in range(5):
                with pool.acquire(timeout=30) as pred:
                    with lock:
                        in_use[0] += 1
                        peak[0] = max(peak[0], in_use[0])
                        assert in_use[0] <= 2
                    pred.run({"x": np.ones((1, 4), np.float32)})
                    with lock:
                        in_use[0] -= 1
        except Exception as e:                # surface thread failures
            errs.append(e)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs
    assert 1 <= peak[0] <= 2

    with pool.acquire() as a, pool.acquire() as b:
        assert a is not b
        with pytest.raises(TimeoutError, match="no free predictor"):
            with pool.acquire(timeout=0.05):
                pass


# ---------------------------------------------------------------------------
# host-swap preemption + deterministic fault injection
# ---------------------------------------------------------------------------

# over-subscribed arena: 4 requests x blocks_for(7 prompt + 12 new) =
# 5 blocks each = up to 20 blocks demanded vs 11 allocatable -> the
# engine MUST preempt (host-swap a running sequence out) to flow
PRESSURE = dict(num_slots=4, max_queue=16, block_size=4, kv_blocks=12,
                decode_chunk=4, preempt=True)


def _pressure_prompts(cfg):
    rng = np.random.RandomState(0)
    return [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
            for n in (5, 7, 4, 6)]


def test_preempt_swap_resume_greedy_identity_and_no_leaks(trained):
    """The tentpole pin, greedy half: an over-subscribed arena forces a
    preemption (pages host-swapped, slot freed, sequence later resumed)
    and every stream is STILL bit-identical to the sequential
    gpt_generate path; after the drain no pages, no parked sequences,
    and no host swap-pool bytes are left behind. The registry series
    and the /varz preemption rollup carry the same numbers the engine
    stats report."""
    from paddle_tpu.observability import get_registry
    from paddle_tpu.observability.debug_server import _serving_varz

    cfg, _ = trained
    prompts = _pressure_prompts(cfg)
    eng = make_engine(trained, **PRESSURE)
    outs = eng.generate(prompts, max_new_tokens=12)
    s = eng.stats()
    assert s["preemptions"] >= 1, "arena not tight enough to preempt"
    assert s["swap_ins"] == s["preemptions"]   # everything parked resumed
    for p, o in zip(prompts, outs):
        np.testing.assert_array_equal(o, sequential_ref(trained, p, 12))
    # leak-free drain: no parked work, no pages, no host pool bytes
    assert s["swapped_slots"] == 0
    assert s["blocks_used"] == 0
    assert s["swap_pool_bytes"] == 0
    label = s["engine_label"]
    snap = get_registry().snapshot()
    for fam, want in (("serving_preemptions_total", s["preemptions"]),
                      ("serving_swap_ins_total", s["swap_ins"]),
                      ("serving_swapped_slots", 0)):
        row = next(r for r in snap[fam]["series"]
                   if r["labels"].get("engine") == label)
        assert row["value"] == want, fam
    for fam in ("serving_swap_out_seconds", "serving_swap_in_seconds"):
        hist = next(r for r in snap[fam]["series"]
                    if r["labels"].get("engine") == label)
        assert hist["count"] == s["preemptions"], fam
    assert _serving_varz(snap)["preemption"][label] == {
        "preemptions": s["preemptions"], "swap_ins": s["swap_ins"],
        "swapped_slots": 0}
    eng.close()


@pytest.mark.parametrize("k", [0, 2])
def test_preempt_seeded_stream_identity(trained, k):
    """The tentpole pin, seeded half (with and without speculation): a
    preempted + swapped + resumed run produces bit-identical sampled
    streams to an unpressured run of the same requests. This is what
    the slot-independent threefry sampler buys — the resumed sequence
    may land in a different slot at a different step and still replay
    its exact key chain."""
    cfg, _ = trained
    prompts = _pressure_prompts(cfg)
    tight = make_engine(trained, speculate_k=k, **PRESSURE)
    roomy = make_engine(trained, num_slots=4, max_queue=16, block_size=4,
                        decode_chunk=4, speculate_k=k)
    o_t = tight.generate(prompts, max_new_tokens=12, temperature=0.8,
                         seed=3)
    o_r = roomy.generate(prompts, max_new_tokens=12, temperature=0.8,
                         seed=3)
    assert tight.stats()["preemptions"] >= 1
    assert roomy.stats()["preemptions"] == 0
    for a, b in zip(o_t, o_r):
        np.testing.assert_array_equal(a, b)
    assert tight.stats()["blocks_used"] == 0
    tight.close()
    roomy.close()


def test_drain_with_swapped_sequences_finishes_every_stream(trained):
    """Graceful drain while preempted sequences sit in the host swap
    pool: the drive loop counts parked work as pending, swaps it back
    in when pages free, and every stream finishes with its full budget
    — zero dropped tokens, zero leaked pages. Slow-step injection
    widens the parked window so the test observes the swapped state
    deterministically rather than racing the driver."""
    cfg, _ = trained
    prompts = _pressure_prompts(cfg)
    plan = FaultPlan(slow_steps={i: 0.001 for i in range(2, 10)})
    eng = make_engine(trained, fault_plan=plan, **PRESSURE)
    streams = {i: [] for i in range(len(prompts))}

    def tap(i):
        return lambda req, tok: streams[i].append(tok)

    reqs = [eng.submit(p, 12, on_token=tap(i))
            for i, p in enumerate(prompts)]
    seen_parked = 0
    for _ in range(60):
        eng.step()
        seen_parked = max(seen_parked, eng.swapped_count)
        if seen_parked:
            break
    assert seen_parked >= 1            # a sequence is parked RIGHT NOW
    eng.run_until_drained()
    for i, (req, p) in enumerate(zip(reqs, prompts)):
        assert req.state == "finished"
        assert len(streams[i]) == 12           # zero dropped tokens
        np.testing.assert_array_equal(
            req.output(), sequential_ref(trained, p, 12))
    s = eng.stats()
    assert s["swapped_slots"] == 0 and s["blocks_used"] == 0
    eng.close()


def test_preempt_policy_selection(trained):
    """pick_victim: "newest" sacrifices the latest admission (least
    work lost), "oldest" the earliest, a callable sees the running
    table and must return one of its slots."""
    from types import SimpleNamespace

    eng = make_engine(trained, preempt=True)
    sched = eng.scheduler
    assert sched.pick_victim() is None         # nothing running
    sched._running = {3: SimpleNamespace(seq=0),
                      1: SimpleNamespace(seq=2),
                      2: SimpleNamespace(seq=1)}
    try:
        assert sched.pick_victim("newest") == 1
        assert sched.pick_victim("oldest") == 3
        assert sched.pick_victim(lambda running: min(running)) == 1
        with pytest.raises(ValueError, match="not a running slot"):
            sched.pick_victim(lambda running: 9)
        with pytest.raises(ValueError, match="unknown preempt policy"):
            sched.pick_victim("fifo")
    finally:
        sched._running = {}
        eng.close()


def test_adopt_blocks_accounting_and_guards(trained):
    """The swap-in allocator path: adopt_blocks claims private blocks
    for a resumed sequence (never consulting the prefix cache), guards
    against occupied slots and over-asks, and free() returns exactly
    the adopted blocks."""
    cfg, _ = trained
    kv = SlotKVCache(cfg, num_slots=2, max_len=16, block_size=4,
                     num_blocks=7)                     # 6 allocatable
    s = kv.alloc()
    kv.map_slot(s, np.arange(1, 10, dtype=np.int32), 12)   # 3 blocks
    assert kv.mapped_block_count(s) == 3
    with pytest.raises(ValueError, match="already has mapped blocks"):
        kv.adopt_blocks(s, 1, 4)
    with pytest.raises(ValueError, match="n_blocks must be >= 1"):
        kv.can_adopt(0)
    assert not kv.can_adopt(kv.blocks_available + 1)
    t = kv.alloc()
    with pytest.raises(ValueError, match="cannot supply"):
        kv.adopt_blocks(t, kv.blocks_available + 1, 4)
    row = kv.adopt_blocks(t, 2, length=6)
    assert kv.mapped_block_count(t) == 2
    assert kv.length(t) == 6
    assert kv.blocks_used == 5
    assert len(set(row[:2]) & set(kv.page_table[s][:3])) == 0
    kv.free(t)
    assert kv.blocks_used == 3


def test_fault_plan_chaos_is_seed_deterministic():
    """Same seed, same storm — the chaos soak replays exactly."""
    a = FaultPlan.chaos(seed=7, steps=200)
    b = FaultPlan.chaos(seed=7, steps=200)
    assert a.step_exceptions == b.step_exceptions
    assert a.page_shortages == b.page_shortages
    assert a.slow_steps == b.slow_steps
    c = FaultPlan.chaos(seed=8, steps=200)
    assert (a.step_exceptions, a.page_shortages, a.slow_steps) \
        != (c.step_exceptions, c.page_shortages, c.slow_steps)
    assert a.summary()["scheduled_shortages"] == len(a.page_shortages)


def test_fault_plan_forced_page_shortage_requeues_not_preempts(trained):
    """A scheduled page shortage makes admission act page-starved: the
    head-of-line request requeues at the queue FRONT (FIFO preserved),
    nothing is admitted that step, and — preemption enabled — a forced
    shortage never evicts a resident (it simulates transient pressure,
    not an evictable sequence)."""
    plan = FaultPlan(page_shortages={0, 1})
    eng = make_engine(trained, preempt=True, fault_plan=plan)
    p = np.asarray([1, 2, 3], np.int32)
    r1 = eng.submit(p, 4)
    r2 = eng.submit(p, 4)
    eng.step()                                 # step 0: denied
    assert plan.denied_steps == 1
    assert eng.scheduler.active_count == 0     # nothing admitted
    assert r1.state == "queued" and r2.state == "queued"
    eng.step()                                 # step 1: denied again
    assert plan.denied_steps == 2
    eng.run_until_drained()
    assert r1.state == "finished" and r2.state == "finished"
    np.testing.assert_array_equal(r1.output(),
                                  sequential_ref(trained, p, 4))
    assert eng.stats()["preemptions"] == 0
    eng.close()


def test_fault_plan_step_exception_fires_exactly_once(trained):
    """The replica-failover trigger: engine.step() raises the scheduled
    InjectedFault AT the scheduled index and never again — the step
    counter advances before the raise, so a supervisor that retries the
    loop proceeds past the fault and the engine completes its work."""
    plan = FaultPlan(step_exceptions={1})
    eng = make_engine(trained, fault_plan=plan)
    p = np.asarray([1, 2, 3], np.int32)
    req = eng.submit(p, 4)
    eng.step()                                 # step 0: clean (admits)
    with pytest.raises(InjectedFault) as ei:
        eng.step()                             # step 1: scheduled fault
    assert ei.value.step == 1
    assert plan.injected_exceptions == 1
    eng.run_until_drained()                    # steps 2..: clean again
    assert plan.injected_exceptions == 1       # fired exactly once
    assert req.state == "finished"
    np.testing.assert_array_equal(req.output(),
                                  sequential_ref(trained, p, 4))
    eng.close()


def test_fault_plan_slow_steps_and_dispatch_delays(trained):
    """Scheduled delays fire through the injectable sleep — once at the
    top of the scheduled engine step, once right before the scheduled
    chunk launch — and the plan's telemetry counts them."""
    naps = []
    plan = FaultPlan(slow_steps={0: 0.025}, slow_dispatches={0: 0.05},
                     sleep=naps.append)
    eng = make_engine(trained, fault_plan=plan)
    p = np.asarray([1, 2, 3], np.int32)
    eng.submit(p, 6)
    eng.run_until_drained()
    assert naps.count(0.025) == 1
    assert naps.count(0.05) == 1
    assert plan.slept_steps == 2
    assert plan.summary()["scheduled_delays"] == 2
    eng.close()


# ---------------------------------------------------------------------------
# request-lifecycle plane (observability PR): disabled no-op pin +
# dispatch split + event log
# ---------------------------------------------------------------------------

def test_lifecycle_plane_disabled_is_noop(trained):
    """Acceptance pin: with no request log installed and
    dispatch_timing off (the defaults), serving is bit-identical to the
    pre-plane behavior — token streams match a fully-instrumented run
    of the same mix, the compile-event sequence is unchanged, and the
    engine's registry footprint is exactly the pre-PR family set (no
    dispatch-split series, no request-log series of any kind)."""
    from paddle_tpu.observability import get_registry
    from paddle_tpu.observability import request_log as rl

    assert rl.get_request_log() is None        # the production default
    rng = np.random.RandomState(11)
    cfg, _ = trained
    prompts = [rng.randint(0, cfg.vocab_size,
                           (3 + i % 4,)).astype(np.int32)
               for i in range(6)]
    eng = make_engine(trained, num_slots=2)
    label = eng.stats()["engine_label"]
    outs = eng.generate(prompts, max_new_tokens=6,
                        temperature=0.7, seed=13)
    events_off = eng.scheduler.compile_events
    snap = get_registry().snapshot()
    # the engine's label appears under EXACTLY the pre-plane families —
    # "zero extra registry series" is a set equality, not an absence
    # check, so a renamed family can't slip through either
    expected = (
        {f"serving_{n}_total" for n in
         ("submitted", "admitted", "completed", "shed", "tokens_out",
          # prefill_chunks is part of the BASE engine surface like the
          # swap counters (monolithic engines publish it at 0); the
          # chunked-prefill KNOB adds zero families beyond this set
          "decode_steps", "prefills", "prefill_chunks", "dispatches",
          "spec_proposed",
          "spec_accepted", "prefix_cache_hits", "prefix_cache_misses",
          "preemptions", "swap_ins")}
        | {f"serving_{n}" for n in
           ("active_slots", "queue_depth", "kv_blocks_total",
            "kv_blocks_used", "kv_blocks_cached", "swapped_slots",
            # mesh + quantization geometry gauges are part of the BASE
            # engine surface (single-chip fp32 engines publish
            # mesh_shards=1, whole-pool per-chip bytes, itemsize 4 and
            # the served weight bytes), not a lifecycle-plane series
            "mesh_shards", "kv_pool_per_chip_bytes",
            "kv_dtype_bytes", "weight_bytes")}
        | {"serving_ttft_seconds", "serving_tpot_seconds",
           "serving_queue_wait_seconds", "serving_tokens_per_dispatch",
           "serving_spec_accepted_run", "serving_swap_out_seconds",
           "serving_swap_in_seconds",
           "serving_prefill_chunk_seconds"})
    labeled = {name for name, fam in snap.items()
               if any(r["labels"].get("engine") == label
                      for r in fam.get("series", []))}
    assert labeled == expected, labeled ^ expected
    eng.close()

    # the fully-instrumented run: request log installed AND the
    # host/device dispatch split on — streams must not move a bit
    with rl.request_logging() as log:
        eng2 = make_engine(trained, num_slots=2, dispatch_timing=True)
        label2 = eng2.stats()["engine_label"]
        outs2 = eng2.generate(prompts, max_new_tokens=6,
                              temperature=0.7, seed=13)
        events_on = eng2.scheduler.compile_events
        snap2 = get_registry().snapshot()
        eng2.close()
    for a, b in zip(outs, outs2):
        np.testing.assert_array_equal(a, b)
    assert events_off == events_on             # zero extra compiles
    # the instrumented run really measured: both split histograms
    # carry one sample per launched dispatch
    for fam in ("serving_dispatch_host_seconds",
                "serving_dispatch_device_seconds"):
        row = next(r for r in snap2[fam]["series"]
                   if r["labels"].get("engine") == label2)
        assert row["count"] > 0, fam
    # and journaled the full lifecycle for every request
    kinds = {e["kind"] for e in log.recent()}
    assert {"submitted", "queued", "admitted", "prefill", "decode",
            "finished"} <= kinds
    assert log.inflight_ids() == []            # everything terminal


def test_dispatch_split_attributes_host_and_device_time(trained):
    """dispatch_timing=True: every collected dispatch lands one sample
    in BOTH split histograms, stats() grows the split columns, and the
    /varz host_overhead_per_dispatch rollup derives the same mean the
    registry sum/count implies."""
    from paddle_tpu.observability import get_registry
    from paddle_tpu.observability.debug_server import _serving_varz

    eng = make_engine(trained, num_slots=2, dispatch_timing=True)
    prompts = [np.asarray([1, 2, 3], np.int32),
               np.asarray([5, 4, 3, 2, 1], np.int32)]
    eng.generate(prompts, max_new_tokens=8)
    label = eng.stats()["engine_label"]
    snap = get_registry().snapshot()
    host = next(r for r in snap["serving_dispatch_host_seconds"]
                ["series"] if r["labels"].get("engine") == label)
    dev = next(r for r in snap["serving_dispatch_device_seconds"]
               ["series"] if r["labels"].get("engine") == label)
    # one sample a dispatch: everything launched was collected by the
    # time generate() returned
    assert host["count"] == dev["count"] == eng.stats()["dispatches"] > 0
    assert host["sum"] > 0 and dev["sum"] >= 0
    varz = _serving_varz(snap)["host_overhead_per_dispatch"][label]
    assert varz["dispatches"] == host["count"]
    assert varz["host_overhead_ms"] == round(
        host["sum"] / host["count"] * 1e3, 3)
    assert varz["host_share"] is not None and 0 < varz["host_share"] <= 1
    # stats() carries the split means alongside the other histograms
    s = eng.stats()
    assert s["mean_dispatch_host"] > 0
    assert s["mean_dispatch_device"] >= 0
    eng.close()


def test_request_log_preemption_timeline(trained):
    """The request log captures a preempted request's full phase
    sequence — submitted/queued/admitted/prefill, preempted and
    swapped_in under page pressure, per-dispatch decode records, and
    the terminal finished event — all correlated on request_id."""
    from paddle_tpu.observability import request_log as rl

    with rl.request_logging() as log:
        eng = make_engine(trained, **PRESSURE)
        prompts = _pressure_prompts(cfg=trained[0])
        reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
        eng.run_until_drained()
        assert eng.stats()["preemptions"] >= 1
        eng.close()
    events = log.recent()
    preempted_ids = {e["request_id"] for e in events
                     if e["kind"] == "preempted"}
    assert preempted_ids                        # pressure really evicted
    rid = sorted(preempted_ids)[0]
    kinds = [e["kind"] for e in events if e["request_id"] == rid]
    for needed in ("submitted", "queued", "admitted", "prefill",
                   "preempted", "swapped_in", "decode", "finished"):
        assert needed in kinds, (needed, kinds)
    # phase order: admission precedes the preemption, the swap-in
    # precedes the finish
    assert kinds.index("admitted") < kinds.index("preempted") \
        < kinds.index("swapped_in") < len(kinds) - 1
    assert kinds[-1] == "finished"
    # every request reached a terminal event and the budget delivered
    assert all(r.state == "finished" and len(r.tokens) == 12
               for r in reqs)

# ---------------------------------------------------------------------------
# cross-replica migration (engine-level halves: MigrationTicket +
# migrate_out/migrate_in)
# ---------------------------------------------------------------------------

def _drive_until_running_with_tokens(eng, req, n=2):
    """Step until `req` has streamed >= n tokens and is still running
    (callers size max_new so the first collects can't finish it)."""
    while len(req.tokens) < n:
        eng.step()
    assert not req.finished


@pytest.mark.parametrize("k", [0, 4])
def test_migrate_stream_identity_greedy_and_seeded(trained, k):
    """The tentpole pin: a stream migrated MID-GENERATION between two
    engines (fence -> ticket -> adopt -> resume) is bit-identical to a
    never-migrated run — greedy AND seeded, with and without
    speculation — and both engines drain to zero pages, zero parked
    sequences. The slot-independent threefry sampler is what makes
    this work: the ticket's key row continues the per-token split
    chain on whatever engine (and slot) the sequence lands."""
    cfg, _ = trained
    p = np.asarray([3, 1, 4, 1, 5], np.int32)
    for temp, seed in ((0.0, 0), (0.8, 3)):
        src = make_engine(trained, speculate_k=k, decode_chunk=4,
                          max_len=48)
        dst = make_engine(trained, speculate_k=k, decode_chunk=4,
                          max_len=48)
        stream = []
        req = src.submit(p, 40, temperature=temp, seed=seed,
                         on_token=lambda r, t: stream.append(t))
        _drive_until_running_with_tokens(src, req)
        ticket = src.migrate_out(req)
        assert ticket.verify()
        assert ticket.emitted == len(stream)
        assert req.state == "migrated"          # detached, never emits
        req2 = dst.migrate_in(ticket,
                              on_token=lambda r, t: stream.append(t))
        src.run_until_drained()
        dst.run_until_drained()
        assert req2.state == "finished"
        if temp == 0.0:
            np.testing.assert_array_equal(
                req2.output(), sequential_ref(trained, p, 40))
        ref_eng = make_engine(trained, speculate_k=k, decode_chunk=4,
                              max_len=48)
        ref_stream = []
        ref_eng.submit(p, 40, temperature=temp, seed=seed,
                       on_token=lambda r, t: ref_stream.append(t))
        ref_eng.run_until_drained()
        assert stream == ref_stream, (k, temp)
        for eng in (src, dst):
            s = eng.stats()
            assert s["blocks_used"] == 0 and s["swapped_slots"] == 0
            assert s["swap_pool_bytes"] == 0
            eng.close()
        ref_eng.close()


def test_migrate_with_prefix_cache_hit_stream_identical(trained):
    """Migration of a sequence whose prompt mapped shared prefix-cache
    blocks: the ticket copies the SHARED block contents into private
    blocks on the target (the target's cache is cold), and the stream
    stays bit-identical to a never-migrated warm run."""
    cfg, _ = trained
    rng = np.random.RandomState(11)
    sys_prompt = rng.randint(0, cfg.vocab_size, (8,)).astype(np.int32)
    tail_a = rng.randint(0, cfg.vocab_size, (3,)).astype(np.int32)
    tail_b = rng.randint(0, cfg.vocab_size, (3,)).astype(np.int32)
    pa = np.concatenate([sys_prompt, tail_a])
    pb = np.concatenate([sys_prompt, tail_b])

    def warm_engine():
        eng = make_engine(trained, num_slots=2, block_size=4,
                          decode_chunk=4, max_len=48,
                          prefill_buckets=(4, 16))
        eng.generate([pa], max_new_tokens=4)    # registers the prefix
        return eng

    src = warm_engine()
    dst = make_engine(trained, num_slots=2, block_size=4,
                      decode_chunk=4, max_len=48, prefill_buckets=(4, 16))
    stream = []
    req = src.submit(pb, 30, temperature=0.7, seed=9,
                     on_token=lambda r, t: stream.append(t))
    _drive_until_running_with_tokens(src, req)
    assert src.kv.prefix_hits > 0               # the hit really happened
    req2 = dst.migrate_in(src.migrate_out(req),
                          on_token=lambda r, t: stream.append(t))
    src.run_until_drained()
    dst.run_until_drained()
    assert req2.state == "finished"
    ref_eng = warm_engine()
    ref_stream = []
    ref_eng.submit(pb, 30, temperature=0.7, seed=9,
                   on_token=lambda r, t: ref_stream.append(t))
    ref_eng.run_until_drained()
    assert stream == ref_stream
    assert src.stats()["blocks_used"] <= src.kv.blocks_cached \
        + src.stats()["blocks_used"]            # shared blocks refcounted
    dst.close(); src.close(); ref_eng.close()


def test_migrate_parked_sequence_from_swap_pool(trained):
    """A PREEMPTED (host-parked) sequence migrates without any fence or
    dispatch — its swap-pool record is already serialized — and resumes
    bit-identically on the target; the source's swap pool shrinks and
    no pages leak on either side."""
    from paddle_tpu.serving import FaultPlan

    cfg, _ = trained
    prompts = _pressure_prompts(cfg)
    plan = FaultPlan(slow_steps={i: 0.001 for i in range(2, 10)})
    tight = make_engine(trained, fault_plan=plan, **PRESSURE)
    roomy = make_engine(trained, num_slots=4, block_size=4,
                        decode_chunk=4)
    streams = {i: [] for i in range(len(prompts))}

    def tap(i):
        return lambda req, tok: streams[i].append(tok)

    reqs = [tight.submit(p, 12, temperature=0.8, seed=3,
                         on_token=tap(i))
            for i, p in enumerate(prompts)]
    for _ in range(60):
        tight.step()
        if tight.swapped_count:
            break
    assert tight.swapped_count >= 1
    parked_req = tight._swapped[0].req
    idx = reqs.index(parked_req)
    before = tight.swapped_count
    ticket = tight.migrate_out(parked_req)
    assert tight.swapped_count == before - 1
    roomy.migrate_in(ticket, on_token=tap(idx))
    tight.run_until_drained()
    roomy.run_until_drained()
    # the whole mix is bit-identical to an unpressured run
    ref = make_engine(trained, num_slots=4, block_size=4,
                      decode_chunk=4)
    ref_streams = {i: [] for i in range(len(prompts))}

    def rtap(i):
        return lambda req, tok: ref_streams[i].append(tok)

    for i, p in enumerate(prompts):
        ref.submit(p, 12, temperature=0.8, seed=3, on_token=rtap(i))
    ref.run_until_drained()
    assert streams == ref_streams
    for eng in (tight, roomy):
        assert eng.stats()["blocks_used"] == 0
        assert eng.swapped_count == 0
        eng.close()
    ref.close()


def test_migrate_out_refuses_during_drain_not_deadlock(trained):
    """Regression (satellite bugfix): migrate_out/migrate_in on a
    DRAINING engine refuse immediately with MigrationError — they must
    never park a sequence nobody will resume (the drain-loop deadlock)
    — and the drain itself still finishes every stream."""
    from paddle_tpu.serving import MigrationError

    src = make_engine(trained, decode_chunk=4, max_len=48)
    peer = make_engine(trained, decode_chunk=4, max_len=48)
    p = np.asarray([1, 2, 3], np.int32)
    req = src.submit(p, 30)
    _drive_until_running_with_tokens(src, req)
    src.begin_drain()
    assert src.draining
    with pytest.raises(MigrationError, match="draining"):
        src.migrate_out(req)
    # the refused sequence is untouched: the drain completes it
    src.run_until_drained()
    assert req.state == "finished" and len(req.tokens) == 30
    np.testing.assert_array_equal(req.output(),
                                  sequential_ref(trained, p, 30))
    # inbound adoption refuses on a draining engine too
    req2 = peer.submit(p, 30)
    _drive_until_running_with_tokens(peer, req2)
    ticket = peer.migrate_out(req2)
    with pytest.raises(MigrationError, match="draining"):
        src.migrate_in(ticket)
    # the ticket survives the refusal: a healthy engine adopts it
    other = make_engine(trained, decode_chunk=4, max_len=48)
    req3 = other.migrate_in(ticket)
    peer.run_until_drained()
    other.run_until_drained()
    np.testing.assert_array_equal(req3.output(),
                                  sequential_ref(trained, p, 30))
    src.close(); peer.close(); other.close()


def test_migration_ticket_integrity_and_compatibility(trained):
    """The ticket's safety rails: a corrupted payload fails the
    checksum, and geometry/speculation mismatches are rejected whole —
    TicketError, nothing mutated on the refusing engine."""
    from paddle_tpu.serving import TicketError

    src = make_engine(trained, decode_chunk=4, max_len=48)
    p = np.asarray([5, 7, 11], np.int32)
    req = src.submit(p, 30)
    _drive_until_running_with_tokens(src, req)
    ticket = src.migrate_out(req)
    assert ticket.version == pt.serving.TICKET_VERSION
    assert ticket.swap_bytes == ticket.payload.nbytes
    # corruption: flip one payload value (via a copy — the extracted
    # payload buffer is read-only) and the checksum catches it
    tampered = ticket.payload.copy()
    tampered[0, 0, 0, 0, 0, 0] += 1.0
    good_payload, ticket.payload = ticket.payload, tampered
    assert not ticket.verify()
    victim = make_engine(trained, decode_chunk=4, max_len=48)
    before = victim.stats()
    with pytest.raises(TicketError, match="checksum"):
        victim.migrate_in(ticket)
    after = victim.stats()
    assert after["swapped_slots"] == before["swapped_slots"] == 0
    ticket.payload = good_payload
    assert ticket.verify()
    # geometry: block size and speculation config must match
    with pytest.raises(TicketError, match="block_size"):
        make_engine(trained, block_size=8, max_len=48).migrate_in(ticket)
    with pytest.raises(TicketError, match="speculation"):
        make_engine(trained, speculate_k=4, max_len=48).migrate_in(ticket)
    # the intact ticket still adopts fine after every rejection
    dst = make_engine(trained, decode_chunk=4, max_len=48)
    req2 = dst.migrate_in(ticket)
    src.run_until_drained()
    dst.run_until_drained()
    np.testing.assert_array_equal(req2.output(),
                                  sequential_ref(trained, p, 30))
    src.close(); dst.close(); victim.close()


def test_migration_request_log_chains_hops(trained):
    """migrate_out/migrate_in land in the request event log with
    replica labels and payload bytes, and the adopting engine's new id
    chains to the source id via rerouted_from — the same link failover
    re-submissions write, so one request stays ONE timeline."""
    from paddle_tpu.observability import request_log as rl

    with rl.request_logging() as log:
        src = make_engine(trained, decode_chunk=4, max_len=48)
        dst = make_engine(trained, decode_chunk=4, max_len=48)
        p = np.asarray([2, 7, 1], np.int32)
        req = src.submit(p, 30)
        _drive_until_running_with_tokens(src, req)
        ticket = src.migrate_out(req)
        req2 = dst.migrate_in(ticket)
        src.run_until_drained()
        dst.run_until_drained()
        src.close(); dst.close()
    events = log.recent()
    out = next(e for e in events if e["kind"] == "migrate_out")
    assert out["request_id"] == ticket.request_id
    assert out["replica"] == src.metrics.engine_label
    assert out["bytes"] == ticket.swap_bytes and out["bytes"] > 0
    assert out["phase"] == "running"
    inn = next(e for e in events if e["kind"] == "migrate_in")
    assert inn["request_id"] == req2.request_id
    assert inn["rerouted_from"] == ticket.request_id
    assert inn["replica"] == dst.metrics.engine_label
    # the superseded id left the in-flight set at adoption, and the
    # new id went terminal at finish
    assert log.inflight_ids() == []


# ---------------------------------------------------------------------------
# multi-chip tensor-parallel serving (ServingConfig(mesh_shape=(tp,)))
# ---------------------------------------------------------------------------
#
# The quick lane pins the tp=2 contract end to end (streams, compile
# discipline, per-chip gauges, config validation, ticket shard
# rejection); the full mesh matrix — mesh 1/2/4 x greedy/seeded x
# speculate_k {0,4} x preempt-resume x migration — runs in the
# multichip lane (tools/run_multichip_tests.sh, `-m multichip`,
# auto-marked slow) under the same 8-device virtual mesh the
# MULTICHIP_r0x benches use.

def _mesh_mix_streams(trained, mesh, speculate_k=0, max_new=8,
                      close=True, **kw):
    """The shared mesh workload: four prompts, alternating greedy and
    seeded sampling, on a fresh engine at the given mesh. Returns
    (streams, stats, compile events, engine) — the engine is closed
    (and returned closed) unless close=False, for callers that must
    read its registry series before retirement."""
    cfg, _ = trained
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (3, 5, 7, 4)]
    eng = make_engine(trained, mesh_shape=mesh, speculate_k=speculate_k,
                      **kw)
    reqs = [eng.submit(p, max_new_tokens=max_new,
                       temperature=0.8 if i % 2 else 0.0, seed=i)
            for i, p in enumerate(prompts)]
    eng.run_until_drained()
    out = [tuple(r.tokens) for r in reqs]
    stats = eng.stats()
    events = eng.scheduler.compile_events
    if close:
        eng.close()
    return out, stats, events, eng


def test_mesh_tp2_streams_compile_discipline_and_gauges(trained):
    """Quick-lane mesh pin: a mesh_shape=(2,) engine emits the SAME
    greedy and seeded streams as the single-chip engine, with the
    sharded chunk loop traced ONCE and compile count still
    O(buckets)+admit; occupancy/stats report the per-chip split
    (hbm_per_chip_bytes = pool_bytes / 2, mesh_shape (2,)) and the
    serving_mesh_shards / serving_kv_pool_per_chip_bytes gauges + the
    /varz mesh rollup carry the same numbers off the scrape path."""
    from paddle_tpu.observability import get_registry
    from paddle_tpu.observability.debug_server import _serving_varz

    base, bstats, _, _ = _mesh_mix_streams(trained, None)
    assert bstats["mesh_shape"] == (1,)
    assert bstats["hbm_per_chip_bytes"] == bstats["pool_bytes"]

    # close=False: the registry asserts below must read the labeled
    # series before close() retires them
    got, s, events, eng = _mesh_mix_streams(trained, (2,), close=False)
    assert got == base, "tp=2 streams diverged from single-chip"
    # compile discipline carries over EXACTLY: one executable per
    # prefill bucket + ONE sharded fused chunk loop + one admit sampler
    assert events.count("decode_chunk") == 1
    assert len(events) <= 2 + 2   # len(buckets)=2 + chunk + admit
    # per-chip-aware occupancy on the sharded pool
    assert s["mesh_shape"] == (2,)
    assert s["hbm_per_chip_bytes"] * 2 == s["pool_bytes"]
    # registry truth BEFORE close() retires the labeled series
    label = s["engine_label"]
    snap = get_registry().snapshot()
    for fam, want in (("serving_mesh_shards", 2),
                      ("serving_kv_pool_per_chip_bytes",
                       s["hbm_per_chip_bytes"])):
        row = next(r for r in snap[fam]["series"]
                   if r["labels"].get("engine") == label)
        assert row["value"] == want, fam
    assert _serving_varz(snap)["mesh"][label] == {
        "mesh_shards": 2,
        "kv_pool_per_chip_bytes": s["hbm_per_chip_bytes"],
        "kv_dtype_bytes": 4,                # fp32 pool on this engine
        "weight_bytes": s["weight_bytes"]}
    eng.close()


def test_mesh_config_validation(trained):
    """Bad mesh geometry fails LOUDLY at construction, before any
    compile: heads not divisible by tp, more chips than devices, and a
    non-(tp,) mesh tuple are all ValueErrors."""
    with pytest.raises(ValueError, match="heads"):
        make_engine(trained, mesh_shape=(3,))      # 4 heads % 3 != 0
    with pytest.raises(ValueError, match="devices"):
        make_engine(trained, mesh_shape=(16,))     # 8 visible
    with pytest.raises(ValueError, match="1-tuple"):
        make_engine(trained, mesh_shape=(2, 2))


def test_migration_ticket_rejects_shard_layout_not_crash(trained):
    """The corrupted-shard case: a ticket whose payload carries a
    PER-CHIP head shard (or a mangled rank) instead of the assembled
    full-head layout is rejected whole with TicketError — a typed
    refusal naming the mesh geometry, never an IndexError/scatter
    crash — and the unmolested ticket still adopts fine afterwards."""
    from paddle_tpu.serving import TicketError

    cfg, _ = trained
    src = make_engine(trained, max_len=48)
    dst = make_engine(trained, max_len=48)
    p = np.asarray([3, 1, 4, 1, 5], np.int32)
    req = src.submit(p, 40, temperature=0.8, seed=3)
    _drive_until_running_with_tokens(src, req)
    ticket = src.migrate_out(req)
    assert ticket.mesh_shape == (1,)

    half = ticket.payload[:, :, :, : cfg.heads // 2]
    ticket.payload = half
    ticket.checksum = ticket._digest()      # "valid" shard-layout ticket
    with pytest.raises(TicketError, match="head geometry"):
        dst.migrate_in(ticket)
    ticket.payload = half.reshape(half.shape[0], -1)
    ticket.checksum = ticket._digest()
    with pytest.raises(TicketError, match="rank"):
        dst.migrate_in(ticket)
    # nothing was mutated on the refusing engine: restore and adopt
    full = np.zeros(half.shape[:3] + (cfg.heads,) + half.shape[4:],
                    half.dtype)
    ticket.payload = full
    ticket.checksum = ticket._digest()
    req2 = dst.migrate_in(ticket)
    dst.run_until_drained()
    assert req2.state == "finished"
    src.run_until_drained()
    src.close(); dst.close()


@pytest.mark.multichip
@pytest.mark.parametrize("k", [0, 4])
@pytest.mark.parametrize("tp", [2, 4])
def test_mesh_token_identity_matrix(trained, tp, k):
    """The acceptance matrix: mesh (2,) and (4,) streams are identical
    to mesh=(1,) — greedy AND seeded in the same batch, speculation on
    and off — with the compile-counter pin that the sharded chunk loop
    traced ONCE at every point."""
    base, _, _, _ = _mesh_mix_streams(trained, None, speculate_k=k,
                                      max_new=12)
    got, s, events, _ = _mesh_mix_streams(trained, (tp,), speculate_k=k,
                                          max_new=12)
    assert got == base, (tp, k)
    assert events.count("decode_chunk") == 1
    assert s["mesh_shape"] == (tp,)
    assert s["hbm_per_chip_bytes"] * tp == s["pool_bytes"]


@pytest.mark.multichip
@pytest.mark.parametrize("tp", [2, 4])
def test_mesh_preempt_resume_identity(trained, tp):
    """Preempt/resume on a tensor-parallel engine: the over-subscribed
    arena forces host-swap preemptions — the payload round-trips
    host <-> sharded arena — and every stream is still identical to
    sequential gpt_generate; the drain leaks nothing."""
    cfg, _ = trained
    prompts = _pressure_prompts(cfg)
    eng = make_engine(trained, mesh_shape=(tp,), **PRESSURE)
    outs = eng.generate(prompts, max_new_tokens=12)
    s = eng.stats()
    assert s["preemptions"] >= 1, "arena not tight enough to preempt"
    for p, o in zip(prompts, outs):
        np.testing.assert_array_equal(o, sequential_ref(trained, p, 12))
    assert s["swapped_slots"] == 0 and s["blocks_used"] == 0
    assert s["swap_pool_bytes"] == 0
    eng.close()


@pytest.mark.multichip
@pytest.mark.parametrize("src_tp,dst_tp", [(2, 2), (2, 1), (1, 4)])
def test_mesh_migration_matrix(trained, src_tp, dst_tp):
    """Mesh-crossing migration: a mid-generation handoff lands
    tp->same-tp, tp->single-chip, and single-chip->bigger-tp with
    streams identical to a never-migrated run — the ticket's
    device_get-assembled full-head payload is what makes the geometry
    portable — and the mesh_shape annotation journals the source."""

    def mesh(tp):
        return (tp,) if tp > 1 else None

    p = np.asarray([3, 1, 4, 1, 5], np.int32)
    for temp, seed in ((0.0, 0), (0.8, 3)):
        src = make_engine(trained, mesh_shape=mesh(src_tp), max_len=48)
        dst = make_engine(trained, mesh_shape=mesh(dst_tp), max_len=48)
        stream = []
        req = src.submit(p, 40, temperature=temp, seed=seed,
                         on_token=lambda r, t: stream.append(t))
        _drive_until_running_with_tokens(src, req)
        ticket = src.migrate_out(req)
        assert ticket.mesh_shape == (src_tp,)
        assert ticket.describe()["mesh_shape"] == [src_tp]
        assert ticket.compatible(dst)
        req2 = dst.migrate_in(ticket,
                              on_token=lambda r, t: stream.append(t))
        src.run_until_drained()
        dst.run_until_drained()
        assert req2.state == "finished"
        ref_eng = make_engine(trained, max_len=48)
        ref_stream = []
        ref_eng.submit(p, 40, temperature=temp, seed=seed,
                       on_token=lambda r, t: ref_stream.append(t))
        ref_eng.run_until_drained()
        assert stream == ref_stream, (src_tp, dst_tp, temp)
        for eng in (src, dst, ref_eng):
            s = eng.stats()
            assert s["blocks_used"] == 0 and s["swapped_slots"] == 0
            eng.close()


# ---------------------------------------------------------------------------
# quantized serving (ServingConfig(weight_dtype="int8", kv_dtype="int8"))
# ---------------------------------------------------------------------------
#
# The contract is DETERMINISM against itself plus a pinned accuracy
# budget against fp32, never fp32 bit-identity: a quantized engine's
# streams are bit-identical across fresh engines, chunk sizes,
# preempt/resume, migration, and (multichip lane) mesh shapes, while
# divergence from the fp32 engine stays inside the greedy-agreement /
# logit-delta budget the bench measures (tools/bench_serving
# --quantize; the budget itself is pinned in test_tooling).

QUANT = dict(weight_dtype="int8", kv_dtype="int8")


def _quant_mix_streams(trained, max_new=8, **kw):
    """Four greedy prompts on a fresh engine; returns (streams, stats,
    compile events). Greedy because the agreement budget is defined on
    argmax streams; seeded determinism rides the same threefry pins as
    fp32 (the sampler never sees the arena dtype)."""
    cfg, _ = trained
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (3, 5, 7, 4)]
    eng = make_engine(trained, **kw)
    reqs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    eng.run_until_drained()
    out = [tuple(r.tokens) for r in reqs]
    stats = eng.stats()
    events = eng.scheduler.compile_events
    eng.close()
    return out, stats, events


def test_quantize_params_weight_roundtrip(trained):
    """quantize_params: every matmul weight becomes int8 with one f32
    scale per OUTPUT channel, dequant error is bounded by half a
    quantization step per entry, and embeddings/LNs/biases are the
    exact fp32 originals (same objects, untouched)."""
    cfg, params = trained
    qp = gd.quantize_params(params, cfg)
    assert qp["wte"] is params["wte"] and qp["wpe"] is params["wpe"]
    assert qp["lnf"] is params["lnf"]
    for blk, qblk in zip(params["blocks"], qp["blocks"]):
        assert qblk["ln1"] is blk["ln1"] and qblk["ln2"] is blk["ln2"]
        for nm in ("q", "k", "v", "out", "mlp1", "mlp2"):
            w = np.asarray(blk[nm]["w"], np.float32)
            wq = np.asarray(qblk[nm]["w_q"])
            ws = np.asarray(qblk[nm]["w_s"])
            assert wq.dtype == np.int8 and ws.dtype == np.float32
            assert wq.shape == w.shape and ws.shape == (w.shape[1],)
            assert qblk[nm]["b"] is blk[nm]["b"]
            # per-channel abs-max: |w - w_q*s| <= s/2 everywhere, and
            # the max-magnitude entry of each channel hits +-127
            err = np.abs(w - wq.astype(np.float32) * ws)
            assert (err <= ws / 2 + 1e-7).all()
            assert (np.abs(wq).max(axis=0)[ws > 0] == 127).all()


def test_quantized_engine_determinism_agreement_and_compile_bound(trained):
    """The quantized tentpole's quick-lane pins: (1) two fresh
    int8-w+int8-kv engines emit bit-identical streams, (2) chunk size
    does not move a quantized stream (the fused-loop invariance fp32
    pins, re-pinned on the dequant path), (3) greedy agreement with
    the fp32 engine meets the >=0.99 budget on the mix, and (4) the
    compile discipline is unchanged: O(buckets) prefills + ONE chunk
    loop + admit."""
    base, _, _ = _quant_mix_streams(trained)
    got, s, events = _quant_mix_streams(trained, **QUANT)
    got2, _, _ = _quant_mix_streams(trained, **QUANT)
    assert got == got2, "quantized engine not deterministic"
    chunk1, _, _ = _quant_mix_streams(trained, decode_chunk=1, **QUANT)
    assert got == chunk1, "quantized stream moved with chunk size"
    pairs = [(a, b) for qs, rs in zip(got, base) for a, b in zip(qs, rs)]
    agree = sum(a == b for a, b in pairs) / len(pairs)
    assert agree >= 0.99, f"greedy agreement {agree} below budget"
    assert events.count("decode_chunk") == 1
    assert len(events) <= len((4, 8)) + 2, events
    assert s["kv_dtype"] == "int8" and s["weight_dtype"] == "int8"


def test_quantized_preempt_resume_identity(trained):
    """Lifecycle corner: preempt -> host-swap -> resume of an int8-KV
    sequence (payload + scale plane round-trip host memory) is
    bit-identical to the never-preempted QUANTIZED stream, and the
    drain leaks neither blocks nor swap-pool bytes."""
    cfg, _ = trained
    prompts = _pressure_prompts(cfg)
    ref = make_engine(trained, num_slots=4, decode_chunk=4,
                      block_size=4, **QUANT)
    refs = [tuple(o.tolist()) for o in
            ref.generate(prompts, max_new_tokens=12)]
    ref.close()
    tight = make_engine(trained, **PRESSURE, **QUANT)
    outs = [tuple(o.tolist()) for o in
            tight.generate(prompts, max_new_tokens=12)]
    s = tight.stats()
    assert s["preemptions"] >= 1, "arena not tight enough to preempt"
    assert outs == refs
    assert s["swapped_slots"] == 0 and s["blocks_used"] == 0
    assert s["swap_pool_bytes"] == 0
    tight.close()


def test_quantized_migration_identity_and_dtype_rejects(trained):
    """Lifecycle corner: an int8-KV sequence migrates int8->int8 with
    the stream bit-identical to a never-migrated quantized run; a
    dtype-mismatched handoff (fp32 ticket -> int8 engine and int8 ->
    fp32) rejects whole with TicketError — a typed refusal, never a
    scatter crash — and a tampered scale plane fails the checksum."""
    from paddle_tpu.serving import TicketError

    p = np.asarray([3, 1, 4, 1, 5], np.int32)
    src = make_engine(trained, max_len=48, **QUANT)
    dst = make_engine(trained, max_len=48, **QUANT)
    stream = []
    req = src.submit(p, 30, on_token=lambda r, t: stream.append(t))
    _drive_until_running_with_tokens(src, req)
    ticket = src.migrate_out(req)
    assert ticket.payload.dtype == np.int8
    assert ticket.scales is not None
    assert ticket.scales.dtype == np.float32
    assert ticket.describe()["kv_dtype"] == "int8"
    assert ticket.swap_bytes == ticket.payload.nbytes \
        + ticket.scales.nbytes
    # scale-plane corruption is caught by the checksum (a flipped
    # scale would silently rescale a whole row: sequence state)
    good = ticket.scales
    tampered = good.copy()
    tampered[0, 0, 0, 0, 0] += 1.0
    ticket.scales = tampered
    assert not ticket.verify()
    with pytest.raises(TicketError, match="checksum"):
        dst.migrate_in(ticket)
    ticket.scales = good
    assert ticket.verify()
    req2 = dst.migrate_in(ticket, on_token=lambda r, t: stream.append(t))
    src.run_until_drained()
    dst.run_until_drained()
    assert req2.state == "finished"
    ref = make_engine(trained, max_len=48, **QUANT)
    ref_stream = []
    ref.submit(p, 30, on_token=lambda r, t: ref_stream.append(t))
    ref.run_until_drained()
    assert stream == ref_stream
    # dtype mismatches reject whole, both directions
    f32 = make_engine(trained, max_len=48)
    req3 = f32.submit(p, 30)
    _drive_until_running_with_tokens(f32, req3)
    t32 = f32.migrate_out(req3)
    with pytest.raises(TicketError, match="dtype"):
        make_engine(trained, max_len=48, **QUANT).migrate_in(t32)
    q_req = ref.submit(p, 30)
    _drive_until_running_with_tokens(ref, q_req)
    tq = ref.migrate_out(q_req)
    with pytest.raises(TicketError, match="dtype"):
        f32.migrate_in(tq)
    f32.run_until_drained()
    ref.run_until_drained()
    src.close(); dst.close(); f32.close(); ref.close()


def test_quantized_prefix_cache_cow_scale_consistency(trained):
    """Lifecycle corner: COW prefix sharing of QUANTIZED blocks — a
    second request hash-hitting the first's prompt blocks maps the
    same int8 rows AND the same scale-plane entries, so its stream is
    bit-identical to a cold (cache-off) quantized run of the same
    request. Divergent tails stay isolated exactly as in fp32."""
    cfg, _ = trained
    sys_prompt = np.arange(1, 9, dtype=np.int32)         # two full blocks
    tails = [np.asarray([13, 17], np.int32), np.asarray([19, 23], np.int32)]
    prompts = [np.concatenate([sys_prompt, t]) for t in tails]

    def run(prefix_cache):
        eng = make_engine(trained, block_size=4, prefix_cache=prefix_cache,
                          prefill_buckets=(4, 16), **QUANT)
        reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
        eng.run_until_drained()
        s = eng.stats()
        eng.close()
        return [tuple(r.tokens) for r in reqs], s

    cold, s_cold = run(False)
    warm, s_warm = run(True)
    assert s_cold["prefix_hits"] == 0
    assert s_warm["prefix_hits"] > 0, "mix never hit the prefix cache"
    assert warm == cold, "shared quantized blocks changed a stream"


def test_quantized_spec_stream_identity(trained):
    """speculate_k > 0 on an int8-KV arena (the verify kernel's
    dequant path): streams bit-identical to the quantized
    speculate_k=0 engine, with acceptance actually happening."""
    spec, s, events = _quant_mix_streams(trained, max_new=12,
                                         decode_chunk=4, speculate_k=4,
                                         **QUANT)
    base, _, _ = _quant_mix_streams(trained, max_new=12, decode_chunk=4,
                                    **QUANT)
    assert spec == base, "speculative quantized stream diverged"
    assert events.count("decode_chunk") == 1
    assert s["spec_proposed"] > 0


def test_quantized_config_validation(trained):
    """Unknown dtype strings raise at construction with a clear
    message (no silent fp32 fallback), the SlotKVCache rejects them
    too, and the engine's gates key on the features the served model
    DECLARES (serving.model.require_features) — strip the verify pass
    from the GPT model's features and kv_dtype x speculate_k must
    refuse."""
    cfg, _ = trained
    with pytest.raises(ValueError, match="weight_dtype"):
        make_engine(trained, weight_dtype="int4")
    with pytest.raises(ValueError, match="kv_dtype"):
        make_engine(trained, kv_dtype="fp8")
    with pytest.raises(ValueError, match="kv_dtype"):
        SlotKVCache(cfg, 2, 32, kv_dtype="int4")
    model = gd.GPT_SERVING_MODEL
    covered = model.features
    try:
        model.features = covered - {"speculation"}
        with pytest.raises(ValueError, match="verify"):
            make_engine(trained, speculate_k=2, **QUANT)
        # without speculation the verify kernel is never entered, so
        # the reduced coverage still serves
        eng = make_engine(trained, **QUANT)
        eng.close()
    finally:
        model.features = covered


def test_quantized_byte_accounting_and_gauges(trained):
    """Satellite pin: pool_bytes derives from the ACTUAL arena
    itemsize plus the scale plane — int8 data bytes + f32 scales, a
    dtype-blind fp32 formula would overstate ~4x — occupancy/stats
    carry kv_dtype/weight_dtype, and the serving_kv_dtype_bytes /
    serving_weight_bytes gauges + the /varz mesh rollup expose the
    same numbers off the scrape path."""
    from paddle_tpu.observability import get_registry
    from paddle_tpu.observability.debug_server import _serving_varz

    cfg, params = trained
    eng = make_engine(trained, **QUANT)
    kv = eng.kv
    heads, hd = cfg.heads, cfg.hidden // cfg.heads
    data = cfg.layers * 2 * kv.num_blocks * heads * kv.block_size * hd
    scales = cfg.layers * 2 * kv.num_blocks * heads * kv.block_size
    assert kv.pool_bytes == data * 1 + scales * 4
    s = eng.stats()
    assert s["kv_dtype"] == "int8" and s["weight_dtype"] == "int8"
    assert s["hbm_per_chip_bytes"] == kv.pool_bytes   # single chip
    # served weight bytes: int8 matmul weights + f32 scales/bias/
    # embeddings/LNs — must match the actual pytree
    import jax
    assert s["weight_bytes"] == sum(
        leaf.nbytes for leaf in
        jax.tree_util.tree_leaves(eng.scheduler.params))
    f32 = make_engine(trained)
    sf = f32.stats()
    assert sf["kv_dtype"] == "float32"
    assert sf["weight_dtype"] == "float32"
    assert sf["pool_bytes"] > s["pool_bytes"] * 2     # the capacity win
    assert sf["weight_bytes"] > s["weight_bytes"] * 2
    label = s["engine_label"]
    snap = get_registry().snapshot()
    for fam, want in (("serving_kv_dtype_bytes", 1),
                      ("serving_weight_bytes", s["weight_bytes"])):
        row = next(r for r in snap[fam]["series"]
                   if r["labels"].get("engine") == label)
        assert row["value"] == want, fam
    mesh_row = _serving_varz(snap)["mesh"][label]
    assert mesh_row["kv_dtype_bytes"] == 1
    assert mesh_row["weight_bytes"] == s["weight_bytes"]
    eng.close(); f32.close()


@pytest.mark.multichip
@pytest.mark.parametrize("tp", [2, 4])
def test_quantized_mesh_identity(trained, tp):
    """Multichip lane: the quantized engine's mesh self-identity — a
    mesh (tp,) int8-w+int8-kv engine emits bit-identical streams to
    the single-chip quantized engine (the int8 tensors + scales shard
    on the same Megatron axes, the scale plane alongside the arena's
    heads), with the sharded chunk loop traced once and the per-chip
    gauges splitting the dtype-aware pool bytes exactly."""
    base, _, _ = _quant_mix_streams(trained, max_new=12, **QUANT)
    got, s, events = _quant_mix_streams(trained, max_new=12,
                                        mesh_shape=(tp,), **QUANT)
    assert got == base, f"quantized tp={tp} streams diverged"
    assert events.count("decode_chunk") == 1
    assert s["kv_dtype"] == "int8"
    assert s["hbm_per_chip_bytes"] * tp == s["pool_bytes"]


@pytest.mark.multichip
@pytest.mark.parametrize("src_tp,dst_tp", [(2, 2), (2, 1)])
def test_quantized_mesh_migration_identity(trained, src_tp, dst_tp):
    """Multichip lane: tp->tp and tp->single migration of an int8-KV
    sequence — the ticket's device_get-assembled FULL-HEAD payload and
    scale plane land on either geometry with the stream bit-identical
    to a never-migrated quantized run."""

    def mesh(tp):
        return (tp,) if tp > 1 else None

    p = np.asarray([3, 1, 4, 1, 5], np.int32)
    src = make_engine(trained, mesh_shape=mesh(src_tp), max_len=48,
                      **QUANT)
    dst = make_engine(trained, mesh_shape=mesh(dst_tp), max_len=48,
                      **QUANT)
    stream = []
    req = src.submit(p, 30, on_token=lambda r, t: stream.append(t))
    _drive_until_running_with_tokens(src, req)
    ticket = src.migrate_out(req)
    assert ticket.payload.dtype == np.int8
    assert ticket.scales is not None
    assert ticket.compatible(dst)
    req2 = dst.migrate_in(ticket, on_token=lambda r, t: stream.append(t))
    src.run_until_drained()
    dst.run_until_drained()
    assert req2.state == "finished"
    ref = make_engine(trained, max_len=48, **QUANT)
    ref_stream = []
    ref.submit(p, 30, on_token=lambda r, t: ref_stream.append(t))
    ref.run_until_drained()
    assert stream == ref_stream, (src_tp, dst_tp)
    src.close(); dst.close(); ref.close()


# ---------------------------------------------------------------------------
# chunked prefill (ServingConfig(prefill_chunk=N))
# ---------------------------------------------------------------------------
#
# The tentpole contract: splitting a prompt's suffix prefill into
# budget-bounded chunk dispatches interleaved with decode changes WHEN
# tokens arrive (no monolithic dispatch stalls co-batched streams),
# never WHICH — streams are pinned identical to prefill_chunk=None
# across greedy/seeded x speculate_k x kv_dtype x preempt/resume (and
# mesh, in the multichip lane), with the executable family growing by
# at most O(prefill buckets).


def _chunked_mix_streams(trained, prefill_chunk, max_new=6, **kw):
    """Shared chunked-prefill workload: varied prompt lengths spanning
    several chunk boundaries, alternating greedy and seeded sampling,
    on a fresh engine. Returns (streams, stats, compile events)."""
    cfg, _ = trained
    rng = np.random.RandomState(21)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (3, 7, 15, 14, 6, 11)]
    eng = make_engine(trained, num_slots=3, prefill_buckets=(4, 8, 16),
                      prefill_chunk=prefill_chunk, **kw)
    reqs = [eng.submit(p, max_new_tokens=max_new,
                       temperature=0.8 if i % 2 else 0.0, seed=i)
            for i, p in enumerate(prompts)]
    eng.run_until_drained()
    out = [tuple(r.tokens) for r in reqs]
    stats = eng.stats()
    events = eng.scheduler.compile_events
    eng.close()
    return out, stats, events


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
@pytest.mark.parametrize("k", [0, 4])
def test_chunked_prefill_stream_identity_matrix(trained, k, kv_dtype):
    """The acceptance matrix (single-chip half): prefill_chunk=4
    streams are bit-identical to prefill_chunk=None — greedy AND
    seeded in the same batch, speculation on/off, fp32 AND quantized
    KV blocks — while the chunked engine's executables come from the
    CHUNK buckets only (the monolithic prefill family never traces)
    and the counter stays O(prefill buckets)+admit+1 chunk loop."""
    base, bstats, bevents = _chunked_mix_streams(
        trained, None, speculate_k=k, kv_dtype=kv_dtype)
    got, s, events = _chunked_mix_streams(
        trained, 4, speculate_k=k, kv_dtype=kv_dtype)
    assert got == base, (k, kv_dtype)
    # monolithic engine: no chunk executables, no chunk dispatches
    assert not [e for e in bevents if e.startswith("prefill_chunk")]
    assert bstats["prefill_chunks"] == 0
    # chunked engine: prefill flows through the chunk family ONLY,
    # every shape a bucket <= the chunk budget, decode chunk traced once
    assert not [e for e in events if e.startswith("prefill:")]
    chunk_shapes = {e for e in events if e.startswith("prefill_chunk")}
    assert chunk_shapes <= {"prefill_chunk:L4"}, events
    assert events.count("decode_chunk") == 1
    assert len(events) <= len((4, 8, 16)) + 2, events
    assert s["prefill_chunks"] > 0
    assert s["completed"] == 6


def test_prefill_attention_counts_cold_warm_where_they_belong(
        trained, monkeypatch):
    """stats()["prefill_attention"]: a cold prompt, a prefix hit and
    the chunks of a chunked prompt, counted by the host under the
    attention each dispatch runs. Then the same with the flash forward
    engaged as on the chip (the verdict forced for the 16 bucket, the
    kernel interpreted): cold prompts count as cold_flash, the hit goes
    through the cond's gather branch, and the greedy streams are the
    sequential path's."""
    rng = np.random.RandomState(30)
    cfg, _ = trained
    p = rng.randint(0, cfg.vocab_size, (10,)).astype(np.int32)
    short = rng.randint(0, cfg.vocab_size, (3,)).astype(np.int32)
    sizes = dict(prefill_buckets=(4, 16), block_size=4)

    def counts(eng):
        s = eng.stats()["prefill_attention"]
        return s["path"], (s["cold_flash"], s["cold_gather"], s["warm"])

    def serve():
        eng = make_engine(trained, **sizes)
        assert counts(eng)[1] == (0, 0, 0)
        (cold,) = eng.generate([p], max_new_tokens=6)
        first = counts(eng)
        (hit,) = eng.generate([p], max_new_tokens=6)      # 2 blocks shared
        eng.generate([short], max_new_tokens=2)           # the 4 bucket
        after = counts(eng)
        eng.close()
        np.testing.assert_array_equal(cold, sequential_ref(trained, p, 6))
        np.testing.assert_array_equal(hit, cold)
        return first, after

    assert serve() == (("gather", (0, 1, 0)), ("gather", (0, 2, 1)))
    # chunks of 4: the first starts at 0 and is cold, the rest are warm
    eng = make_engine(trained, prefill_chunk=4, prefix_cache=False, **sizes)
    eng.generate([p], max_new_tokens=6)
    assert counts(eng) == ("gather", (0, 1, 2))
    eng.close()

    monkeypatch.setattr(
        gd, "prefill_attention_path",
        lambda arena, bucket, con=None: "flash" if bucket == 16 else "gather")
    assert serve() == (("flash", (1, 0, 0)), ("flash", (1, 1, 1)))
    # the tiles the cold flash prefills walked: a 16-row bucket is one
    # tile a head, whatever its 10 real rows (ops/flash_attention)
    eng = make_engine(trained, **sizes)
    eng.generate([p], max_new_tokens=2)
    s = eng.stats()["prefill_attention"]
    assert (s["cold_flash"], s["tiles_visited"], s["tiles_in_bucket"]) \
        == (1, 1, 1)
    eng.close()


def test_chunked_prefill_mid_batch_long_prompt_does_not_stall_streams(
        trained):
    """Behavioral half of the tentpole: a long prompt admitted while
    short streams are decoding runs its prefill as multiple chunk
    dispatches (registry-counted) interleaved with decode — the short
    streams keep emitting between the long prompt's admission and its
    first token — and every stream still matches sequential
    gpt_generate."""
    cfg, _ = trained
    rng = np.random.RandomState(5)
    shorts = [rng.randint(0, cfg.vocab_size, (3,)).astype(np.int32)
              for _ in range(2)]
    long_p = rng.randint(0, cfg.vocab_size, (16,)).astype(np.int32)
    eng = make_engine(trained, num_slots=3, prefill_buckets=(4, 8, 16),
                      max_len=32, prefill_chunk=4, decode_chunk=1)
    sreqs = [eng.submit(p, max_new_tokens=10) for p in shorts]
    while any(len(r.tokens) < 2 for r in sreqs):
        eng.step()
    counts = sum(len(r.tokens) for r in sreqs)
    lreq = eng.submit(long_p, max_new_tokens=4)
    # drive while the long prompt is mid-prefill: the shorts must make
    # progress BEFORE its first token lands (no monolithic stall)
    while not lreq.tokens:
        eng.step()
        assert eng.scheduler.prefilling_count <= 1
    assert sum(len(r.tokens) for r in sreqs) > counts, \
        "short streams stalled across the long prompt's prefill"
    eng.run_until_drained()
    for r in sreqs:
        np.testing.assert_array_equal(
            r.output(), sequential_ref(trained, r.prompt, 10))
    np.testing.assert_array_equal(
        lreq.output(), sequential_ref(trained, long_p, 4))
    # 16 suffix tokens at budget 4 = 4 chunk dispatches for the long
    # prompt alone; the engine counter saw every one
    assert eng.stats()["prefill_chunks"] >= 4
    eng.close()


@pytest.mark.parametrize("k", [0, 2])
def test_chunked_prefill_preempt_resume_identity(trained, k):
    """Chunked prefill composes with host-swap preemption: the
    over-subscribed PRESSURE arena forces preemptions on a chunked
    engine and every stream (greedy and seeded, with and without
    speculation) is bit-identical to an unpressured chunked run; the
    drain leaks nothing."""
    cfg, _ = trained
    prompts = _pressure_prompts(cfg)
    tight = make_engine(trained, speculate_k=k, prefill_chunk=4,
                        **PRESSURE)
    t_reqs = [tight.submit(p, max_new_tokens=12,
                           temperature=0.7 if i % 2 else 0.0, seed=i)
              for i, p in enumerate(prompts)]
    tight.run_until_drained()
    assert tight.stats()["preemptions"] >= 1
    loose = make_engine(trained, speculate_k=k, prefill_chunk=4,
                        num_slots=4, block_size=4, decode_chunk=4)
    l_reqs = [loose.submit(p, max_new_tokens=12,
                           temperature=0.7 if i % 2 else 0.0, seed=i)
              for i, p in enumerate(prompts)]
    loose.run_until_drained()
    assert loose.stats()["preemptions"] == 0
    assert [r.tokens for r in t_reqs] == [r.tokens for r in l_reqs]
    s = tight.stats()
    assert s["swapped_slots"] == 0 and s["blocks_used"] == 0
    tight.close(); loose.close()


def test_chunked_prefill_shared_prefix_admitted_mid_prefill(trained):
    """Deferred prefix-cache registration: a second request sharing a
    long prefix is admitted WHILE the first is still mid-chunked-
    prefill. It may only hash-hit blocks whose filling chunk is
    already enqueued (register_prefix's frontier), so both streams
    stay bit-identical to sequential gpt_generate — a hit on an
    unfilled block would read zeros and corrupt the second stream."""
    cfg, _ = trained
    rng = np.random.RandomState(9)
    sys_prompt = rng.randint(0, cfg.vocab_size, (12,)).astype(np.int32)
    p1 = np.concatenate(
        [sys_prompt, rng.randint(0, 97, (3,))]).astype(np.int32)
    p2 = np.concatenate(
        [sys_prompt, rng.randint(0, 97, (3,))]).astype(np.int32)
    eng = make_engine(trained, num_slots=2, prefill_buckets=(4, 8, 16),
                      max_len=32, block_size=4, prefill_chunk=4)
    r1 = eng.submit(p1, max_new_tokens=6)
    eng.step()                         # first chunk dispatched only
    assert eng.scheduler.prefilling_count == 1
    r2 = eng.submit(p2, max_new_tokens=6)
    eng.run_until_drained()
    np.testing.assert_array_equal(
        r1.output(), sequential_ref(trained, p1, 6))
    np.testing.assert_array_equal(
        r2.output(), sequential_ref(trained, p2, 6))
    # blocks the first admission had already filled were shared in
    assert eng.kv.prefix_hits >= 1
    # nothing left pending after the drain
    assert not eng.kv._pending_reg
    eng.close()


def test_mid_prefill_cancel_frees_all_pages(trained):
    """Cancel of a mid-chunked-prefill sequence releases the slot
    in-graph (page row to scratch) and frees EVERY mapped page —
    prefix hits included — with its unpublished prefix digests
    dropped; nothing leaks and the engine keeps serving."""
    cfg, _ = trained
    rng = np.random.RandomState(3)
    long_p = rng.randint(0, cfg.vocab_size, (16,)).astype(np.int32)
    eng = make_engine(trained, num_slots=2, prefill_buckets=(4, 8, 16),
                      max_len=32, block_size=4, prefill_chunk=4)
    req = eng.submit(long_p, max_new_tokens=6)
    eng.step()
    assert eng.scheduler.prefilling_count == 1
    assert eng.kv.blocks_used > 0
    assert eng.cancel(req)
    eng.step()                         # deferred cancel applies
    assert eng.scheduler.prefilling_count == 0
    assert eng.kv.blocks_used == 0
    assert eng.kv.free_count == 2
    assert not eng.kv._pending_reg     # unpublished digests dropped
    assert req.state == "cancelled" and req.tokens == []
    # the engine still serves cleanly after the aborted prefill
    out = eng.generate([long_p], max_new_tokens=4)[0]
    np.testing.assert_array_equal(out, sequential_ref(trained, long_p, 4))
    eng.close()


def test_mid_prefill_migration_refused_not_victim(trained):
    """Mid-prefill sequences hand off safely or not at all: migrate_out
    REFUSES with a typed MigrationError while the fill cursor is live
    (never a corrupt ticket), the preemption victim picker never
    chooses a mid-prefill slot, and the same request migrates normally
    once its first token lands — bit-identical on the target."""
    from paddle_tpu.serving import MigrationError

    cfg, _ = trained
    rng = np.random.RandomState(13)
    long_p = rng.randint(0, cfg.vocab_size, (16,)).astype(np.int32)
    eng = make_engine(trained, num_slots=2, prefill_buckets=(4, 8, 16),
                      max_len=32, prefill_chunk=4, decode_chunk=2)
    req = eng.submit(long_p, max_new_tokens=12)
    eng.step()
    assert eng.scheduler.prefilling_count == 1
    with pytest.raises(MigrationError, match="mid-prefill"):
        eng.migrate_out(req)
    # the refusal left the sequence exactly where it was (still
    # prefilling, still holding its pages) and it is never a victim
    assert eng.scheduler.prefilling_count == 1
    assert eng.scheduler.pick_victim() is None
    while len(req.tokens) < 2:
        eng.step()
    ticket = eng.migrate_out(req)      # now ticketable
    dst = make_engine(trained, num_slots=2, prefill_buckets=(4, 8, 16),
                      max_len=32, prefill_chunk=4, decode_chunk=2)
    req2 = dst.migrate_in(ticket)
    dst.run_until_drained()
    assert req2.state == "finished"
    full = np.concatenate([long_p, np.asarray(req2.tokens, np.int32)])
    np.testing.assert_array_equal(
        full, sequential_ref(trained, long_p, 12))
    eng.run_until_drained()
    assert eng.kv.blocks_used == 0
    eng.close(); dst.close()


def test_chunked_prefill_request_log_and_metrics(trained):
    """Observability satellites: each chunk journals a `prefill` event
    carrying chunk_index/budget, serving_summary renders the
    PREFILL(xn) annotation and per-chain chunk count, the
    serving_prefill_chunks_total counter and
    serving_prefill_chunk_seconds histogram carry one entry per
    dispatched chunk (retired on close()), and the /varz serving
    rollup derives prefill_chunks_per_admission from the same
    series."""
    import sys as _sys, os as _os
    _sys.path.insert(0, _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        "tools"))
    import serving_summary
    from paddle_tpu.observability import get_registry
    from paddle_tpu.observability import request_log as rl
    from paddle_tpu.observability.debug_server import _serving_varz

    cfg, _ = trained
    rng = np.random.RandomState(31)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (14, 3)]
    with rl.request_logging() as log:
        eng = make_engine(trained, num_slots=2,
                          prefill_buckets=(4, 8, 16), max_len=32,
                          prefill_chunk=4)
        reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
        eng.run_until_drained()
        s = eng.stats()
        label = s["engine_label"]
        snap = get_registry().snapshot()
        eng.close()
    # per-chunk journal: the 14-token prompt ran 4 chunks, each with
    # its index and the tick budget
    long_rid = reqs[0].request_id
    chunk_evs = [e for e in log.recent() if e["kind"] == "prefill"
                 and e["request_id"] == long_rid]
    assert [e["chunk_index"] for e in chunk_evs] == [0, 1, 2, 3]
    assert all(e["budget"] == 4 for e in chunk_evs)
    assert sum(e["suffix_len"] for e in chunk_evs) == 14
    # serving_summary: one row per chain with the annotation + count
    rows = serving_summary.summarize(log.recent())
    row = next(r for r in rows if r["request_id"] == long_rid)
    assert row["prefill_chunks"] == 4
    assert "PREFILL(x4)" in row["annotations"]
    short_row = next(r for r in rows
                     if r["request_id"] == reqs[1].request_id)
    assert short_row["prefill_chunks"] == 1      # one chunk, no banner
    assert not [a for a in short_row["annotations"]
                if a.startswith("PREFILL")]
    # registry truth: counter == dispatched chunks == histogram count
    total = s["prefill_chunks"]
    assert total >= 5                   # 4 + 1
    ctr = next(r for r in snap["serving_prefill_chunks_total"]["series"]
               if r["labels"].get("engine") == label)
    assert ctr["value"] == total
    hist = next(
        r for r in snap["serving_prefill_chunk_seconds"]["series"]
        if r["labels"].get("engine") == label)
    assert hist["count"] == total and hist["sum"] > 0
    assert s["mean_prefill_chunk"] > 0
    # /varz rollup: chunks per admission off the same scrape
    varz = _serving_varz(snap)["prefill"][label]
    assert varz["prefill_chunks"] == total
    assert varz["admitted"] == 2
    assert varz["prefill_chunks_per_admission"] == round(total / 2, 4)
    # close() retired the labeled series
    snap2 = get_registry().snapshot()
    assert not any(
        r["labels"].get("engine") == label
        for r in snap2.get("serving_prefill_chunks_total",
                           {}).get("series", []))


def test_requeue_reservation_counts_prefix_hits(trained):
    """Bugfix regression: with a sequence parked in the swap pool, the
    head-of-line page reservation must charge an admission only for
    the blocks it would ACTUALLY consume from the available supply —
    fresh pages plus LRU hits it would incref out of the evictable
    pool; hits on a RUNNING sequence's referenced blocks are free.
    A prompt sharing a running sequence's prefix in the near-full
    window (pages cover reserved + consumed but not reserved + full
    prompt) used to over-reserve by its whole hit depth and requeue
    instead of admitting. The window arises mid-burst when an earlier
    admission preempts a victim and a later shared-prefix request
    must fit the remaining pages, so the check is probed directly at
    the exact arena state, then the engine is drained normally
    (parked victim resumed, every stream intact)."""
    import types

    cfg, _ = trained
    rng = np.random.RandomState(17)
    long_p = rng.randint(0, cfg.vocab_size, (16,)).astype(np.int32)
    # block_size 4: long prompt + 4 new = 5 blocks, first 3 shareable
    eng = make_engine(trained, num_slots=3, prefill_buckets=(4, 8, 16),
                      max_len=32, block_size=4, kv_blocks=16,
                      decode_chunk=2, preempt=True)
    # a RUNNING holder keeps the shared prefix blocks referenced —
    # hits on them consume nothing from the available supply (budget
    # sized so it is still mid-stream at the probe below)
    holder = eng.submit(long_p, max_new_tokens=16)
    while not holder.tokens:
        eng.step()
    # park one sequence, the reservation the admission must respect
    vic = eng.submit(rng.randint(0, 97, (5,)).astype(np.int32),
                     max_new_tokens=12)
    while not vic.tokens:
        eng.step()
    eng._fence()
    assert holder.state == "running"   # prefix blocks still referenced
    victim_slot = eng.scheduler.pick_victim()     # newest = vic
    sw = eng.scheduler.swap_out(victim_slot)
    eng._swapped.append(sw)
    avail = eng.kv.blocks_available
    reserved = sum(s.n_blocks for s in eng._swapped)
    full = eng.kv.blocks_for(long_p.size + 4)
    need = eng.kv.blocks_needed(long_p, long_p.size + 4)
    assert need < full                   # live-referenced hits are free
    assert reserved + need <= avail < reserved + full, \
        (reserved, need, full, avail)    # exactly the regression window
    probe = types.SimpleNamespace(prompt=long_p, max_new_tokens=4)
    assert eng._admission_feasible(probe, 0), \
        "hit-aware reservation refused a shared-prefix prompt that fits"
    # normal service resumes cleanly: the parked victim swaps back in
    # with strict priority and finishes its full budget, and the
    # shared-prefix prompt serves bit-identically
    req = eng.submit(long_p, max_new_tokens=4)
    eng.run_until_drained()
    assert vic.state == "finished" and len(vic.tokens) == 12
    assert holder.state == "finished" and req.state == "finished"
    np.testing.assert_array_equal(
        req.output(), sequential_ref(trained, long_p, 4))
    assert eng.stats()["blocks_used"] == 0
    # everything retired: the prefix blocks fell to the LRU pool, and
    # claiming LRU hits consumes evictable supply — blocks_needed now
    # charges them like fresh pages (the under-count guard)
    assert eng.kv.blocks_needed(long_p, long_p.size + 4) == full
    eng.close()


@pytest.mark.multichip
def test_chunked_prefill_mesh_tp2_identity(trained):
    """Quick-lane mesh pin for chunked prefill: a mesh_shape=(2,)
    engine with prefill_chunk on emits the same greedy and seeded
    streams as the single-chip MONOLITHIC engine — the chunk kernel's
    GSPMD sharding composes with the budget discipline — and its
    executables still come from the chunk buckets only."""
    base, _, _ = _chunked_mix_streams(trained, None)
    got, s, events = _chunked_mix_streams(trained, 4, mesh_shape=(2,))
    assert got == base
    assert not [e for e in events if e.startswith("prefill:")]
    assert events.count("decode_chunk") == 1
    assert s["mesh_shape"] == (2,)
    assert s["prefill_chunks"] > 0
