"""An admission does not wait for its first token (serving/scheduler.py).

The prefill and its sampler are queued, the slot runs with its first
token PENDING, and the host reads the token only after the tick's launch
(`_resolve_first`, one fetch a tick; ahead of the launch only when no
dispatch is in flight). Pinned here on the tiny GPT, on the
CPU: the streams are the reference's, a tick queues everything before it
waits, a request that its first token finishes is retired at the fetch,
and cancel, the fence and chunked prefill meet a pending token rightly."""

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import gpt_decode as gd
from paddle_tpu.models.gpt import GPTConfig, gpt_lm_program
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.scheduler import PREFILL_PENDING


@pytest.fixture(scope="module")
def trained():
    """(cfg, params) of a randomly initialised tiny GPT."""
    cfg = GPTConfig(vocab_size=97, hidden=32, layers=2, heads=4,
                    max_pos=64, dropout=0.0, attn_impl="xla")
    _, startup, _ = gpt_lm_program(cfg, 8, is_test=True)
    exe = pt.Executor()
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup)
        params = gd.collect_gpt_params(scope, cfg)
    return cfg, params


def make_engine(trained, **kw):
    cfg, params = trained
    kw.setdefault("num_slots", 3)
    kw.setdefault("max_queue", 16)
    kw.setdefault("prefill_buckets", (4, 8))
    kw.setdefault("max_len", 32)
    return ServingEngine(params, cfg, ServingConfig(**kw))


def reference(trained, prompt, max_new):
    """The generated tokens of the sequential path."""
    cfg, params = trained
    out = gd.gpt_generate(params, cfg, np.asarray(prompt)[None], max_new)[0]
    return [int(t) for t in out[len(prompt):]]


def prompts_of(trained, *lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, trained[0].vocab_size, (n,)).astype(np.int32)
            for n in lengths]


def bare_scheduler(eng):
    """The engine's scheduler driven by hand: first tokens wait for
    drain_first_tokens() (the sink before the engine took it)."""
    sched = eng.scheduler
    sched.on_first_tokens = sched._first_events.extend
    return sched


def record_calls(sched, monkeypatch):
    """Every program the scheduler dispatches, by family, with "wait"
    where it starts to read pending first tokens and "device_get" at
    every fetch, in order."""
    log = []
    jit_call, resolve, device_get = \
        sched._jit_call, sched._resolve_first, jax.device_get

    def logged_call(family, fn, *args):
        log.append(family)
        return jit_call(family, fn, *args)

    def logged_resolve():
        if sched._pending_first:
            log.append("wait")
        return resolve()

    def logged_get(x):
        log.append("device_get")
        return device_get(x)

    sched._jit_call = logged_call
    sched._resolve_first = logged_resolve
    monkeypatch.setattr(jax, "device_get", logged_get)
    return log


SEEDED = dict(temperature=0.8, seed=7)


@pytest.fixture(scope="module")
def seeded_alone(trained):
    """A seeded request's stream served alone, a step a dispatch, nothing
    ever in flight: what every placement of it must reproduce."""
    eng = make_engine(trained, num_slots=1, decode_chunk=1, overlap=False)
    (prompt,) = prompts_of(trained, 6, seed=3)
    req = eng.submit(prompt, max_new_tokens=9, **SEEDED)
    eng.run_until_drained()
    eng.close()
    return prompt, list(req.tokens)


@pytest.mark.parametrize("overlap", [True, False], ids=["overlap", "serial"])
@pytest.mark.parametrize("decode_chunk", [1, 8])
def test_streams_are_the_reference(trained, seeded_alone, decode_chunk,
                                   overlap):
    """Admitted into an empty engine or behind a dispatch in flight, a
    greedy stream is gpt_generate's and a seeded one is the one the
    request has alone."""
    eng = make_engine(trained, decode_chunk=decode_chunk, overlap=overlap)
    p_first, p_late = prompts_of(trained, 5, 3)
    p_seeded, alone = seeded_alone
    first = eng.submit(p_first, max_new_tokens=12)      # an empty engine
    eng.step()
    eng.step()
    assert eng.scheduler.inflight_count == int(overlap)
    late = eng.submit(p_late, max_new_tokens=7)         # behind a dispatch
    seeded = eng.submit(p_seeded, max_new_tokens=9, **SEEDED)
    eng.run_until_drained()
    assert first.tokens == reference(trained, p_first, 12)
    assert late.tokens == reference(trained, p_late, 7)
    assert seeded.tokens == alone
    s = eng.stats()
    assert s["first_tokens"] == 3 and s["first_token_waits"] == 2
    # behind the launch only where a dispatch was in flight: an empty
    # engine (and a serial one always) reads its first token first
    assert s["first_tokens_behind_launch"] == (2 if overlap else 0)
    assert s["blocks_used"] == 0
    eng.close()


def test_a_tick_queues_everything_before_it_waits(trained, monkeypatch):
    """With a chunk in flight, a tick that admits two requests dispatches
    both prefills and both samplers, then the next chunk, and only then
    fetches: ONE fetch for both first tokens, then the oldest block's."""
    eng = make_engine(trained, decode_chunk=4)
    a, b, c = prompts_of(trained, 5, 3, 7)
    eng.submit(a, max_new_tokens=16)
    eng.step()
    eng.step()
    assert eng.scheduler.inflight_count == 1
    log = record_calls(eng.scheduler, monkeypatch)
    got = []
    late = [eng.submit(p, max_new_tokens=6,
                       on_token=lambda r, t: got.append((r, t)))
            for p in (b, c)]
    eng.step()
    assert log == ["prefill:L4", "admit_sample", "prefill:L8",
                   "admit_sample", "decode_chunk", "wait", "device_get",
                   "device_get"]
    # both first tokens are out, in admission order, ahead of the block's
    assert [r for r, _ in got[:2]] == late
    assert [t for _, t in got[:2]] == [reference(trained, p, 1)[0]
                                       for p in (b, c)]
    eng.run_until_drained()
    for req, p in zip(late, (b, c)):
        assert req.tokens == reference(trained, p, 6)
    s = eng.stats()
    assert (s["first_token_waits"], s["first_tokens"]) == (2, 3)
    eng.close()


@pytest.mark.parametrize("how", ["eos", "budget"])
def test_a_request_its_first_token_finishes(trained, how):
    """First token = eos, or a budget of one: one event, finished; slot
    and pages freed at the fetch; nothing of the chunk it rode frozen is
    emitted; the slot serves its next request rightly."""
    eng = make_engine(trained, num_slots=2, decode_chunk=4)
    a, b, c = prompts_of(trained, 5, 3, 6, seed=1)
    long = eng.submit(a, max_new_tokens=14)
    eng.step()
    eng.step()
    got = []
    first = reference(trained, b, 1)[0]
    kw = dict(max_new_tokens=1) if how == "budget" \
        else dict(max_new_tokens=8, eos_id=first)
    short = eng.submit(b, on_token=lambda r, t: got.append(t), **kw)
    eng.step()
    assert got == [first] and short.state == "finished"
    assert eng.kv.free_count == 1 and eng.scheduler.active_count == 1
    nxt = eng.submit(c, max_new_tokens=5)       # into the freed slot
    eng.run_until_drained()
    assert got == [first] and short.tokens == [first]
    assert nxt.tokens == reference(trained, c, 5)
    assert long.tokens == reference(trained, a, 14)
    assert eng.stats()["blocks_used"] == 0
    eng.close()


def test_an_empty_engine_reads_the_first_token_before_it_launches(
        trained, monkeypatch):
    """No dispatch in flight: the sampler is all the device has to do,
    so the launch's host time is not put in front of the first token."""
    eng = make_engine(trained, decode_chunk=4)
    log = record_calls(eng.scheduler, monkeypatch)
    (p,) = prompts_of(trained, 4)
    req = eng.submit(p, max_new_tokens=6)
    assert eng.step() == 1
    assert log == ["prefill:L4", "admit_sample", "wait", "device_get",
                   "decode_chunk"]
    eng.run_until_drained()
    assert req.tokens == reference(trained, p, 6)
    assert eng.stats()["first_tokens_behind_launch"] == 0
    eng.close()


def test_a_budget_of_one_alone_launches_nothing(trained):
    eng = make_engine(trained)
    (p,) = prompts_of(trained, 4)
    req = eng.submit(p, max_new_tokens=1)
    assert eng.step() == 1
    assert req.tokens == reference(trained, p, 1) and req.finished
    s = eng.stats()
    assert s["dispatches"] == 0 and s["first_tokens_behind_launch"] == 0
    assert (s["first_token_waits"], s["first_tokens"]) == (1, 1)
    eng.close()


def test_cancel_with_a_pending_first_token_emits_nothing(trained):
    eng = make_engine(trained)
    sched = bare_scheduler(eng)
    p, q = prompts_of(trained, 4, 5)
    assert sched.admit("gone", p, 6) is PREFILL_PENDING
    assert sched.admit("stays", q, 6) is PREFILL_PENDING
    assert sched.cancel("gone")
    assert eng.kv.free_count == 2
    assert sched.step() == []
    (event,) = sched.drain_first_tokens()
    assert event.request == "stays"
    assert event.token == reference(trained, q, 1)[0]
    assert sched.first_tokens == 2              # fetched, one dropped
    assert [e.request for e in sched.sync()] == ["stays"] * 5
    # a cancelled admission alone is still read, so nothing stays pending
    assert sched.admit("gone too", p, 6) is PREFILL_PENDING
    assert sched.cancel("gone too")
    assert sched.step() == [] and sched.drain_first_tokens() == []
    assert not sched._pending_first and eng.kv.blocks_used == 0
    eng.close()


def test_sync_reads_pending_first_tokens_first(trained):
    """sync() hands out a pending first token ahead of every block, and
    swap_out refuses to run past one."""
    eng = make_engine(trained, decode_chunk=4)
    sched = bare_scheduler(eng)
    p, q = prompts_of(trained, 4, 5)
    sched.admit("old", p, 9)
    sched.step()
    assert [e.request for e in sched.drain_first_tokens()] == ["old"]
    assert sched.inflight_count == 1
    sched.admit("new", q, 9)
    with pytest.raises(RuntimeError, match="sync"):
        sched.swap_out(0)
    events = sched.sync()
    assert events[0].request == "new"
    assert events[0].token == reference(trained, q, 1)[0]
    assert [e.request for e in events[1:]] == ["old"] * 4
    assert not sched._pending_first and sched.inflight_count == 0
    eng.close()


def test_the_fence_emits_a_pending_first_token_first(trained):
    """The engine's fence (before swap_out and migration) finds an
    admission of its own tick half done: its first token goes out before
    any block's, and counts towards no dispatch."""
    eng = make_engine(trained, decode_chunk=4)
    got = []
    p, q = prompts_of(trained, 4, 5)
    old = eng.submit(p, max_new_tokens=9,
                     on_token=lambda r, t: got.append("old"))
    eng.step()
    eng.step()
    del got[:]
    new = eng.submit(q, max_new_tokens=9,
                     on_token=lambda r, t: got.append("new"))
    eng._admit_tick(eng._step_no)
    assert got == [] and new.state == "running"
    steps = eng.metrics.decode_steps
    eng._fence()
    assert got == ["new"] + ["old"] * 4
    assert eng.metrics.decode_steps == steps + 1
    eng.run_until_drained()
    assert old.tokens == reference(trained, p, 9)
    assert new.tokens == reference(trained, q, 9)
    eng.close()


def test_chunked_prefill_ends_on_the_same_path(trained, monkeypatch):
    """The last chunk of a chunked prefill is sampled by the same body:
    its first token pending, read behind the launch."""
    eng = make_engine(trained, decode_chunk=4, prefill_chunk=4)
    a, b = prompts_of(trained, 3, 7)
    running = eng.submit(a, max_new_tokens=12)
    eng.step()
    eng.step()
    log = record_calls(eng.scheduler, monkeypatch)
    chunked = eng.submit(b, max_new_tokens=6)
    eng.step()                                   # first chunk of two
    assert "admit_sample" not in log and "wait" not in log
    del log[:]
    eng.step()
    assert log[:4] == ["prefill_chunk:L4", "admit_sample", "decode_chunk",
                       "wait"]
    assert len(chunked.tokens) == 1
    eng.run_until_drained()
    assert chunked.tokens == reference(trained, b, 6)
    assert running.tokens == reference(trained, a, 12)
    assert eng.stats()["first_tokens"] == 2
    eng.close()


def test_two_admissions_of_a_bucket_in_one_tick(trained):
    """No admission waits for its prefill, so each stages its prompt in
    a buffer of its own: two prompts of ONE bucket admitted in one tick
    are both served rightly."""
    eng = make_engine(trained, decode_chunk=4)
    ps = prompts_of(trained, 3, 4, 3, seed=5)
    reqs = [eng.submit(p, max_new_tokens=5) for p in ps]
    eng.step()
    assert eng.stats()["first_tokens"] == 3
    assert eng.stats()["first_token_waits"] == 1
    eng.run_until_drained()
    for req, p in zip(reqs, ps):
        assert req.tokens == reference(trained, p, 5)
    eng.close()
