"""The engine's decode loop (paddle_tpu.serving.decode_loop).

A model supplies a step; the scan, the sampler's cadence, the finish rule
and the carry are the engine's. Pinned here: (a) a toy model with no loop
of its own is served token-identically to its sequential loop; (b) the
finish rule has one source, so the loop, the admission program and the
host agree; (c) the named carry flattens to the leaves the positional
tuple had, which is what keeps every executable's signature."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.serving import ServingConfig, ServingEngine, sampling
from paddle_tpu.serving.decode_loop import (DecodeCarry, decode_chunk,
                                            finish_rule)
from paddle_tpu.serving.model import CacheSpec, ServingModel
from paddle_tpu.serving.scheduler import PREFILL_PENDING, _Running

V = 23


def _lookup(table, tokens, pos):
    """The toy's whole forward pass: a row of `table` by (token, pos)."""
    return table[(tokens * 7 + pos) % table.shape[0]]


class _ToyModel(ServingModel):
    """A table lookup for prefill and for decode_step: no cache rows, no
    features, no loop."""
    name = "toy"

    def max_positions(self, cfg):
        return cfg.max_pos

    def cache_spec(self, cfg):
        return CacheSpec(1, 1, 1)

    def activation_dtype(self, params):
        return jnp.float32

    def decode_attention_path(self, arena, arena_constraint=None):
        return "none"

    def prefill(self, params, cfg, tokens, pfx_len, real_len, arena, pages,
                adapters=None, adapter_id=None):
        last = tokens[0, real_len - 1][None]
        return _lookup(params, last, pfx_len + real_len - 1), arena, None

    def decode_step(self, params, cfg, tokens, arena, pt, ts, done, **kw):
        return _lookup(params, tokens, ts), arena, None


class _ToyConfig:
    max_pos = 64

    def serving_model(self):
        return _TOY


_TOY = _ToyModel()


@pytest.fixture(scope="module")
def table():
    return jnp.asarray(np.random.RandomState(5).standard_normal((V, V)),
                       jnp.float32)


def toy_engine(table, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("prefill_buckets", (4, 8))
    kw.setdefault("max_len", 48)
    kw.setdefault("block_size", 4)
    kw.setdefault("decode_chunk", 3)
    return ServingEngine(table, _ToyConfig(), ServingConfig(**kw))


def sequential(table, sampler, prompt, max_new, eos_id=None,
               temperature=0.0, seed=0):
    """The toy's own loop, one token at a time: the lookup, the engine's
    per-row sampler on a key chain of one split a token, the finish."""
    tok, pos, out = int(prompt[-1]), len(prompt) - 1, []
    key = sampling.sample_key(np.int32(seed))
    while True:
        tok, key = sampler(key, _lookup(table, jnp.int32(tok), pos),
                           jnp.float32(temperature))
        tok = int(tok)
        out.append(tok)
        pos += 1
        if len(out) >= max_new or tok == eos_id:
            return out


def test_toy_model_without_a_loop_is_served_like_its_sequential_loop(table):
    """Five requests through two slots (queueing, slot reuse), chunks
    that end mid-stream: greedy and seeded, budget finishes of 1, 2 and
    7 tokens and an EOS finish inside a chunk."""
    eng = toy_engine(table)
    sampler = eng.scheduler._sample_row
    rng = np.random.RandomState(6)
    prompts = [rng.randint(0, V, (n,)).astype(np.int32)
               for n in (3, 5, 2, 7, 4)]
    greedy = sequential(table, sampler, prompts[0], 7)
    asks = [dict(max_new_tokens=7), dict(max_new_tokens=7, temperature=0.8,
                                         seed=11),
            dict(max_new_tokens=1), dict(max_new_tokens=2, temperature=1.3,
                                         seed=4),
            # stops at the third greedy token of prompt 0, if no earlier
            # token is the same id
            dict(max_new_tokens=7, eos_id=greedy[2])]
    prompts[4] = prompts[0]
    reqs = [eng.submit(p, **ask) for p, ask in zip(prompts, asks)]
    eng.run_until_drained()
    for prompt, ask, req in zip(prompts, asks, reqs):
        want = sequential(table, sampler, prompt, ask["max_new_tokens"],
                          ask.get("eos_id"), ask.get("temperature", 0.0),
                          ask.get("seed", 0))
        assert req.tokens == want, (ask, req.tokens, want)
    assert len(reqs[4].tokens) == greedy.index(greedy[2]) + 1 < 7
    s = eng.stats()
    assert s["completed"] == 5 and s["free_slots"] == 2
    assert s["model"] == "toy" and s["decode_attention"] == "none"
    assert eng.scheduler.compile_events.count("decode_chunk") == 1
    eng.close()


# (max_new, eos_id, the token a step emits): budget 1, 2 and 5; the eos
# hit, missed, absent, and id 0 (the carry's "no eos" is -1, never 0)
FINISH_CASES = [(1, None, 3), (1, 3, 3), (2, None, 3), (2, 3, 3), (2, 4, 3),
                (5, 3, 3), (5, 0, 0), (5, None, 0), (5, 9, 0)]


@pytest.mark.parametrize("max_new,eos_id,token", FINISH_CASES)
def test_finish_rule_has_one_source(max_new, eos_id, token):
    """Whether `token` ends a sequence as its FIRST token (the admission
    program and the host's _sample_first) and as its SECOND (the loop's
    scan and the host's block walk): every verdict is
    decode_loop.finish_rule's, and it is the rule written out here."""
    always = jnp.zeros((V, V), jnp.float32).at[:, token].set(1.0)
    sched = toy_engine(always, num_slots=1, decode_chunk=1,
                       overlap=False).scheduler
    sched.on_first_tokens = sched._first_events.extend  # driven by hand
    prompt = np.asarray([1, 2], np.int32)

    first_ends = max_new <= 1 or token == eos_id
    assert sched.admit("r", prompt, max_new, eos_id=eos_id) \
        is PREFILL_PENDING
    assert bool(sched._state.done[0]) == first_ends
    # the first token is read by the tick; with overlap off a live slot's
    # second token comes in the same tick, behind it
    block = sched.step()
    (ev,) = sched.drain_first_tokens()
    assert ev.token == token and ev.finished == first_ends
    assert finish_rule(token, -1 if eos_id is None else eos_id,
                       max_new - 1) == first_ends

    second_ends = max_new <= 2 or token == eos_id
    host = _Running("r", pos=2, max_new=max_new, eos_id=eos_id, live_from=0)
    host.produced = 2
    assert host.finished_by(token) == second_ends
    if first_ends:
        assert block == []
    else:
        (ev,) = block
        assert ev.token == token and ev.finished == second_ends
        assert bool(sched._state.done[0]) == second_ends
    # and the scan alone, from the carry an admission leaves
    carry = DecodeCarry(
        tokens=jnp.asarray([token], jnp.int32), ts=jnp.asarray([2]),
        done=jnp.asarray([False]),
        remaining=jnp.asarray([max_new - 1], jnp.int32),
        temps=jnp.zeros((1,)),
        eos_ids=jnp.asarray([-1 if eos_id is None else eos_id], jnp.int32))
    _, _, _, after, _ = decode_chunk(
        _TOY, always, None, jnp.zeros((1, 1, 2, 1, 4, 1)),
        jnp.zeros((1, 2), jnp.int32), jnp.zeros((1, 2), jnp.uint32), carry, 1)
    assert bool(after.done[0]) == second_ends


@pytest.mark.parametrize("spec,adapters", [(False, False), (True, False),
                                           (False, True), (True, True)])
def test_decode_carry_flattens_to_the_old_tuples_leaves(spec, adapters):
    """tokens, ts, done, remaining, temps, eos_ids, then the drafter's
    prev and table if speculating, then the adapter rows LAST if there
    is a pool: the order of the positional carry the executables were
    compiled against, with nothing for a field that is off."""
    marked = DecodeCarry(0, 1, 2, 3, 4, 5, (6, 7) if spec else None,
                         8 if adapters else None)
    want = [0, 1, 2, 3, 4, 5] + ([6, 7] if spec else []) \
        + ([8] if adapters else [])
    assert jax.tree_util.tree_leaves(marked) == want
    idle = DecodeCarry.idle(3, 16 if spec else None, adapters)
    kinds = [(leaf.shape, str(leaf.dtype))
             for leaf in jax.tree_util.tree_leaves(idle)]
    i32, row = ((3,), "int32"), ((3, 17), "int32")
    assert kinds == [i32, i32, ((3,), "bool"), i32, ((3,), "float32"), i32] \
        + ([i32, row] if spec else []) + ([i32] if adapters else [])
    assert bool(idle.done.all()) and int(idle.eos_ids[0]) == -1
    # a round trip through a jitted program keeps the fields
    back = jax.jit(lambda c: c._replace(ts=c.ts + 1))(idle)
    assert isinstance(back, DecodeCarry) and (back.spec is None) != spec
    assert (back.adapter_rows is None) != adapters
