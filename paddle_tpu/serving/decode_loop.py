"""The fused decode loop: the engine's, one for every served model.

A model supplies ONE decode step over the pool (`ServingModel.decode_step`)
and, for speculation, one multi-position verify pass (`verify`). What is
wrapped around it is written here once: the `lax.scan` of `chunk`
iterations, the per-slot sampling call and its key cadence, the frozen-slot
rule, the EOS/budget finish rule (`finish_rule`, which the scan, the
speculative acceptance, the admission sampler and the host's block walk all
call), the n-gram drafter with its exact-match acceptance, and the named
decode carry (`DecodeCarry`) every jitted program of the scheduler threads.

Imports no model and, at module level, no jax.
"""

from __future__ import annotations

from typing import Any, NamedTuple

__all__ = ["DecodeCarry", "finish_rule", "decode_chunk", "spec_ngram_seed",
           "SAMPLE_SCOPE", "FINISH_SCOPE"]

# The loop's own stages in a device trace (`jax.named_scope`, metadata
# only): the sampling call with its key split, and the finish rule with the
# carry's update. The model's step between them stays under the model's own
# scopes; the scheduler's admission sampler uses the same two names.
SAMPLE_SCOPE = "loop/sample"
FINISH_SCOPE = "loop/finish"


class DecodeCarry(NamedTuple):
    """The device-resident per-slot decode state, all (S,) unless said:
    the token each slot feeds next, its absolute position, whether it
    rides along frozen (finished, free or cancelled), the tokens it may
    still emit, its temperature and its eos id (-1 = none; sampled ids
    are >= 0, so -1 never matches). `spec` is the drafter's (prev (S,)
    previous committed token, table (S, T+1) trigram table, see
    `spec_ngram_seed`) under speculation and `adapter_rows` the per-slot
    adapter POOL ROW (0 = the base identity) with an adapter pool; each
    is None when off and then flattens to nothing, so the carry's leaves
    are exactly the fields in use, in this order."""
    tokens: Any
    ts: Any
    done: Any
    remaining: Any
    temps: Any
    eos_ids: Any
    spec: Any = None
    adapter_rows: Any = None

    @classmethod
    def idle(cls, num_slots, speculate_ngram=None, adapters=False):
        """Every slot frozen and empty: the carry before any admission.
        `speculate_ngram` sizes the drafter table (its extra column is
        the trash lane masked scatter writes land in; -1 marks "no
        prediction"); None leaves speculation off."""
        import jax.numpy as jnp
        s = int(num_slots)
        return cls(
            tokens=jnp.zeros((s,), jnp.int32),
            ts=jnp.zeros((s,), jnp.int32),
            done=jnp.ones((s,), bool),
            remaining=jnp.zeros((s,), jnp.int32),
            temps=jnp.zeros((s,), jnp.float32),
            eos_ids=jnp.full((s,), -1, jnp.int32),
            spec=None if speculate_ngram is None else (
                jnp.zeros((s,), jnp.int32),
                jnp.full((s, int(speculate_ngram) + 1), -1, jnp.int32)),
            adapter_rows=jnp.zeros((s,), jnp.int32) if adapters else None)


def finish_rule(token, eos_id, remaining, done=None):
    """THE finish rule: a sequence is finished once it has emitted its
    eos id or has no budget left AFTER this token (`remaining` counts
    the tokens it may still emit once `token` is out), or was finished
    already (`done`). Plain operators, so it serves traced arrays
    in-graph and Python ints on the host alike; the device's done mask
    and the host's retirement agree because both are this function."""
    hit = token == eos_id
    if done is not None:
        hit = done | hit
    return hit | (remaining <= 0)


def _ngram_hash(a, b, size):
    """Hash a 2-token drafter context into [0, size). Deterministic in
    the token ids; collisions only cost acceptance rate, never
    correctness — every draft is verified by the target model."""
    import jax.numpy as jnp
    ua = a.astype(jnp.uint32) * jnp.uint32(2654435761)
    ub = b.astype(jnp.uint32) * jnp.uint32(40503)
    return ((ua ^ ub) % jnp.uint32(size)).astype(jnp.int32)


def spec_ngram_seed(table, slot, tokens, real_len):
    """Reset one slot's drafter row and seed it with the prompt's
    trigram statistics: context (tokens[j-2], tokens[j-1]) predicts
    tokens[j] for every real j — prompt-lookup decoding's free lunch on
    repetitive/structured text. tokens: (B,) int32 right-padded prompt
    suffix; real_len: traced scalar count of real entries. table:
    (S, T+1) int32 where column T is the trash column masked writes
    land in and -1 marks "no prediction". The RESET is what matters for
    hygiene: slot reuse must not draft from the previous occupant's
    stream (drafts are verified, so stale entries could never corrupt
    tokens — but acceptance stats must be a function of THIS request
    alone)."""
    import jax.numpy as jnp
    B = tokens.shape[0]
    size = table.shape[1] - 1
    table = table.at[slot].set(-1)
    if B < 3:
        return table
    idx = _ngram_hash(tokens[:-2], tokens[1:-1], size)   # (B-2,)
    idx = jnp.where(jnp.arange(2, B) < real_len, idx, size)
    return table.at[slot, idx].set(tokens[2:])


def _spec_step(verify, sample_fn, temps, eos_ids, speculate_k, carry):
    """One draft -> verify -> accept iteration of the speculative chunk
    loop. carry = (tok, pool, ts, keys, done, rem, prev, table);
    verify(inputs (S, k+1), pool, ts, done) -> (logits (S, k+1, V),
    pool). Returns (carry', (out_tokens (k+1, S), counts (S,))).

    Acceptance is EXACT-MATCH against what the sampler itself produces:
    candidate j is sample_fn(key_j, logits_j, temp) where the key chain
    advances one split per candidate — precisely the sequential
    schedule — and logits_j are conditioned on the committed stream
    only while every draft before j matched. So each committed token
    equals, bit for bit, what the non-speculative path would have
    emitted with the same seed: the drafter changes WHEN tokens arrive
    (how many commit per model pass), never WHICH. Greedy is the
    temp=0 special case (candidates are argmax rows).

    EOS/budget stops are applied inside the accepted run with
    `finish_rule`, so the committed run always ends at the finish
    token; frozen slots re-emit their token with count 1 and advance
    their key chain by one split — the non-speculative ride-along
    cadence."""
    import jax
    import jax.numpy as jnp

    k = int(speculate_k)
    tok, pool, ts, keys, done, rem, prev, table = carry
    s_dim = tok.shape[0]
    rows = jnp.arange(s_dim)
    size = table.shape[1] - 1
    # draft: k chained trigram lookups; a miss (-1) proposes token 0 —
    # shapes are fixed, so a hopeless draft costs nothing extra
    drafts = []
    a, b = prev, tok
    with jax.named_scope("loop/draft"):
        for _ in range(k):
            d = table[rows, _ngram_hash(a, b, size)]
            d = jnp.where(d < 0, 0, d)
            drafts.append(d)
            a, b = b, d
        inputs = jnp.stack([tok] + drafts, axis=1)       # (S, k+1)
    logits, pool = verify(inputs, pool, ts, done)
    cands, chain, cur = [], [keys], keys
    with jax.named_scope(SAMPLE_SCOPE):
        for j in range(k + 1):
            cj, cur = jax.vmap(sample_fn)(cur, logits[:, j], temps)
            cands.append(cj)
            chain.append(cur)
        cands = jnp.stack(cands, axis=1)                 # (S, k+1)
        chain = jnp.stack(chain, axis=1)                 # (S, k+2, key)
    with jax.named_scope(FINISH_SCOPE):
        dr = jnp.stack(drafts, axis=1)                   # (S, k)
        # candidate j is valid only while drafts 0..j-1 all matched (its
        # logits saw the committed stream); the mask is monotone by cumprod
        lead = jnp.cumprod((cands[:, :k] == dr).astype(jnp.int32), axis=1)
        base = jnp.concatenate(
            [jnp.ones((s_dim, 1), bool), lead.astype(bool)], axis=1)
        jj = jnp.arange(k + 1)[None, :]
        stop = finish_rule(cands, eos_ids[:, None], rem[:, None] - (jj + 1))
        stopped_before = jnp.concatenate(
            [jnp.zeros((s_dim, 1), bool),
             jnp.cumsum(stop.astype(jnp.int32), axis=1)[:, :-1] > 0], axis=1)
        can = base & ~stopped_before             # monotone commit mask
        c = can.sum(axis=1).astype(jnp.int32)    # >= 1: j=0 always commits
        live = ~done
        last = cands[rows, c - 1]
        prev_commit = jnp.where(c >= 2, cands[rows, jnp.maximum(c - 2, 0)],
                                tok)
        ndone = done | (can & stop).any(axis=1)
        # n-gram table update: every committed token registered under its
        # 2-token context (frozen slots and rejected tails -> trash column)
        seq = jnp.concatenate([prev[:, None], tok[:, None], cands], axis=1)
        idx = _ngram_hash(seq[:, :k + 1], seq[:, 1:k + 2], size)
        idx = jnp.where(can & live[:, None], idx, size)
        table = table.at[rows[:, None], idx].set(cands)
        out = jnp.where(live[:, None],
                        jnp.where(can, cands, last[:, None]), tok[:, None])
        counts = jnp.where(live, c, 1)
        keys = chain[rows, jnp.where(live, c, 1)]
        tok = jnp.where(live, last, tok)
        prev = jnp.where(live, prev_commit, prev)
        ts = jnp.where(live, ts + c, ts)
        rem = jnp.where(live, rem - c, rem)
    return ((tok, pool, ts, keys, ndone, rem, prev, table),
            (out.T, counts))


def decode_chunk(model, params, cfg, arena, pt, keys, carry, chunk,
                 sample_fn=None, speculate_k=0, adapters=None,
                 arena_constraint=None):
    """Fused multi-token decode: `chunk` iterations of the model's
    decode step + per-slot sampling + in-graph EOS/budget masking inside
    ONE lax.scan — a single dispatch (and a single host fetch) emits a
    (chunk, S) token block, amortizing the per-step Python + dispatch +
    sync cost by the chunk factor.

    model: a serving.model.ServingModel. arena: the model's block arena
    (a bare array or, quantized, the (int8 data, f32 scale plane)
    pytree; the scan carries every leaf). pt: (S, P) int32 page table,
    read-only here — it changes only at admission. keys: (S, 2) per-slot
    PRNG keys. carry: DecodeCarry.

    A slot whose `done` is set rides along FROZEN: it re-emits its last
    token, never advances ts and decrements nothing, and the step is
    handed `done` so that its cache write goes to the scratch block (a
    retired slot's blocks are reallocated; its ride-along must not
    dirty them). A live slot freezes in-graph the moment `finish_rule`
    says so, exactly where the host retires it — so the host can consume
    a slot's column up to ITS OWN finish point and discard the frozen
    repeats after it, and a chunked stream is token-identical to the
    per-step path whatever the chunk size. ts never reaches the
    sequence's capacity: the engine admits only prompt + max_new <=
    max_len, and the budget freezes ts at p_len + max_new - 1 at most.

    sample_fn(key, logits_row, temp) -> (token, key_next) is traced
    per-slot (the scheduler passes its temperature/top-k sampler); None
    means greedy argmax. Keys advance every iteration for every slot —
    frozen slots included — so per-request streams stay identical across
    chunk sizes (a request's key is re-seeded at admission anyway).

    `arena_constraint` (tensor-parallel serving, else None): a callable
    re-asserting the arena's mesh sharding, applied to the scan carry at
    the top of every iteration so GSPMD keeps the per-head block layout
    stable through the whole fused loop — one sharded executable, no
    mid-scan resharding/all-gather of the arena. Purely a layout pin.
    `adapters` (the LoRA pool, else None) and `carry.adapter_rows`
    thread to every step/verify pass, read-only through the scan.

    Returns (block (chunk, S) int32 — iteration-major, so block[i, s] is
    slot s's i-th in-chunk token — arena, keys, carry', counters), where
    counters is the model's in-graph counters (`model.counter_names`)
    summed over the chunk, None for a model that has none.

    SPECULATIVE MODE (speculate_k > 0): each scan iteration becomes a
    draft -> verify -> accept pass — the per-slot n-gram drafter in
    `carry.spec` proposes speculate_k tokens, ONE `model.verify` pass
    scores every draft position, and in-graph exact-match acceptance
    (`_spec_step`) commits the matched run plus one corrected token —
    between 1 and speculate_k+1 tokens per model pass, streams
    bit-identical to speculate_k=0 at every chunk size. `block` is then
    the pair (block (chunk, speculate_k+1, S), counts (chunk, S)):
    block[i, :counts[i, s], s] are slot s's committed tokens of pass i,
    entries past the count are frozen repeats the host discards."""
    import jax
    import jax.numpy as jnp

    if sample_fn is None:
        def sample_fn(key, logits, temp):
            return jnp.argmax(logits, -1).astype(jnp.int32), key

    temps, eos_ids, aids = carry.temps, carry.eos_ids, carry.adapter_rows

    if int(speculate_k) > 0:
        def verify(inputs, arena, ts, done):
            if arena_constraint is not None:
                arena = arena_constraint(arena)
            return model.verify(params, cfg, inputs, arena, pt, ts, done,
                                adapters=adapters, adapter_ids=aids)

        def spec_body(c, _):
            return _spec_step(verify, sample_fn, temps, eos_ids,
                              speculate_k, c)

        (tokens, arena, ts, keys, done, remaining, prev, table), \
            (block, counts) = jax.lax.scan(
                spec_body, (carry.tokens, arena, carry.ts, keys,
                            carry.done, carry.remaining) + carry.spec,
                None, length=int(chunk))
        return ((block, counts), arena, keys,
                carry._replace(tokens=tokens, ts=ts, done=done,
                               remaining=remaining, spec=(prev, table)),
                None)

    names = model.counter_names(cfg)
    zeros = {name: jnp.zeros(shape, jnp.int32)
             for name, shape in names.items()} if names else None

    def body(c, _):
        tok, arena, ts, keys, done, rem, counters = c
        if arena_constraint is not None:
            arena = arena_constraint(arena)
        logits, arena, stepped = model.decode_step(
            params, cfg, tok, arena, pt, ts, done, adapters=adapters,
            adapter_ids=aids, arena_constraint=arena_constraint)
        counters = jax.tree_util.tree_map(jnp.add, counters, stepped)
        with jax.named_scope(SAMPLE_SCOPE):
            nxt, keys = jax.vmap(sample_fn)(keys, logits, temps)
        with jax.named_scope(FINISH_SCOPE):
            emit = jnp.where(done, tok, nxt)
            rem = jnp.where(done, rem, rem - 1)
            ndone = finish_rule(emit, eos_ids, rem, done)
            ts = jnp.where(done, ts, ts + 1)
        return (emit, arena, ts, keys, ndone, rem, counters), emit

    (tokens, arena, ts, keys, done, remaining, counters), block = \
        jax.lax.scan(body, (carry.tokens, arena, carry.ts, keys,
                            carry.done, carry.remaining, zeros), None,
                     length=int(chunk))
    return (block, arena, keys,
            carry._replace(tokens=tokens, ts=ts, done=done,
                           remaining=remaining), counters)
