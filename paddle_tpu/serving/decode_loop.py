"""The fused decode loop: the engine's, one for every served model.

A model supplies ONE decode step over the pool (`ServingModel.decode_step`)
and, for speculation, one multi-position verify pass (`verify`); a model
that generates by diffusion over blocks supplies one block pass instead
(`block_step`). What is wrapped around it is written here once: the
`lax.scan` of `chunk` iterations, the per-slot sampling call and its key
cadence, the frozen-slot rule, the EOS/budget finish rule (`finish_rule`,
which the scan, the speculative acceptance, the block commit, the
admission sampler and the host's block walk all call), the n-gram drafter
with its exact-match acceptance, the unmasking rule of a denoising pass,
and the named decode carry (`DecodeCarry`) every jitted program of the
scheduler threads.

A scan iteration has three bodies: the causal step (one token a slot), the
speculative pass (1 to k + 1 tokens a slot) and the DIFFUSION pass (0 tokens
a slot until its block commits, then up to B at once: `_block_pass`).

Imports no model and, at module level, no jax.
"""

from __future__ import annotations

from typing import Any, NamedTuple

from .model import DIFFUSION_COUNTERS

__all__ = ["DecodeCarry", "finish_rule", "decode_chunk", "spec_ngram_seed",
           "SAMPLE_SCOPE", "FINISH_SCOPE", "UNMASK_SCOPE", "MASKED", "PROMPT",
           "open_block"]

# The loop's own stages in a device trace (`jax.named_scope`, metadata
# only): the sampling call with its key split, and the finish rule with the
# carry's update. The model's step between them stays under the model's own
# scopes; the scheduler's admission sampler uses the same two names.
SAMPLE_SCOPE = "loop/sample"
FINISH_SCOPE = "loop/finish"
# the diffusion pass's own stage between them: which masked positions a
# pass fixes (the threshold, else the rank)
UNMASK_SCOPE = "loop/unmask"

# `fixed_at` of a block position that is not a pass's number: still the
# mask token, or a token of the prompt (never emitted)
MASKED, PROMPT = -2, -1


class DecodeCarry(NamedTuple):
    """The device-resident per-slot decode state, all (S,) unless said:
    the token each slot feeds next, its absolute position, whether it
    rides along frozen (finished, free or cancelled), the tokens it may
    still emit, its temperature and its eos id (-1 = none; sampled ids
    are >= 0, so -1 never matches). `spec` is the drafter's (prev (S,)
    previous committed token, table (S, T+1) trigram table, see
    `spec_ngram_seed`) under speculation and `adapter_rows` the per-slot
    adapter POOL ROW (0 = the base identity) with an adapter pool;
    `block` a block-diffusion model's current block of B positions a slot
    (tokens (S, B), the mask token where still masked; fixed_at (S, B):
    MASKED, PROMPT or the pass that fixed the position; confidence (S, B)
    float32: the probability that pass gave the token it fixed, 0 where
    none did; passes (S,) run on the block so far), `ts` then being the
    block's first position; each
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
    block: Any = None

    @classmethod
    def idle(cls, num_slots, speculate_ngram=None, adapters=False,
             block_length=None):
        """Every slot frozen and empty: the carry before any admission.
        `speculate_ngram` sizes the drafter table (its extra column is
        the trash lane masked scatter writes land in; -1 marks "no
        prediction"); None leaves speculation off. `block_length` sizes a
        block-diffusion model's block; None leaves it off."""
        import jax.numpy as jnp
        s = int(num_slots)
        return cls(
            block=None if block_length is None else (
                jnp.zeros((s, int(block_length)), jnp.int32),
                jnp.full((s, int(block_length)), MASKED, jnp.int32),
                jnp.zeros((s, int(block_length)), jnp.float32),
                jnp.zeros((s,), jnp.int32)),
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


def open_block(cfg_diffusion, tail):
    """A request's FIRST block as the carry holds it, from the prompt's
    last p mod B tokens `tail` (a host sequence): those, fixed as PROMPT,
    and the mask token elsewhere. Returns (tokens (B,), fixed_at (B,)) as
    int32 numpy arrays."""
    import numpy as np
    B = int(cfg_diffusion["block_length"])
    toks = np.full((B,), int(cfg_diffusion["mask_token_id"]), np.int32)
    fixed = np.full((B,), MASKED, np.int32)
    toks[:len(tail)] = tail
    fixed[:len(tail)] = PROMPT
    return toks, fixed


def _block_pass(block_step, sample_fn, diffusion, temps, eos_ids, carry):
    """One PASS of block diffusion over every slot's current block.
    carry = (toks (S, B), fixed (S, B), sure (S, B), passes (S,), pool, ts,
    keys, done, rem, counters); block_step(toks, pool, ts, done) -> (logits
    (S, B, V), pool, counters). Returns (carry', (out (B, S), counts (S,),
    fixed_at (B, S), confidence (B, S))).

    A block that holds a mask is DENOISED: at each masked position j the
    pass's logits (the mask token's own at -inf) give x0_j, drawn by
    `sample_fn` from a key folded from the slot's by j, and c_j, the
    probability softmax(l_j / T) gives x0_j (T = 1 for a greedy slot); with
    n = B / steps, the positions whose c_j clears the threshold are fixed
    if at least n do, else the n of largest c_j; a fixed position keeps its
    c_j beside the pass's number. A block that holds none was run for its
    K|V alone and COMMITS: its tokens behind the prompt's are emitted up to the
    first that `finish_rule` stops at (an eos inside the block, a budget
    that is no multiple of B), `ts` moves B on and the next block is all
    mask. So a slot emits 0 tokens a pass, or up to B. Keys split once a
    slot a pass, frozen slots included."""
    import jax
    import jax.numpy as jnp
    from . import sampling

    B = int(diffusion["block_length"])
    n = B // int(diffusion["denoising_steps"])
    mask_id = int(diffusion["mask_token_id"])
    threshold = float(diffusion["confidence_threshold"])
    toks, fixed, sure, passes, pool, ts, keys, done, rem, counters = carry
    live = ~done
    masked = fixed == MASKED
    denoise = live & masked.any(axis=1)
    commit = live & ~denoise
    logits, pool, stepped = block_step(toks, pool, ts, done)
    counters = jax.tree_util.tree_map(jnp.add, counters, stepped)
    with jax.named_scope(SAMPLE_SCOPE):
        # row by row over (S x B, V), as the head left the logits: a block's
        # B rows in the sublanes of a (S, B, V) array would be a copy of it
        S = toks.shape[0]
        rows = logits.reshape(S * B, -1)
        vocab = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
        rows = jnp.where(vocab == mask_id, -jnp.inf, rows)
        sub = jax.vmap(lambda key: jax.vmap(
            lambda j: sampling.sample_fold(key, j))(
                jnp.arange(B, dtype=jnp.uint32)))(keys)      # (S, B, 2)
        row_temps = jnp.repeat(temps, B)
        x0, _ = jax.vmap(sample_fn)(sub.reshape(S * B, 2), rows, row_temps)
        keys = jax.vmap(sampling.sample_split)(keys)
        z = rows / jnp.where(row_temps > 0.0, row_temps, 1.0)[:, None]
        conf = jnp.exp(
            jnp.take_along_axis(z, x0[:, None], -1)[:, 0]
            - jax.scipy.special.logsumexp(z, axis=-1)).reshape(S, B)
        x0 = x0.reshape(S, B)
    with jax.named_scope(UNMASK_SCOPE):
        # rank 0 is the most confident masked position; ties to the left
        order = jnp.argsort(-jnp.where(masked, conf, -1.0), axis=1,
                            stable=True)
        rank = jnp.argsort(order, axis=1, stable=True)
        by_rank = masked & (rank < n)
        high = masked & (conf > threshold)
        by_threshold = high.sum(axis=1) >= n
        fix = jnp.where(by_threshold[:, None], high, by_rank) \
            & denoise[:, None]
        toks = jnp.where(fix, x0, toks)
        fixed = jnp.where(fix, passes[:, None], fixed)
        sure = jnp.where(fix, conf, sure)
        n_fixed = fix.sum(axis=1).astype(jnp.int32)
    with jax.named_scope(FINISH_SCOPE):
        # the committed block's tokens behind the prompt's, in order
        skip = (fixed == PROMPT).sum(axis=1)
        jj = jnp.arange(B)[None, :]
        at = jnp.minimum(skip[:, None] + jj, B - 1)
        out = jnp.take_along_axis(toks, at, 1)
        out_fixed = jnp.take_along_axis(fixed, at, 1)
        out_sure = jnp.take_along_axis(sure, at, 1)
        valid = skip[:, None] + jj < B
        stop = finish_rule(out, eos_ids[:, None], rem[:, None] - (jj + 1))
        stopped_before = jnp.concatenate(
            [jnp.zeros_like(stop[:, :1]),
             jnp.cumsum(stop.astype(jnp.int32), axis=1)[:, :-1] > 0], axis=1)
        can = valid & ~stopped_before & commit[:, None]
        counts = can.sum(axis=1).astype(jnp.int32)
        done = done | (can & stop).any(axis=1)
        rem = rem - counts
        ts = jnp.where(commit, ts + B, ts)
        toks = jnp.where(commit[:, None], mask_id, toks)
        fixed = jnp.where(commit[:, None], MASKED, fixed)
        sure = jnp.where(commit[:, None], 0.0, sure)
        passes = jnp.where(commit, 0, jnp.where(denoise, passes + 1, passes))
        own = {"block_passes": jnp.sum(live),
               "blocks_committed": jnp.sum(commit),
               "tokens_fixed_by_threshold":
                   jnp.sum(jnp.where(by_threshold, n_fixed, 0)),
               "tokens_fixed_by_rank":
                   jnp.sum(jnp.where(by_threshold, 0, n_fixed))}
        counters = dict(counters, **{
            name: counters[name] + own[name].astype(jnp.int32)
            for name in DIFFUSION_COUNTERS})
    return ((toks, fixed, sure, passes, pool, ts, keys, done, rem, counters),
            (out.T, counts, out_fixed.T, out_sure.T))


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
    entries past the count are frozen repeats the host discards.

    DIFFUSION MODE (a model whose `diffusion(cfg)` is not None; `carry.block`
    holds every slot's block): each scan iteration is one PASS of
    `model.block_step` over the slots' current blocks (`_block_pass`). `block`
    is then (block (chunk, B, S), counts (chunk, S), fixed_at (chunk, B, S),
    confidence (chunk, B, S) float32): counts[i, s] is 0 while slot s's
    block is being denoised and up to B at the pass that commits it;
    fixed_at[i, j, s] is the pass of its block at which committed token j
    was fixed and confidence[i, j, s] the probability that pass gave it. The
    parameters are the model's, not the engine's."""
    import jax
    import jax.numpy as jnp

    if sample_fn is None:
        def sample_fn(key, logits, temp):
            return jnp.argmax(logits, -1).astype(jnp.int32), key

    temps, eos_ids, aids = carry.temps, carry.eos_ids, carry.adapter_rows

    diffusion = model.diffusion(cfg)
    if diffusion is not None:
        names = model.counter_names(cfg)
        zeros = {name: jnp.zeros(shape, jnp.int32)
                 for name, shape in names.items()}

        def block_step(toks, arena, ts, done):
            return model.block_step(params, cfg, toks, arena, pt, ts, done)

        def pass_body(c, _):
            return _block_pass(block_step, sample_fn, diffusion, temps,
                               eos_ids, c)

        (toks, fixed, sure, passes, arena, ts, keys, done, remaining,
         counters), out = jax.lax.scan(
                pass_body, carry.block + (arena, carry.ts, keys, carry.done,
                                          carry.remaining, zeros),
                None, length=int(chunk))
        return (out, arena, keys,
                carry._replace(ts=ts, done=done, remaining=remaining,
                               block=(toks, fixed, sure, passes)), counters)

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
