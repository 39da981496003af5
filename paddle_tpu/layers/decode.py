"""Autoregressive decoding helpers: greedy and beam search.

Reference: the LoD-based beam_search/beam_search_decode ops
(operators/beam_search_op.cc, beam_search_decode_op.cc) driven by a
while_op loop. TPU redesign: decoding is a host-side loop over a jitted
single-step function (each step is one XLA call with static shapes —
beams are a fixed dimension folded into the batch), finished with the
gather_tree backtrace op. No dynamic LoD structures anywhere.

`step_fn(tokens) -> logits` receives the full padded token prefix
[b*beam, t] and returns next-token logits [b*beam, V] — the natural form
for the transformer_nmt decoder run teacher-forced on the prefix.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import numpy as np

__all__ = ["greedy_decode", "beam_search_decode",
           "beam_search_decode_on_device"]

# compiled on-device decoders, keyed by (step_fn, shape/config) — a
# fresh jit per call would re-trace the whole L-step loop every time
_ON_DEVICE_CACHE = {}


def greedy_decode(step_logits: Callable[[np.ndarray], np.ndarray],
                  batch_size: int, bos_id: int, eos_id: int,
                  max_len: int) -> np.ndarray:
    """Greedy argmax decoding; returns [b, max_len] token ids (eos-padded
    after each row finishes)."""
    tokens = np.full((batch_size, max_len + 1), eos_id, np.int64)
    tokens[:, 0] = bos_id
    done = np.zeros(batch_size, bool)
    for t in range(max_len):
        logits = np.asarray(step_logits(tokens[:, : t + 1]))
        nxt = np.argmax(logits, axis=-1).astype(np.int64)
        nxt = np.where(done, eos_id, nxt)
        tokens[:, t + 1] = nxt
        done |= nxt == eos_id
        if done.all():
            break
    return tokens[:, 1:]


def beam_search_decode(step_logits: Callable[[np.ndarray], np.ndarray],
                       batch_size: int, beam_size: int, bos_id: int,
                       eos_id: int, max_len: int,
                       length_penalty: float = 0.0
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """Standard beam search. step_logits sees [b*beam, t] prefixes and
    returns [b*beam, V] next-token logits. Returns (sequences [b, beam,
    max_len], scores [b, beam]) best-first, reconstructed with the
    gather_tree backtrace (ids/parents stacked per step like the
    reference's beam-search decode pass)."""
    def log_softmax(x, axis=-1):
        m = x.max(axis=axis, keepdims=True)
        z = x - m
        return z - np.log(np.exp(z).sum(axis=axis, keepdims=True))

    b, k = batch_size, beam_size
    tokens = np.full((b * k, max_len + 1), eos_id, np.int64)
    tokens[:, 0] = bos_id
    scores = np.full((b, k), -1e9, np.float32)
    scores[:, 0] = 0.0                      # only beam 0 is live at t=0
    finished = np.zeros((b, k), bool)
    ids_hist, parents_hist = [], []

    for t in range(max_len):
        logits = np.asarray(step_logits(tokens[:, : t + 1]))
        logp = log_softmax(logits.astype(np.float64), axis=-1)
        v = logp.shape[-1]
        logp = logp.reshape(b, k, v)
        # finished beams only extend with eos at no cost
        pad_mask = np.full((v,), -1e9)
        pad_mask[eos_id] = 0.0
        logp = np.where(finished[:, :, None], pad_mask[None, None, :], logp)
        total = scores[:, :, None] + logp      # [b, k, v]
        flat = total.reshape(b, k * v)
        top = np.argsort(-flat, axis=-1)[:, :k]
        scores = np.take_along_axis(flat, top, axis=-1).astype(np.float32)
        parents = (top // v).astype(np.int64)          # [b, k]
        ids = (top % v).astype(np.int64)               # [b, k]
        ids_hist.append(ids)
        parents_hist.append(parents)
        # reorder token prefixes by parent beam
        tokens = tokens.reshape(b, k, -1)
        tokens = np.take_along_axis(tokens, parents[:, :, None], axis=1)
        tokens = tokens.reshape(b * k, -1)
        tokens[:, t + 1] = ids.reshape(-1)
        finished = np.take_along_axis(finished, parents, axis=1) | (
            ids == eos_id)
        if finished.all():
            break

    # backtrace with the gather_tree op (jit-compiled once)
    import jax.numpy as jnp
    from ..framework.registry import get_op_def, LowerContext
    ids_arr = jnp.asarray(np.stack(ids_hist))          # [T, b, k]
    par_arr = jnp.asarray(np.stack(parents_hist))
    seqs = np.asarray(get_op_def("gather_tree").lower(
        LowerContext(), {"Ids": [ids_arr], "Parents": [par_arr]},
        {})["Out"][0])                                 # [T, b, k]
    seqs = np.transpose(seqs, (1, 2, 0))               # [b, k, T]
    if seqs.shape[-1] < max_len:
        pad = np.full((b, k, max_len - seqs.shape[-1]), eos_id, np.int64)
        seqs = np.concatenate([seqs, pad], axis=-1)
    if length_penalty > 0:
        lens = (seqs != eos_id).sum(-1).clip(min=1)
        scores = scores / (lens.astype(np.float32) ** length_penalty)
        order = np.argsort(-scores, axis=-1)
        seqs = np.take_along_axis(seqs, order[:, :, None], axis=1)
        scores = np.take_along_axis(scores, order, axis=-1)
    return seqs, scores


def beam_search_decode_on_device(step_logits, batch_size: int,
                                 beam_size: int, bos_id: int, eos_id: int,
                                 max_len: int,
                                 length_penalty: float = 0.0,
                                 init_state=None, reorder_state=None):
    """ON-DEVICE beam search: the whole decode loop is ONE jitted XLA
    computation (lax.fori_loop over steps + gather_tree backtrace) — no
    per-step host round trip: a host-loop step pays a dispatch and a
    device-to-host sync each, this variant pays one dispatch in total.

    step_logits must be a JAX-traceable fn(tokens [b*k, max_len+1],
    t: int32 scalar) -> [b*k, V] next-token logits for the prefix
    tokens[:, :t+1] (static padded shape; use `t` for masking).

    CACHED (incremental-state) steps: pass `init_state` (any pytree —
    e.g. a KV cache from models/gpt_decode.gpt_prefill) and the step
    signature becomes fn(tokens, t, state) -> (logits, new_state). After
    each step's top-k the surviving beams are a parent-permutation of the
    previous ones, so the state must be reordered too: `reorder_state
    (state, parent [b, k] int32) -> state` does that (required with
    init_state unless every state leaf has leading dim b*k, which is
    reordered automatically). This is the O(1)-per-step contract of the
    reference's tensor-array decode state (test_machine_translation.py:
    110-136) — without it each step recomputes the whole padded prefix.

    Returns (sequences [b, beam, max_len], scores [b, beam]) best-first,
    matching the host-loop beam_search_decode.
    """
    import jax
    import jax.numpy as jnp

    b, k = batch_size, beam_size
    L = max_len
    neg_inf = -1e9
    stateful = init_state is not None

    if stateful and reorder_state is None:
        # the default reorder gathers leaf[parent] along axis 0; under
        # jit an out-of-range gather CLAMPS instead of erroring, so a
        # wrong-layout state (e.g. a KV cache with batch at axis 2)
        # would silently decode garbage — validate up front
        import jax as _jax
        for leaf in _jax.tree.leaves(init_state):
            if leaf.shape[:1] != (b * k,):
                raise ValueError(
                    f"init_state leaf has shape {leaf.shape}; the default"
                    f" reorder needs leading dim b*beam={b * k}. Pass "
                    "reorder_state= for other layouts (e.g. a KV cache "
                    "with its batch axis elsewhere)")

    def _default_reorder(state, parent):
        # every leaf (b*k, ...): gather rows by parent beam
        flat = (parent + jnp.arange(b)[:, None] * k).reshape(-1)
        return jax.tree.map(lambda a: a[flat], state)

    do_reorder = reorder_state if reorder_state is not None \
        else _default_reorder

    cache_key = (step_logits, b, k, bos_id, eos_id, L,
                 float(length_penalty), stateful, reorder_state)
    cached = _ON_DEVICE_CACHE.get(cache_key)
    if cached is not None:
        seqs, scores = cached(init_state) if stateful else cached()
        return np.asarray(seqs), np.asarray(scores)

    def decode(state0=None):
        tokens0 = jnp.full((b * k, L + 1), eos_id, jnp.int32)
        tokens0 = tokens0.at[:, 0].set(bos_id)
        # only beam 0 live initially (identical prefixes must not
        # multiply through top-k)
        scores0 = jnp.where(jnp.arange(k)[None, :] == 0, 0.0, neg_inf)
        scores0 = jnp.broadcast_to(scores0, (b, k))
        ids_stack0 = jnp.zeros((L, b, k), jnp.int32)
        par_stack0 = jnp.zeros((L, b, k), jnp.int32)
        fin0 = jnp.zeros((b, k), jnp.bool_)

        def body(t, carry):
            tokens, scores, ids_stack, par_stack, finished, state = carry
            if stateful:
                logits, state = step_logits(tokens, t, state)
            else:
                logits = step_logits(tokens, t)      # [b*k, V]
            v = logits.shape[-1]
            logp = jax.nn.log_softmax(
                logits.astype(jnp.float32)).reshape(b, k, v)
            # finished beams only extend with eos at zero cost
            only_eos = jnp.full((b, k, v), neg_inf).at[:, :, eos_id].set(0.0)
            logp = jnp.where(finished[:, :, None], only_eos, logp)
            total = scores[:, :, None] + logp        # [b, k, v]
            flat = total.reshape(b, k * v)
            top_s, top_i = jax.lax.top_k(flat, k)    # [b, k]
            parent = (top_i // v).astype(jnp.int32)
            tok = (top_i % v).astype(jnp.int32)
            # reorder token prefixes to the selected parents
            tokens = tokens.reshape(b, k, L + 1)
            tokens = jnp.take_along_axis(
                tokens, parent[:, :, None], axis=1).reshape(b * k, L + 1)
            tokens = tokens.at[:, t + 1].set(tok.reshape(-1))
            finished = jnp.take_along_axis(finished, parent, axis=1) | \
                (tok == eos_id)
            ids_stack = ids_stack.at[t].set(tok)
            par_stack = par_stack.at[t].set(parent)
            if stateful:
                state = do_reorder(state, parent)
            return tokens, top_s, ids_stack, par_stack, finished, state

        tokens, scores, ids_stack, par_stack, _, _ = jax.lax.fori_loop(
            0, L, body,
            (tokens0, scores0, ids_stack0, par_stack0, fin0, state0))

        # backtrace with the registered gather_tree lowering (one
        # implementation shared with the host-loop variant)
        from ..framework.registry import get_op_def, LowerContext
        seqs = get_op_def("gather_tree").lower(
            LowerContext(), {"Ids": [ids_stack],
                             "Parents": [par_stack]}, {})["Out"][0]
        seqs = seqs.transpose(1, 2, 0)                    # [b, k, L]

        if length_penalty > 0.0:
            # same formula as the host-loop variant above: plain
            # len**p over non-eos tokens (clipped at 1)
            lengths = jnp.maximum(
                (seqs != eos_id).sum(-1), 1).astype(jnp.float32)
            scores = scores / (lengths ** length_penalty)
        order = jnp.argsort(-scores, axis=1)
        seqs = jnp.take_along_axis(seqs, order[:, :, None], axis=1)
        scores = jnp.take_along_axis(scores, order, axis=1)
        return seqs, scores

    jitted = jax.jit(decode)
    _ON_DEVICE_CACHE[cache_key] = jitted
    seqs, scores = jitted(init_state) if stateful else jitted()
    return np.asarray(seqs), np.asarray(scores)
