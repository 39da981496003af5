"""Long-context sequence/context parallelism: ring attention + Ulysses.

New capability relative to the reference (SURVEY.md §2.6 last row: the 2019
codebase has no CP/SP — its only long-sequence mechanism is LoD ragged
batching, lod_tensor.h:104). Built TPU-first:

* **Ring attention** — K/V shards rotate around the `cp` mesh axis with
  `lax.ppermute` (ICI neighbor exchange) while each device accumulates
  blockwise attention with an online softmax; memory stays O(s_local), the
  collective is bandwidth-optimal, and XLA overlaps the permute with the
  per-step matmuls. Differentiable end-to-end (scan + ppermute both have
  transpose rules), so the backward is itself a ring.
* **Ulysses / all-to-all SP** — `lax.all_to_all` trades the sequence shard
  for a heads shard, runs full (flash) attention on contiguous sequences,
  and trades back. Cheaper collectives for moderate sequence lengths; needs
  heads % cp == 0.

Both are exposed as shard_map'd functions over a `jax.sharding.Mesh` and as
the lowering of the `fused_attention` program op when `cp_axis` is set.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..observability.tracer import trace_span, tracing_enabled

__all__ = ["ring_attention", "ulysses_attention", "ring_attention_sharded",
           "ulysses_attention_sharded"]


def _comm_span(kind: str, k, axis_name: str, hops: int):
    """Observability span for one collective call site. Recorded at trace
    time (these wrappers run under jit tracing), so the span measures
    host-side build cost; the byte count is the collective's per-device
    K+V traffic — the number tools/comm_volume.py accounts for on the
    wire. k: the local K shard (V matches). With the ring off the byte
    math is skipped and the span is its profiler annotation alone."""
    if not tracing_enabled():
        return trace_span(f"comm/{kind}", "comm")
    per_hop = 2 * int(np.prod(k.shape)) * k.dtype.itemsize   # K and V
    return trace_span(f"comm/{kind}", "comm",
                      {"axis": axis_name, "bytes": per_hop * max(1, hops),
                       "bytes_per_hop": per_hop})

_NEG_INF = -1e30


def _block_scores(q, k, sm_scale, bias_k):
    """(b, sq, n, d) x (b, sk, n, d) -> (b, n, sq, sk) f32 scores."""
    s = jnp.einsum("bqnd,bknd->bnqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if bias_k is not None:
        s = s + bias_k[:, None, None, :].astype(jnp.float32)
    return s


def ring_attention_sharded(q, k, v, bias_k, axis_name: str,
                           causal: bool = False,
                           sm_scale: Optional[float] = None):
    """Per-shard ring attention body (call under shard_map).

    q, k, v: local shards (b, s_local, n, d) — sequence dim sharded over
    `axis_name`. bias_k: optional per-key additive bias shard (b, s_local)
    (rotates with k/v). Returns the local output shard (b, s_local, n, d).
    """
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    axis_size = jax.lax.axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, s_loc, n, d = q.shape

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    acc0 = jnp.zeros((b, n, s_loc, d), jnp.float32)
    m0 = jnp.full((b, n, s_loc, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, n, s_loc, 1), jnp.float32)
    if bias_k is None:
        bias_k = jnp.zeros((b, s_loc), q.dtype)

    def step(carry, t):
        acc, m, l, k_t, v_t, b_t = carry
        src = (my_idx - t) % axis_size      # which shard k_t/v_t came from
        s = _block_scores(q, k_t, sm_scale, b_t)
        if causal:
            # global positions: q rows at my_idx*s_loc+i, keys at src*s_loc+j
            qi = (jax.lax.broadcasted_iota(jnp.int32, (s_loc, s_loc), 0)
                  + my_idx * s_loc)
            ki = (jax.lax.broadcasted_iota(jnp.int32, (s_loc, s_loc), 1)
                  + src * s_loc)
            s = jnp.where((qi >= ki)[None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bnqk,bknd->bnqd", p.astype(v_t.dtype), v_t,
            preferred_element_type=jnp.float32)
        k_t = jax.lax.ppermute(k_t, axis_name, perm)
        v_t = jax.lax.ppermute(v_t, axis_name, perm)
        b_t = jax.lax.ppermute(b_t, axis_name, perm)
        return (acc, m_new, l, k_t, v_t, b_t), ()

    (acc, m, l, _, _, _), _ = jax.lax.scan(
        step, (acc0, m0, l0, k, v, bias_k), jnp.arange(axis_size))
    l = jnp.where(l == 0.0, 1.0, l)
    o = (acc / l).astype(q.dtype)           # (b, n, s_loc, d)
    return o.transpose(0, 2, 1, 3)


def ulysses_attention_sharded(q, k, v, bias_k, axis_name: str,
                              causal: bool = False,
                              sm_scale: Optional[float] = None,
                              impl: Optional[str] = None):
    """Per-shard Ulysses attention body (call under shard_map).

    all_to_all converts the (seq-sharded, all-heads) layout into
    (full-seq, heads-sharded), runs fused attention, converts back.
    Requires heads % axis_size == 0.
    """
    from ..ops.flash_attention import attention

    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    axis_size = jax.lax.axis_size(axis_name)
    if q.shape[2] % axis_size != 0:
        raise ValueError(
            f"ulysses needs heads ({q.shape[2]}) divisible by the "
            f"{axis_name!r} axis size ({axis_size})")

    def gather_seq(x):  # (b, s_loc, n, d) -> (b, s_full, n/ax, d)
        return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

    qg, kg, vg = gather_seq(q), gather_seq(k), gather_seq(v)
    bias4 = None
    if bias_k is not None:
        bk = jax.lax.all_gather(bias_k, axis_name, axis=1, tiled=True)
        bias4 = bk[:, None, None, :]
    o = attention(qg, kg, vg, bias4, causal=causal, sm_scale=sm_scale,
                  impl=impl)
    return jax.lax.all_to_all(o, axis_name, split_axis=1,
                              concat_axis=2, tiled=True)


def _shard_mapped(body, mesh, axis_name, has_bias):
    spec = P(None, axis_name, None, None)
    bspec = P(None, axis_name)
    in_specs = (spec, spec, spec, bspec if has_bias else None)
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=spec, check_vma=False)


def ring_attention(q, k, v, mesh, axis_name: str, bias_k=None,
                   causal: bool = False, sm_scale: Optional[float] = None):
    """Global-view ring attention: q/k/v (b, s, n, d) with s sharded over
    mesh axis `axis_name`; bias_k optional (b, s) per-key additive bias."""
    body = functools.partial(ring_attention_sharded, axis_name=axis_name,
                             causal=causal, sm_scale=sm_scale)
    hops = int(mesh.shape[axis_name])
    with _comm_span("ring_attention", k, axis_name, hops):
        return _shard_mapped(lambda a, b_, c, d_: body(a, b_, c, d_),
                             mesh, axis_name, bias_k is not None)(
            q, k, v, bias_k)


def ulysses_attention(q, k, v, mesh, axis_name: str, bias_k=None,
                      causal: bool = False,
                      sm_scale: Optional[float] = None,
                      impl: Optional[str] = None):
    body = functools.partial(ulysses_attention_sharded, axis_name=axis_name,
                             causal=causal, sm_scale=sm_scale, impl=impl)
    # all_to_all moves each shard once in, once back out: 2 "hops"
    with _comm_span("ulysses_attention", k, axis_name, 2):
        return _shard_mapped(lambda a, b_, c, d_: body(a, b_, c, d_),
                             mesh, axis_name, bias_k is not None)(
            q, k, v, bias_k)
