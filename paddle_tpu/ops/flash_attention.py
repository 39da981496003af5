"""Fused multi-head attention: Pallas TPU flash kernel + XLA reference.

The TPU-native replacement for the reference's composed attention
(python/paddle/fluid/nets.py scaled_dot_product_attention: matmul + scale +
softmax + dropout + matmul, materialising the (s, s) score matrix in HBM)
and for the operators/fused/ fusion-op family: one online-softmax kernel that
keeps scores in VMEM, O(s) memory, with a custom VJP whose backward is also
a Pallas kernel.

Layout is (batch, seq, heads, head_dim) end-to-end — no transposes around
the kernel. The forward's row statistics (m, l, lse) are stored lane-padded
to 128 (Mosaic tiling requires the last dim be a lane multiple or the full
array dim); the tiled backward takes delta as compact rows.
`attention()` dispatches: Pallas on TPU backends, the einsum
reference elsewhere (CPU tests) or when shapes are tiny/unaligned;
impl='flash' forces the kernel (interpreted on CPU, an error on any
other non-TPU backend).
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["attention", "attention_fwd_lse", "attention_bwd_saved",
           "causal_rows_tiles", "flash_attention", "flash_causal_rows",
           "flash_dispatch", "mha_reference"]

_NEG_INF = -1e30
_LANES = 128


def mha_reference(q, k, v, bias=None, causal: bool = False,
                  sm_scale: Optional[float] = None):
    """Plain-XLA attention. q: (b, sq, n, d); k/v: (b, sk, n, d);
    bias: additive, broadcastable to (b, n, sq, sk). Returns (b, sq, n, d)."""
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqnd,bknd->bnqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        qi = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where(qi[None, None] >= ki[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bnqk,bknd->bqnd", p, v)


def _lanes_to(x, n):
    """(rows, 128) all-lanes-equal -> (rows, n)."""
    if n == _LANES:
        return x
    if n < _LANES:
        return x[:, :n]
    assert n % _LANES == 0
    return jnp.tile(x, (1, n // _LANES))


# ---------------------------------------------------------------------------
# Pallas forward kernel: a walk over the tiles that hold work
# ---------------------------------------------------------------------------
#
# The forward's grid is (heads, VISITS). A visit is one (query tile, KV
# tile) pair that holds a (row, column) the result needs: a column of the
# keys' real length, at or below the row (causal), inside the row's window
# (a band), of a row below `length` (a serving prompt's real row count
# inside its bucket). A query tile's visits follow each other, its KV tiles
# in order, the diagonal one last, so `m`, `l` and `acc` stay in VMEM over
# them. The walk reaches the kernel as scalar-prefetch operands that the
# index maps read: no grid step exists that fetches a tile and computes
# nothing. Each visit carries bits: the query tile's first and last visit,
# and whether the query tile holds no real row at all (its one visit stores
# zeros). Every computed visit builds the masks, as the rectangle's steps
# did: on a v5e they hide under the MXU's time (PERF.md, PR 34).

_FIRST, _LAST, _EMPTY = 1, 2, 4


# the columns one online-softmax update takes of a walk's KV tile: a
# larger tile is taken in chunks, m, l and acc carried as values from one
# to the next (TPU v5e, 32 heads 192/192/128, 16,384 rows: tiles of 1,024
# whole 653 us a head, in chunks of 512 601; tiles of 512 whole 683, in
# chunks of 256 748: PERF.md, PR 34)
_CHUNK = 512


def _spans(nq, block_q, block_k, causal, window, kv_len):
    """(first KV tile, KV tiles) of each of `nq` query tiles: the KV tiles
    that hold a column some row of the query tile attends."""
    r0 = np.arange(nq) * block_q
    hi = np.full(nq, kv_len - 1)
    if causal:
        hi = np.minimum(hi, r0 + block_q - 1)
    lo = np.zeros(nq, np.int64) if window is None \
        else np.maximum(r0 - (window - 1), 0)
    first = lo // block_k
    return first, hi // block_k - first + 1


def _walk(nq, block_q, block_k, causal, window, kv_len):
    """The walk over every row of the sequence, as numpy int32 arrays
    (query tile (V,), KV tile (V,), bits (V,)) and the running visit
    count before each query tile (nq + 1,)."""
    first, n = _spans(nq, block_q, block_k, causal, window, kv_len)
    upto = np.concatenate([[0], np.cumsum(n)])
    qt = np.repeat(np.arange(nq), n)
    kt = np.arange(upto[-1]) - upto[qt] + first[qt]
    bits = _FIRST * (kt == first[qt]) + _LAST * (kt == (first + n)[qt] - 1)
    return (qt.astype(np.int32), kt.astype(np.int32), bits.astype(np.int32),
            upto.astype(np.int32))


def _walk_to(length, walk, block_q, block_k):
    """`_walk`'s arrays of a causal self-attention whose KV tile is a
    multiple of its query tile, cut to the rows below the traced `length`:
    the query tiles that hold a real row keep their visits (no KV tile's
    edge falls inside a query tile: the same KV tiles, whole or not),
    every later one has ONE visit that stores zeros and fetches nothing
    new, and the steps past the count repeat the last visit. Returns
    (query tile, KV tile, bits, count)."""
    qt, kt, bits, upto = walk
    nq, V = upto.shape[0] - 1, qt.shape[0]
    live = jnp.clip((length + block_q - 1) // block_q, 0, nq)
    real = jnp.asarray(upto)[live]
    at = jnp.arange(V, dtype=jnp.int32)
    held = at < real
    return (jnp.where(held, qt, jnp.minimum(live + at - real, nq - 1)),
            jnp.where(held, kt, jnp.maximum(live * block_q - 1, 0) // block_k),
            jnp.where(held, bits, _EMPTY), real + nq - live)


def _fwd_kernel(qt_ref, kt_ref, bits_ref, meta_ref, q_ref, k_ref, v_ref,
                b_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *, sm_scale,
                causal, block_q, block_k, kv_len, window, ragged, single,
                chunk, block=None):
    from jax.experimental import pallas as pl

    d = v_ref.shape[-1]       # the output's width: v's, not q's

    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute(q_idx, k_idx):
        # the KV tile in chunks of columns, one online-softmax update
        # each, m, l and acc carried as values: a chunk's first product
        # does not wait for the chunk before it
        q = q_ref[0]
        m, l, acc = m_scr[:], l_scr[:], acc_scr[:]   # (block_q, 128 | d)
        for c in range(block_k // chunk):
            cols = slice(c * chunk, (c + 1) * chunk)
            s = jax.lax.dot_general(
                q, k_ref[0, cols, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            if b_ref is not None:
                s = s + b_ref[0, :, cols].astype(jnp.float32)
            col = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                   + (k_idx * block_k + c * chunk))
            s = jnp.where(col < kv_len, s, _NEG_INF)       # kv padding
            if causal:
                row = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                       + q_idx * block_q)
                if block is not None:
                    # block-causal: row i attends columns j <= i | (B - 1),
                    # the whole of its own block of B rows (B a power of
                    # two that divides the tiles: the diagonal tiles' mask
                    # alone differs, the walk is the causal one)
                    row = row | (block - 1)
                s = jnp.where(row >= col, s, _NEG_INF)
                if window is not None:
                    # row i attends columns j with i - window < j
                    s = jnp.where(row - col < window, s, _NEG_INF)
            m_curr = jnp.max(s, axis=1)[:, None]         # (block_q, 1)
            m_new = jnp.maximum(m, m_curr)               # (block_q, 128)
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - _lanes_to(m_new, chunk))
            l = l * alpha + jnp.sum(p, axis=1)[:, None]
            acc = acc * _lanes_to(alpha, d) + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, cols, :],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            m = m_new
        m_scr[:], l_scr[:], acc_scr[:] = m, l, acc

    def _fin(q_idx):
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)         # fully-masked rows
        o = acc_scr[:] / _lanes_to(l_safe, d)
        lse = m_scr[:] + jnp.log(l_safe)
        if ragged:
            # a row at or past the length is nobody's: zeros
            row = (jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
                   + q_idx * block_q)
            o = jnp.where(row < meta_ref[1], o, 0.0)
            lse = jnp.where(row < meta_ref[1], lse, _NEG_INF)
        o_ref[0] = o.astype(o_ref.dtype)
        lse_ref[0] = lse

    if single:
        # one tile a head (a serving prefill's short bucket): one visit,
        # straight-line code: no branch
        _init()
        _compute(0, 0)
        _fin(0)
        return

    i = pl.program_id(1)

    @pl.when(i < meta_ref[0])
    def _visit():
        q_idx, k_idx, bits = qt_ref[i], kt_ref[i], bits_ref[i]
        pl.when((bits & _FIRST) != 0)(_init)
        if ragged:
            pl.when((bits & _EMPTY) == 0)(lambda: _compute(q_idx, k_idx))
        else:
            _compute(q_idx, k_idx)
        pl.when((bits & _LAST) != 0)(lambda: _fin(q_idx))

        if ragged:
            @pl.when((bits & _EMPTY) != 0)
            def _nobody():
                o_ref[...] = jnp.zeros_like(o_ref)
                lse_ref[...] = jnp.full_like(lse_ref, _NEG_INF)


# ---------------------------------------------------------------------------
# Pallas backward kernel: one pass over the tile pairs that hold work
# ---------------------------------------------------------------------------
#
# The tiled backward is ONE call whose grid is (heads, VISITS), a walk as
# the forward's but KV-tile major: a KV tile's visits follow each other, its
# query tiles in order, so `dk` and `dv` (and `db`) accumulate in float32
# scratch over them and are written at the tile's last visit, while `dq`
# accumulates in a float32 scratch of ALL the call's query rows, (rows, d),
# and is written once, at the head's last visit (how many rows a call may
# hold: `backward_span_rows`). A pair's scores, probabilities, `dp` and `ds`
# exist once and feed all three gradients. They are held TRANSPOSED,
# (block_k, block_q): `lse` and `delta` then broadcast from (1, block_q)
# rows (`delta` comes as compact rows; the forward's lane-padded `lse` tile
# is transposed here), a key's bias is a column, `dv = p^T do` and `dk =
# ds^T q` are plain products, and only `dq = ds k` transposes its left
# operand. The scores, the exponential against the saved `lse`, `dp - delta`
# and `ds` are float32; `p` and `ds` take the TYPE OF THE OPERAND they meet
# in a product, as the forward's `p` does (float32 operands: nothing is
# rounded), and every product accumulates in float32. A square pair ON the
# causal diagonal is taken in two halves of its keys, the upper half against
# the upper half of its rows alone (the quarter above holds nothing).

# beside _FIRST and _LAST, of a KV tile here: the pair lies ON the diagonal
_DIAGONAL = 4

_NT = (((1,), (1,)), ((), ()))          # a b^T
_TN = (((0,), (0,)), ((), ()))          # a^T b


def _diagonal_halves(causal, block_q, block_k):
    """Whether a pair ON the diagonal is taken in two halves: square
    tiles whose halves are whole lanes."""
    return causal and block_q == block_k and block_q % 256 == 0


def _bwd_walk(nq, nk, block_q, block_k, causal, row0=0):
    """The backward's walk as numpy int32 arrays (query tile (V,), KV tile
    (V,), bits (V,)), KV-tile major, over `nq` query tiles whose first row
    is row `row0` of the sequence and `nk` KV tiles from column 0. Causal:
    a KV tile is visited by the query tiles that hold a row at or below its
    first column; one above every row (sk > sq) keeps the last query tile's
    visit, which meets masks alone and stores zeros."""
    first = np.zeros(nk, np.int64)
    if causal:
        first = np.clip((np.arange(nk) * block_k - row0) // block_q, 0, nq - 1)
    visits = nq - first
    upto = np.concatenate([[0], np.cumsum(visits)])
    kt = np.repeat(np.arange(nk), visits)
    at = np.arange(upto[-1]) - upto[kt]
    qt = first[kt] + at
    diagonal = (_diagonal_halves(causal, block_q, block_k)
                & (row0 + qt * block_q == kt * block_k))
    bits = (_FIRST * (at == 0) + _LAST * (at == visits[kt] - 1)
            + _DIAGONAL * diagonal)
    return qt.astype(np.int32), kt.astype(np.int32), bits.astype(np.int32)


def _bwd_kernel(qt_ref, kt_ref, bits_ref, q_ref, k_ref, v_ref, b_ref,
                do_ref, lse_ref, dl_ref, dq_ref, dk_ref, dv_ref, db_ref,
                dq_scr, dk_scr, dv_scr, db_scr, *, sm_scale, causal,
                block_q, block_k, kv_len, row0):
    from jax.experimental import pallas as pl

    i = pl.program_id(1)
    q_idx, k_idx, bits = qt_ref[i], kt_ref[i], bits_ref[i]

    @pl.when(i == 0)
    def _head():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when((bits & _FIRST) != 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)
        if db_scr is not None:
            db_scr[...] = jnp.zeros_like(db_scr)

    def _pair(keys=(0, block_k), rows=(0, block_q)):
        """The keys [k0, k1) of the KV tile against the rows [r0, r1) of
        the query tile."""
        (k0, k1), (r0, r1) = keys, rows
        q, do = q_ref[0, r0:r1, :], do_ref[0, r0:r1, :]
        k, v = k_ref[0, k0:k1, :], v_ref[0, k0:k1, :]
        st = jax.lax.dot_general(                  # (keys, rows)
            k, q, _NT, preferred_element_type=jnp.float32) * sm_scale
        if b_ref is not None:
            st = st + b_ref[0, k0:k1, :1]          # a key's bias: a column
        if causal or kv_len % block_k:
            col = (jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
                   + (k_idx * block_k + k0))
            keep = col < kv_len                    # kv padding
            if causal:
                row = (jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
                       + (q_idx * block_q + (row0 + r0)))
                keep = keep & (row >= col)
            st = jnp.where(keep, st, _NEG_INF)
        pt = jnp.exp(st - lse_ref[0, r0:r1, :].T[:1])
        dv_scr[k0:k1, :] += jnp.dot(pt.astype(do.dtype), do,
                                    preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(
            v, do, _NT, preferred_element_type=jnp.float32)
        dst = pt * (dpt - dl_ref[0, :, r0:r1]) * sm_scale
        if db_scr is not None:
            # a key's bias grad: ds summed over the query rows (ds carries
            # sm_scale; the bias enters the scores unscaled, so divide it
            # back out)
            db_scr[k0:k1, :] += jnp.sum(dst, axis=1, keepdims=True) / sm_scale
        dk_scr[k0:k1, :] += jnp.dot(dst.astype(q.dtype), q,
                                    preferred_element_type=jnp.float32)
        at = pl.ds(pl.multiple_of(q_idx * block_q + r0, 128), r1 - r0)
        dq_scr[at, :] += jax.lax.dot_general(
            dst.astype(k.dtype), k, _TN, preferred_element_type=jnp.float32)

    if _diagonal_halves(causal, block_q, block_k):
        half = block_k // 2

        @pl.when((bits & _DIAGONAL) != 0)
        def _diagonal():
            _pair(keys=(0, half))
            _pair(keys=(half, block_k), rows=(half, block_q))

        pl.when((bits & _DIAGONAL) == 0)(_pair)
    else:
        _pair()

    @pl.when((bits & _LAST) != 0)
    def _fin():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)
        if db_ref is not None:
            db_ref[0] = db_scr[...]

    @pl.when(i == pl.num_programs(1) - 1)
    def _out():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# small-sequence single-pass kernels
# ---------------------------------------------------------------------------
#
# At short seq (s <= 256) the tiled online-softmax kernel loses to XLA's
# fused composition: one (128, 128) tile per (batch*head) program leaves
# each program mostly overhead (measured r2: 34.8% vs 48% MFU on the
# BERT flagship at s=128). The fix is WIDTH, not depth: scores fit VMEM
# whole, so a single-pass kernel batches MANY (batch*head) rows per
# program (dot_general with a batch dim) and amortizes the grid/DMA
# overhead — the "unfused flash" regime from the flash-attention paper's
# small-N appendix.

def _small_batch(bn, s):
    """Rows per program: largest power-of-two divisor of bn whose f32
    score tile (B, s, s) stays within ~1.5MB of VMEM (the backward's
    working set is ~8x the score tile — scores + p + dp + ds plus the
    q/k/v/do tiles — against the 16MB scoped limit)."""
    budget = 3 * 512 * 1024
    b = 16
    while b > 1 and (bn % b != 0 or b * s * s * 4 > budget):
        b //= 2
    return b


def _small_scores(q_ref, k_ref, b_ref, sm_scale, causal):
    """(B, sq, d) x (B, sk, d) -> masked f32 scores (B, sq, sk)."""
    qq = q_ref[...].astype(jnp.float32)
    kk = k_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(qq, kk, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * sm_scale
    if b_ref is not None:
        s = s + b_ref[...].astype(jnp.float32)     # (B, 1, sk) broadcast
    if causal:
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(row >= col, s, _NEG_INF)
    return s


def _small_fwd_kernel(q_ref, k_ref, v_ref, b_ref, o_ref, lse_ref, *,
                      sm_scale, causal):
    s = _small_scores(q_ref, k_ref, b_ref, sm_scale, causal)
    m = jnp.max(s, axis=2, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=2, keepdims=True)
    o = jax.lax.dot_general((p / l).astype(v_ref.dtype), v_ref[...],
                            (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    o_ref[...] = o.astype(o_ref.dtype)
    lse_ref[...] = jnp.broadcast_to(m + jnp.log(l), lse_ref.shape)


def _small_bwd_kernel(q_ref, k_ref, v_ref, b_ref, do_ref, lse_ref, dl_ref,
                      dq_ref, dk_ref, dv_ref, db_ref, *, sm_scale, causal):
    s = _small_scores(q_ref, k_ref, b_ref, sm_scale, causal)
    p = jnp.exp(s - lse_ref[..., :1])              # (B, sq, sk)
    qq = q_ref[...].astype(jnp.float32)
    kk = k_ref[...].astype(jnp.float32)
    vv = v_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)
    dp = jax.lax.dot_general(do, vv, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - dl_ref[..., :1])
    dq_ref[...] = (jax.lax.dot_general(
        ds.astype(kk.dtype), kk, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * sm_scale).astype(dq_ref.dtype)
    dk_ref[...] = (jax.lax.dot_general(
        ds.astype(qq.dtype), qq, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * sm_scale).astype(dk_ref.dtype)
    dv_ref[...] = jax.lax.dot_general(
        p.astype(do.dtype), do, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).astype(dv_ref.dtype)
    if db_ref is not None:
        db_ref[...] = jnp.sum(ds, axis=1, keepdims=True) \
            .astype(db_ref.dtype)


def _small_call(q, k, v, bias, causal, sm_scale, interpret):
    """Single-pass path over the (b*n, s, d) layout: whole (sq, sk)
    score tile per row, B rows per program (batched dot_general) to
    amortize grid/DMA overhead. bias: (b*n, sk) per-key additive.
    Returns (o (bn,sq,d), lse (bn,sq,LANES) lane-padded)."""
    from jax.experimental import pallas as pl

    bn, sq, d = q.shape
    sk = k.shape[1]
    B = _small_batch(bn, max(sq, sk))
    kw = dict(sm_scale=sm_scale, causal=causal)
    in_specs = [
        pl.BlockSpec((B, sq, d), lambda i: (i, 0, 0)),
        pl.BlockSpec((B, sk, d), lambda i: (i, 0, 0)),
        pl.BlockSpec((B, sk, d), lambda i: (i, 0, 0)),
    ]
    args = [q, k, v]
    if bias is not None:
        args.append(bias[:, None, :])              # (bn, 1, sk)
        in_specs.append(pl.BlockSpec((B, 1, sk), lambda i: (i, 0, 0)))
        kern = functools.partial(_small_fwd_kernel, **kw)
    else:
        def kern(q_r, k_r, v_r, o_r, lse_r):
            _small_fwd_kernel(q_r, k_r, v_r, None, o_r, lse_r, **kw)

    o, lse = pl.pallas_call(
        kern,
        grid=(bn // B,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((B, sq, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((B, sq, _LANES), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bn, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bn, sq, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return o, lse


def _small_bwd_call(q, k, v, bias, o, lse, do, causal, sm_scale,
                    interpret):
    """Single-pass backward over the (b*n, s, d) layout (recomputes
    scores from q/k + lse — the save-p variant measured slower, see
    BASELINE.md r3); db comes back (bn, sk)."""
    from jax.experimental import pallas as pl

    bn, sq, d = q.shape
    sk = k.shape[1]
    B = _small_batch(bn, max(sq, sk))
    dl = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    dl3 = jnp.broadcast_to(dl[:, :, None], (bn, sq, _LANES))
    kw = dict(sm_scale=sm_scale, causal=causal)

    in_specs = [
        pl.BlockSpec((B, sq, d), lambda i: (i, 0, 0)),
        pl.BlockSpec((B, sk, d), lambda i: (i, 0, 0)),
        pl.BlockSpec((B, sk, d), lambda i: (i, 0, 0)),
    ]
    args = [q, k, v]
    if bias is not None:
        args.append(bias[:, None, :])
        in_specs.append(pl.BlockSpec((B, 1, sk), lambda i: (i, 0, 0)))
    args += [do, lse, dl3]
    in_specs += [
        pl.BlockSpec((B, sq, d), lambda i: (i, 0, 0)),
        pl.BlockSpec((B, sq, _LANES), lambda i: (i, 0, 0)),
        pl.BlockSpec((B, sq, _LANES), lambda i: (i, 0, 0)),
    ]
    out_specs = [
        pl.BlockSpec((B, sq, d), lambda i: (i, 0, 0)),
        pl.BlockSpec((B, sk, d), lambda i: (i, 0, 0)),
        pl.BlockSpec((B, sk, d), lambda i: (i, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((bn, sq, d), q.dtype),
        jax.ShapeDtypeStruct((bn, sk, d), k.dtype),
        jax.ShapeDtypeStruct((bn, sk, d), v.dtype),
    ]
    if bias is not None:
        out_specs.append(pl.BlockSpec((B, 1, sk), lambda i: (i, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((bn, 1, sk), jnp.float32))
        kern = functools.partial(_small_bwd_kernel, **kw)
    else:
        def kern(q_r, k_r, v_r, do_r, lse_r, dl_r, dq_r, dk_r, dv_r):
            _small_bwd_kernel(q_r, k_r, v_r, None, do_r, lse_r, dl_r,
                              dq_r, dk_r, dv_r, None, **kw)

    outs = pl.pallas_call(
        kern,
        grid=(bn // B,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*args)
    if bias is not None:
        dq, dk, dv, db3 = outs
        return dq, dk, dv, db3[:, 0, :]
    dq, dk, dv = outs
    return dq, dk, dv, None


def _small_ok(sq, sk):
    """Shapes the single-pass path handles: both dims fit one VMEM-sized
    score tile and are lane/sublane aligned."""
    return (sq <= 512 and sk <= 512 and sk % _LANES == 0
            and sq % 8 == 0)


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------

def _pick_blocks(sq, sk):
    block_q = min(512, sq) if sq % min(512, sq) == 0 else 128
    block_k = min(512, sk) if sk % min(512, sk) == 0 else 128
    return block_q, block_k


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _flash_call(q, k, v, bias, causal, sm_scale, interpret, blocks=None,
                group=1, window=None, length=None, block=None):
    """The forward. q: (bn, sq, d); k: (bn, sk, d); v: (bn, sk, dv); bias:
    (bn, sk) or None. Returns o (bn, sq, dv) unpadded and lse (bn, sq_pad,
    128) lane-padded.

    The grid is (bn, visits): `_walk`'s list of the (query tile, KV tile)
    pairs that hold work, read by the index maps from scalar-prefetch
    operands (the comment above `_fwd_kernel`). Four things only the
    forward has; the backward kernels have none of them, and training
    never asks:
      * `group` > 1: k and v are (bn // group, sk, .), KV head i // group
        shared by the query heads i of its group through the index map
        (no repeated K or V in HBM);
      * `window` (causal self-attention, sq == sk, the KV tile a multiple
        of the query tile, no bias):
        row i attends i - window < j <= i, and a query tile visits only
        the KV tiles that touch its window, not the sequence's;
      * a value width of its own (latent attention's prefill: q, k of 192
        and v of 128);
      * `length` (a traced int32 scalar, 0 <= length <= sq; the same
        conditions as a window): only rows below it
        are real. The walk is then computed from it: tiles past it are
        not visited, rows at or past it come back ZERO in o and _NEG_INF
        in lse, whatever q, k and v hold there (no real row attends them:
        causal). None: every row is real and the walk is a compile-time
        constant.
      * `block` (causal self-attention, a power of two that divides both
        tiles): the mask is BLOCK-causal, row i attends j <= i | (block -
        1); with a `length` that is a multiple of it.
    `blocks` (block_q, block_k) stands in for `_pick_blocks`' choice where
    a caller knows its grid better (`flash_causal_rows`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bn, sq0, d = q.shape
    dv = v.shape[-1]
    sk0 = k.shape[1]
    block_q, block_k = blocks or _pick_blocks(sq0, sk0)
    q = _pad_to(q, 1, block_q)
    k = _pad_to(k, 1, block_k)
    v = _pad_to(v, 1, block_k)
    sq, sk = q.shape[1], k.shape[1]
    if (window is not None or length is not None) and not (
            causal and bias is None and sq0 == sk0
            and block_k % block_q == 0):
        raise ValueError(
            "a window or a length is causal self-attention without a bias, "
            "its KV tile a multiple of its query tile")

    if block is not None and (
            not causal or block & (block - 1) or block_q % block
            or block_k % block or window is not None):
        raise ValueError(
            f"a block-causal mask of {block} rows is causal, without a "
            f"window, a power of two that divides the tiles "
            f"({block_q}, {block_k})")
    walk = _walk(sq // block_q, block_q, block_k, causal, window, sk0)
    if length is None:
        qt, kt, bits = walk[:3]
        meta = np.asarray([qt.shape[0], sq0], np.int32)
    else:
        length = jnp.asarray(length, jnp.int32)
        qt, kt, bits, count = _walk_to(length, walk, block_q, block_k)
        meta = jnp.stack([count, length])
    kern = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, kv_len=sk0, ragged=length is not None,
        window=None if window is None else int(window),
        single=walk[0].shape[0] == 1,
        chunk=block_k if qt.shape[0] == 1 else min(block_k, _CHUNK),
        block=block)

    q_map = lambda i, s, qt, kt, *_: (i, qt[s], 0)
    kv_map = lambda i, s, qt, kt, *_: (i // group, kt[s], 0)
    in_specs = [
        pl.BlockSpec((1, block_q, d), q_map),
        pl.BlockSpec((1, block_k, d), kv_map),
        pl.BlockSpec((1, block_k, dv), kv_map),
    ]
    args = [q, k, v]
    if bias is not None:
        args.append(_pad_to(bias, 1, block_k)[:, None, :])  # (bn, 1, sk)
        in_specs.append(pl.BlockSpec(
            (1, 1, block_k), lambda i, s, qt, kt, *_: (i, 0, kt[s])))
    else:
        def kern(*refs, kern=kern):
            kern(*refs[:7], None, *refs[7:])

    o, lse = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(bn, qt.shape[0]),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, block_q, dv), q_map),
                pl.BlockSpec((1, block_q, _LANES), q_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, dv), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((bn, sq, dv), q.dtype),
            jax.ShapeDtypeStruct((bn, sq, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(qt), jnp.asarray(kt), jnp.asarray(bits),
      jnp.asarray(meta), *args)
    return o[:, :sq0], lse


# What the one-pass backward may hold in VMEM: Mosaic's own grant to a
# kernel on a TPU v5e. Nothing more is asked for (`vmem_limit_bytes`).
_BWD_VMEM = 16 * 2 ** 20


def _tile_bytes(rows, cols, itemsize):
    """A (rows, cols) buffer as VMEM holds it: whole (8 x 32 bit, 128)
    tiles."""
    sub = 8 * 4 // itemsize
    return (-(-rows // sub) * sub) * (-(-cols // _LANES) * _LANES) * itemsize


def _bwd_vmem_bytes(rows, d, itemsize, block_q, block_k):
    """The bytes of VMEM the one-pass backward needs of a head whose dq
    holds `rows` (padded) rows: every block twice (the pipeline's two
    buffers), the scratch once, and the float32 (block_k, block_q) values
    of a pair (the scores, p, dp, ds and a mask: six at once at most). A
    key's bias and its gradient are counted whether or not one is given."""
    qd = _tile_bytes(block_q, d, itemsize)
    kd = _tile_bytes(block_k, d, itemsize)
    key = _tile_bytes(block_k, _LANES, 4)
    stats = _tile_bytes(block_q, _LANES, 4) + _tile_bytes(1, block_q, 4)
    blocks = 2 * qd + stats + 4 * kd + 2 * key    # q do lse delta k v dk dv b db
    blocks += _tile_bytes(rows, d, itemsize)      # dq, all the call's rows
    scratch = _tile_bytes(rows, d, 4) + 2 * _tile_bytes(block_k, d, 4) + key
    return 2 * blocks + scratch + 6 * block_q * block_k * 4


def backward_span_rows(sq, sk, d, dtype):
    """The query rows ONE call of the tiled backward takes of (sq, sk) rows
    of width d: a pure function of what the call can see. All `sq` where a
    head's float32 dq and its block in `dtype` fit `_BWD_VMEM` beside the
    tiles (`_bwd_vmem_bytes`; at tiles of 512 up to 6,144 rows in bfloat16
    and 3,072 in float32, at any d up to 128); a longer sequence in spans of
    that many rows, a call each, every call the same kernel over its span's
    rows and the keys they attend, `dk`, `dv` and `db` summed over the spans
    in float32."""
    block_q, block_k = _pick_blocks(sq, sk)
    itemsize = jnp.dtype(dtype).itemsize
    unit = max(block_q, block_k)         # a span starts on a tile of both
    rows = -(-sq // unit) * unit
    while rows > unit and _bwd_vmem_bytes(
            rows, d, itemsize, block_q, block_k) > _BWD_VMEM:
        rows -= unit
    return rows


def _bwd_span(q, k, v, bias, lse, dl, do, causal, sm_scale, interpret,
              blocks, row0, kv_dtype):
    """The one Mosaic call (the comment above `_bwd_kernel`) over the query
    rows it is handed, rows `row0` on of the sequence, and the keys from 0.
    q, do: (bn, rows, d); lse (bn, rows_pad, 128) and dl (bn, 1, rows_pad)
    padded to the tile. Returns dq as q, dk and dv in `kv_dtype`, db (bn,
    sk) float32 or None."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bn, sq0, d = q.shape
    sk0 = k.shape[1]
    block_q, block_k = blocks
    q = _pad_to(q, 1, block_q)
    do = _pad_to(do, 1, block_q)
    k = _pad_to(k, 1, block_k)
    v = _pad_to(v, 1, block_k)
    sq, sk = q.shape[1], k.shape[1]
    qt, kt, bits = _bwd_walk(sq // block_q, sk // block_k, block_q, block_k,
                             causal, row0)

    q_map = lambda i, s, qt, kt, bits: (i, qt[s], 0)
    row_map = lambda i, s, qt, kt, bits: (i, 0, qt[s])
    kv_map = lambda i, s, qt, kt, bits: (i, kt[s], 0)
    q_spec = pl.BlockSpec((1, block_q, d), q_map)
    kv_spec = pl.BlockSpec((1, block_k, d), kv_map)
    key_spec = pl.BlockSpec((1, block_k, _LANES), kv_map)

    kern = functools.partial(
        _bwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, kv_len=sk0, row0=row0)
    args, in_specs = [q, k, v], [q_spec, kv_spec, kv_spec]
    out_specs = [pl.BlockSpec((1, sq, d), lambda i, s, *_: (i, 0, 0)),
                 kv_spec, kv_spec]
    out_shape = [jax.ShapeDtypeStruct((bn, sq, d), q.dtype),
                 jax.ShapeDtypeStruct((bn, sk, d), kv_dtype),
                 jax.ShapeDtypeStruct((bn, sk, d), kv_dtype)]
    scratch = [pltpu.VMEM((sq, d), jnp.float32),
               pltpu.VMEM((block_k, d), jnp.float32),
               pltpu.VMEM((block_k, d), jnp.float32)]
    if bias is not None:
        # a key's bias and its grad ride lane-padded, a column a key
        args.append(jnp.broadcast_to(
            _pad_to(bias, 1, block_k).astype(jnp.float32)[:, :, None],
            (bn, sk, _LANES)))
        in_specs.append(key_spec)
        out_specs.append(key_spec)
        out_shape.append(jax.ShapeDtypeStruct((bn, sk, _LANES), jnp.float32))
        scratch.append(pltpu.VMEM((block_k, _LANES), jnp.float32))
    else:
        def kern(*refs, kern=kern):
            # no bias: neither its block, nor db's, nor db's scratch
            kern(*refs[:6], None, *refs[6:12], None, *refs[12:], None)
    args += [do, lse, dl]
    in_specs += [q_spec, pl.BlockSpec((1, block_q, _LANES), q_map),
                 pl.BlockSpec((1, 1, block_q), row_map)]

    outs = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bn, qt.shape[0]),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd",
    )(jnp.asarray(qt), jnp.asarray(kt), jnp.asarray(bits), *args)
    dq, dk, dv = outs[:3]
    db = outs[3][:, :sk0, 0] if bias is not None else None
    return dq[:, :sq0], dk[:, :sk0], dv[:, :sk0], db


# jitted INLINE: a program that unrolls its layers traces the call and its
# kernel once and replays the equations at each layer (tracing the kernel a
# layer was seconds of a training step's set-up on four chips: PERF.md, PR
# 48), and what the compiler is handed holds no call: the program is the one
# an unjitted function would give
@functools.partial(jax.jit, inline=True,
                   static_argnames=("causal", "sm_scale", "interpret"))
def _flash_bwd_call(q, k, v, bias, o, lse, do, causal, sm_scale, interpret):
    """The tiled backward. q, o, do: (bn, sq, d); k, v: (bn, sk, d); bias
    (bn, sk) or None; lse: lane-padded (bn, sq_pad, 128) from _flash_call.
    Returns dq, dk, dv and db (bn, sk) or None. ONE Mosaic call where
    `backward_span_rows` gives all the rows, else one a span of them."""
    bn, sq0, d = q.shape
    sk0 = k.shape[1]
    blocks = _pick_blocks(sq0, sk0)
    span = backward_span_rows(sq0, sk0, d, q.dtype)

    # delta as compact rows, (bn, 1, sq): a pair broadcasts them down its
    # (block_k, block_q) scores; lse stays as the forward left it
    dl = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    dl = _pad_to(dl, 1, blocks[0])[:, None, :]
    if span >= sq0:
        # every key rides along: one above every row (causal, sk > sq)
        # meets masks alone and comes back zero
        return _bwd_span(q, k, v, bias, lse, dl, do, causal, sm_scale,
                         interpret, blocks, 0, k.dtype)

    dq = []
    dk = jnp.zeros((bn, sk0, d), jnp.float32)
    dv = jnp.zeros((bn, sk0, d), jnp.float32)
    db = None if bias is None else jnp.zeros((bn, sk0), jnp.float32)
    for r0 in range(0, sq0, span):
        r1 = min(r0 + span, sq0)
        # the keys a span attends: causal, those up to its last row
        ks = min(sk0, r1) if causal else sk0
        pad = -(-r1 // blocks[0]) * blocks[0]
        dq_s, dk_s, dv_s, db_s = _bwd_span(
            q[:, r0:r1], k[:, :ks], v[:, :ks],
            None if bias is None else bias[:, :ks], lse[:, r0:pad],
            dl[:, :, r0:pad], do[:, r0:r1], causal, sm_scale, interpret,
            blocks, r0, jnp.float32)
        dq.append(dq_s)
        dk = dk.at[:, :ks].add(dk_s)
        dv = dv.at[:, :ks].add(dv_s)
        if bias is not None:
            db = db.at[:, :ks].add(db_s)
    return (jnp.concatenate(dq, axis=1), dk.astype(k.dtype),
            dv.astype(v.dtype), db)


# ---------------------------------------------------------------------------
# custom-vjp public entry
# ---------------------------------------------------------------------------

def _to_bn(x):
    b, s, n, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * n, s, d)


def _from_bn(x, b, n):
    bn, s, d = x.shape
    return x.reshape(b, n, s, d).transpose(0, 2, 1, 3)


def _bias_to_bn(bias, b, n, sk):
    """Accepts (b, 1, 1, sk) / (b, sk) per-key additive bias → (b*n, sk)."""
    bias = bias.reshape(b, -1)[:, -sk:]
    return jnp.repeat(bias, n, axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def flash_attention(q, k, v, bias, causal, sm_scale, interpret):
    o, _ = _flash_fwd(q, k, v, bias, causal, sm_scale, interpret)
    return o


def _flash_fwd(q, k, v, bias, causal, sm_scale, interpret):
    b, sq, n, d = q.shape
    sk = k.shape[1]
    bb = None if bias is None else _bias_to_bn(bias, b, n, sk)
    call = _small_call if _small_ok(sq, sk) else _flash_call
    q_bn, k_bn, v_bn = _to_bn(q), _to_bn(k), _to_bn(v)
    o, lse = call(q_bn, k_bn, v_bn, bb, causal, sm_scale, interpret)
    # residuals stay in the KERNEL's (b*n, s, d) layout: the backward
    # otherwise re-relayouts q/k/v from (b,s,n,d) — 3 of the ~6
    # full-tensor copies the r3 grid blamed for the s=128 loss
    # (BASELINE.md r3; VERDICT r3 item 6)
    return _from_bn(o, b, n), (q_bn, k_bn, v_bn, bias, o, lse, b, n)


def _flash_bwd(causal, sm_scale, interpret, res, g):
    q_bn, k_bn, v_bn, bias, o_bn, lse, b, n = res
    bn, sq, d = q_bn.shape
    sk = k_bn.shape[1]
    bb = None if bias is None else _bias_to_bn(bias, b, n, sk)
    bwd = _small_bwd_call if _small_ok(sq, sk) else _flash_bwd_call
    dq, dk, dv, db_bn = bwd(
        q_bn, k_bn, v_bn, bb, o_bn, lse, _to_bn(g),
        causal, sm_scale, interpret)
    db = None
    if bias is not None:
        # db_bn: (b*n, sk) -> sum heads -> original (per-key) bias shape
        db = db_bn.reshape(b, n, sk).sum(axis=1).reshape(bias.shape) \
            .astype(bias.dtype)
    return _from_bn(dq, b, n), _from_bn(dk, b, n), _from_bn(dv, b, n), db


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def flash_dispatch(q, k, bias=None, impl: Optional[str] = None):
    """The fwd/bwd-shared dispatch decision: (use_flash, interpret).

    Factored out so an op-level grad can replay the SAME choice the forward
    made and drive the Pallas backward from saved residuals (out + lse)
    instead of re-running the forward kernel — XLA does not CSE custom
    calls, so a vjp-replayed flash forward is a real second kernel launch.
    """
    if impl is None:
        impl = os.environ.get("FLAGS_attention_impl", "")
    flag_ok = impl in ("", "auto", "flash")
    platform = jax.default_backend()
    on_tpu = platform == "tpu"
    # flash supports only per-key biases: (b, sk) or (b, 1, 1, sk)
    bias_ok = bias is None or bias.ndim == 2 or (
        bias.ndim == 4 and bias.shape[1] == 1 and bias.shape[2] == 1)
    shapes_ok = (q.shape[-1] % 8 == 0 and q.shape[1] % 8 == 0
                 and k.shape[1] % 128 == 0)
    # dispatch by shape, the way cuDNN picks algos (BASELINE.md r3 grid,
    # re-measured after the separate-q/k/v-projection change): s=128
    # XLA's fused composition wins (52.0% vs 51.4% MFU); s=256 is a tie
    # within run variance (einsum 44.3 vs kernel 43.9); at s=512 the
    # batched single-pass kernel wins big (41.2% vs 32.0%, also beating
    # the r2 tiled kernel's 37.0). impl='flash' still forces the kernel.
    long_enough = k.shape[1] >= 256
    if impl == "flash" and not bias_ok:
        raise ValueError(
            "flash attention requires a per-key bias of shape (b, sk) or "
            f"(b, 1, 1, sk); got {bias.shape}. Use impl='xla' for general "
            "biases.")
    use = impl == "flash" or (flag_ok and on_tpu and bias_ok and shapes_ok
                              and long_enough and impl != "xla")
    if use and platform not in ("tpu", "cpu"):
        # the Pallas interpreter is a CPU test facility; anywhere else a
        # forced kernel would run interpreted and pass for the real one
        raise RuntimeError(
            "impl='flash' compiles for TPU (Mosaic) and interprets on "
            f"CPU for tests; the active backend is {platform!r}")
    return use, platform == "cpu"


# a serving prefill's longest one-tile bucket: one sequence gives the
# grid only `heads` programs a layer, so a tile per head beats many small
# ones (TPU v5e, 25 heads x 64, ms a layer: 768 rows as 128 x 128 tiles
# 0.284, as one tile 0.089; 1,024 rows as 512 x 512 tiles 0.127, as one
# 0.128), and (1024, 1024) float32 scores still fit the kernel's VMEM
_ONE_TILE_ROWS = 1024


# rows from which a whole triangle is walked in tiles of 1,024 (taken in
# chunks of _CHUNK columns): half the K and V fetches and half the scratch
# traffic a product (TPU v5e, a head of 192/192/128, us at 512 / at 1,024:
# 2,048 rows 15.0 / 16.2, 4,096 53.9 / 52.0, 8,192 199 / 182, 16,384
# holding 15,360 683 / 601); a band of 1,024 stays at 512 (117 / 124: it
# would compute 2,048 columns a row)
_LARGE_TILE_ROWS = 8192


def _causal_rows_blocks(rows, window=None):
    """The tiles of a `rows`-row sequence of `flash_causal_rows`."""
    if rows <= _ONE_TILE_ROWS:
        return rows, rows
    if window is None and rows >= _LARGE_TILE_ROWS and rows % 1024 == 0:
        return 1024, 1024
    return _pick_blocks(rows, rows)


def causal_rows_tiles(rows, length=None, window=None):
    """(visited, in the bucket): the (query tile, KV tile) pairs a head of
    `flash_causal_rows` computes of a `rows`-row bucket that holds `length`
    real rows (None: all), and those of the bucket whole. Host integers
    from the kernel's own walk (`_spans`), for the engine's counts."""
    tile = _causal_rows_blocks(rows, window)[0]
    nq = -(-rows // tile)
    _, n = _spans(nq, tile, tile, True, window, rows)
    live = nq if length is None else min(nq, -(-int(length) // tile))
    return int(n[:live].sum()), int(n.sum())


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret",
                                             "window", "block"))
def _causal_rows_call(q, k, v, length, sm_scale, interpret, window=None,
                      block=None):
    # jitted like ops/paged_attention's calls: a program that unrolls its
    # layers traces and lowers the kernel once and calls it from each
    o, _ = _flash_call(
        q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1), None, True,
        sm_scale, interpret, blocks=_causal_rows_blocks(q.shape[0], window),
        group=q.shape[1] // k.shape[1], window=window, length=length,
        block=block)
    return o.swapaxes(0, 1)


def flash_causal_rows(q, k, v, sm_scale, window=None, length=None,
                      block=None):
    """Causal self-attention of ONE sequence's rows by the tiled flash
    forward, for a serving prefill: q (rows, heads, d), k (rows, kv_heads,
    d), v (rows, kv_heads, dv) as the projections leave them (kv_heads
    divides heads: query head i reads KV head i // (heads / kv_heads),
    shared by the index map; dv may differ from d), row i attending over
    rows 0..i, or with `window` over the last `window` of them, itself
    counted; returns (rows, heads, dv). No residuals, no backward.

    `length` (a traced int32 scalar; None: `rows`) is the prompt's real
    row count inside its bucket. The kernel walks only the tiles that hold
    work: at or below the diagonal, inside the window, below `length`.
    Rows at or past `length` come back ZERO, never what the buffer held.

    `block` (None: causal) makes the mask BLOCK-causal for a model that
    generates by diffusion over blocks: row i attends rows 0 .. i | (block
    - 1), its own block of `block` rows whole. A power of two that divides
    the tiles and `length`; only the diagonal tiles' mask changes.

    Up to _ONE_TILE_ROWS the sequence is one tile a head: one visit
    (`_pick_blocks` would cut 768 rows, no multiple of 512, into 36 tiles
    of 128 x 128); longer ones take `_pick_blocks`' tiles, and a whole
    triangle from _LARGE_TILE_ROWS rows tiles of 1,024. Compiled by
    Mosaic on a TPU backend, interpreted on the CPU (a test facility), an
    error on any other backend, like `flash_dispatch`'s forced kernel."""
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(
            "flash_causal_rows compiles for TPU (Mosaic) and interprets "
            f"on CPU for tests; the active backend is {platform!r}")
    return _causal_rows_call(q, k, v, length, float(sm_scale),
                             platform == "cpu",
                             window=None if window is None else int(window),
                             block=None if block is None else int(block))


def attention(q, k, v, bias=None, causal: bool = False,
              sm_scale: Optional[float] = None, impl: Optional[str] = None):
    """Dispatching fused attention. impl: None (auto) | 'flash' | 'xla'.

    bias, when given to the flash path, must be per-key additive
    (broadcastable from (b, 1, 1, sk)); arbitrary (b, n, sq, sk) biases fall
    back to the XLA reference.
    """
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    use_flash, interpret = flash_dispatch(q, k, bias, impl)
    if use_flash:
        return flash_attention(q, k, v, bias, causal, float(sm_scale),
                               interpret)
    return mha_reference(q, k, v, bias, causal, sm_scale)


def attention_fwd_lse(q, k, v, bias=None, causal: bool = False,
                      sm_scale: Optional[float] = None,
                      impl: Optional[str] = None):
    """Forward returning (out, lse) for op-level saved-residual backward.

    lse is the kernel's (b*n, sq) f32 row log-sum-exp on the flash path,
    None on the XLA path (whose replayed backward is pure ops — CSE-free).
    """
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    use_flash, interpret = flash_dispatch(q, k, bias, impl)
    if not use_flash:
        return mha_reference(q, k, v, bias, causal, sm_scale), None
    o, (_, _, _, _, o_bn, lse, _, _) = _flash_fwd(
        q, k, v, bias, causal, float(sm_scale), interpret)
    return o, lse


def attention_bwd_saved(q, k, v, bias, out, lse, g, causal: bool,
                        sm_scale: Optional[float] = None,
                        impl: Optional[str] = None):
    """Flash backward from saved (out, lse) — no forward recompute.
    Only valid when the forward's flash_dispatch said use_flash.
    Returns (dq, dk, dv) in the (b, s, n, d) layout."""
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    _, interpret = flash_dispatch(q, k, bias, impl)
    b, sq, n, d = q.shape
    res = (_to_bn(q), _to_bn(k), _to_bn(v), bias, _to_bn(out), lse, b, n)
    dq, dk, dv, _ = _flash_bwd(causal, float(sm_scale), interpret, res, g)
    return dq, dk, dv
