"""A prompt's delta-rule recurrence (Kimi Delta Attention) in chunks, the
state in VMEM from the first chunk to the last.

    S' = exp(g)[:, None] * S;  S = S' + outer(k, beta (v - S'^T k));
    o = S^T q                                  (a row a head, float32)

over T rows of ONE sequence, algebraically `models/_delta.kda_step` T
times, in the chunked form `models/_delta.kda_chunked` derives: with
the cumulative decay G_r = sum_{i<=r} g_i inside a chunk of 64 rows, the
delta rule's corrections solve the unit-lower-triangular system
(I + diag(beta) A) U = diag(beta) (V - K+ S0), A_ji = sum_c k_j k_i
exp(G_j - G_i) (i < j), K+ = k exp(G); O = Q+ S0 + B U with B_rj = sum_c
q_r k_j exp(G_r - G_j) (j <= r); S_C = exp(G_C) S0 + (k exp(G_C - G))^T U.
EVERY EXPONENT IS <= 0: between sub-chunks of 16 rows the differences are
taken against the LATER sub-chunk's first row (two factors, each at most 1,
and a product on the MXU), inside a sub-chunk elementwise, a column at a
time. Every product is float32 at `HIGHEST`, the state float32.

q, k, v, g come as `(T, heads x 128)`, the layout the mixer's activations
have, and are read in `(64, heads-a-step x 128)` blocks indexed (chunk,
head group); `o` is written the same way: nothing is transposed in HBM.
beta rides as a small `(head groups, T, heads a step)`. The grid is (head
groups, chunks), the chunk axis the carried one: a head's state lives
TRANSPOSED, (value, key), in a VMEM scratch (the decay of a key channel is
then a row's broadcast), comes in (or starts at zero) at chunk 0 and goes
out behind the last chunk. Two heads share every matrix of a chunk's rows:
their 64 x 64 Gram matrices are the diagonal blocks of ONE 128 x 128, so
the cumulative sum, the triangular system and B U fill the MXU's array, and
the several heads of a grid step are independent chains for the scheduler.

THE TRIANGULAR SYSTEM, (I + N) X = rhs with N strictly lower, is solved as
the published kernels solve it: FORWARD SUBSTITUTION inside the eight
16-row diagonal blocks of the pair (fifteen rank-one updates of all eight
at once, on the VPU: the blocks' inverses Y), then the blocks MERGED on the
MXU by the exact inverse of a block triangle, [[A, 0], [C, B]]^-1 = [[A^-1,
0], [-B^-1 C A^-1, B^-1]], as Y <- Y - Y C Y, 16 -> 32 -> 64 rows (four
products), and X = Y rhs. No power of N is ever formed: with correlated
keys, slow decay and beta near 1 (a run of one token) N is c times the
all-ones triangle and its powers pass 1e9 where the inverse's entries stay
under 1, so a Neumann series or its doubling loses every digit in float32;
substitution does not.

Chunks wholly past `real_len` are NOT VISITED (the count of live chunks is
scalar-prefetched: their operands are not fetched, their `o` rows are
written as zeros, the state passes them by). Rows past `real_len` inside a
live chunk must come with g = 0 and beta = 0 (the caller's: they then
leave the state as it was). Mosaic on a TPU backend, interpreted on the
CPU (tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["kda_chunk", "CHUNK", "SUB"]

CHUNK = 64
SUB = 16
_SUBS = CHUNK // SUB
_PAIR = 2 * CHUNK            # two heads' rows: one MXU tile of rows


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


_NT = (((1,), (1,)), ((), ()))     # a @ b.T


def _in_half(x, half):
    """x (64, d) as the pair's rows (128, d): head `half`'s 64, zeros for
    the other head's."""
    nothing = jnp.zeros_like(x)
    return jnp.concatenate([nothing, x] if half else [x, nothing], 0)


def _of_each_block(x, i):
    """Row i of every 16-row block of x (rows, w), each over its block's 16
    rows: a broadcast inside the registers."""
    rows, w = x.shape
    picked = x.reshape(rows // SUB, SUB, w)[:, i:i + 1]
    return jnp.broadcast_to(picked, (rows // SUB, SUB, w)).reshape(rows, w)


def _grams(half, q, k, G):
    """One head's two Gram matrices. BETWEEN sub-chunks as (64, 128) strips
    whose columns are this head's HALF of the pair's 128: A's rows are k's
    (its diagonal 16 x 16 blocks left zero), B's rows are q's (whole, i <=
    j). INSIDE a sub-chunk A comes as its sixteen columns, `a_cols[i]` (64,
    1) the column i of all four diagonal blocks, rows j > i alone."""
    dk = k.shape[-1]
    # a sub-chunk's rows decayed from its first row on
    own = jnp.exp(G - _of_each_block(G, 0))
    own_k, own_q = k * own, q * own
    row = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, dk), 0)
    a_rows = [jnp.zeros((SUB, _PAIR), jnp.float32)]
    b_rows = list(a_rows)
    for s in range(1, _SUBS):
        # the earlier sub-chunks' keys decayed up to this one's first row
        lo = s * SUB
        back = k * jnp.exp(jnp.where(row < lo, G[lo:lo + 1] - G, -jnp.inf))
        off = _dot(jnp.concatenate([own_k[lo:lo + SUB], own_q[lo:lo + SUB]],
                                   0), _in_half(back, half), _NT)
        a_rows.append(off[:SUB])
        b_rows.append(off[SUB:])
    A, B = jnp.concatenate(a_rows, 0), jnp.concatenate(b_rows, 0)
    # inside a sub-chunk, a COLUMN i of all four at a time: rows j >= i,
    # the exponent G_j - G_i <= 0, the sum over the key channels a lane
    # reduce; B's is put where the column lies among the pair's 128
    in_sub = row % SUB
    below = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, 1), 0) % SUB
    col = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, _PAIR), 1)
    first = half * CHUNK + (jax.lax.broadcasted_iota(
        jnp.int32, (CHUNK, _PAIR), 0) // SUB) * SUB
    a_cols = []
    for i in range(SUB):
        e = jnp.exp(jnp.where(in_sub >= i, G - _of_each_block(G, i),
                              -jnp.inf)) * _of_each_block(k, i)
        a_cols.append(jnp.where(below > i,
                                jnp.sum(k * e, -1, keepdims=True), 0.0))
        B = jnp.where(col == first + i, jnp.sum(q * e, -1, keepdims=True), B)
    return A, a_cols, B


def _kernel(live_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, *rest, heads, dk,
            dv, carried):
    """One chunk of `heads` heads (an even count: pairs). The state
    scratch st (heads, dv, dk) is S TRANSPOSED."""
    from jax.experimental import pallas as pl
    if carried:
        s0_ref, o_ref, s_ref, st_scr = rest
    else:
        o_ref, s_ref, st_scr = rest
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _():
        for h in range(heads):
            st_scr[h] = s0_ref[h].T if carried \
                else jnp.zeros((dv, dk), jnp.float32)

    @pl.when(c >= live_ref[0])
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    @pl.when(c < live_ref[0])
    def _():
        r2 = jax.lax.broadcasted_iota(jnp.int32, (_PAIR, _PAIR), 0)
        c2 = jax.lax.broadcasted_iota(jnp.int32, (_PAIR, _PAIR), 1)
        same = (r2 >= CHUNK) == (c2 >= CHUNK)
        tril = jnp.where(same & (c2 <= r2), 1.0, 0.0)
        eye = jnp.where(r2 == c2, 1.0, 0.0)
        in_32 = r2 // (2 * SUB) == c2 // (2 * SUB)
        for p in range(heads // 2):
            pair = (2 * p, 2 * p + 1)
            k_l = [slice(h * dk, (h + 1) * dk) for h in pair]
            v_l = [slice(h * dv, (h + 1) * dv) for h in pair]
            G2 = _dot(tril, jnp.concatenate([g_ref[:, l] for l in k_l], 0))
            A, cols, B, rhs, q_plus, k_end, decay_end = [], [], [], [], [], \
                [], []
            for half, h in enumerate(pair):
                q, k = q_ref[:, k_l[half]], k_ref[:, k_l[half]]
                G = G2[half * CHUNK:(half + 1) * CHUNK]
                a, a_cols, b = _grams(half, q, k, G)
                beta = beta_ref[:, h:h + 1]                     # (64, 1)
                A.append(beta * a)
                cols.append([beta * a_col for a_col in a_cols])
                B.append(b)
                up = jnp.exp(G)
                rhs.append(beta * jnp.concatenate(
                    [v_ref[:, v_l[half]], k * up], 1))
                q_plus.append(q * up)
                last = G[CHUNK - 1:]
                k_end.append(k * jnp.exp(last - G))
                decay_end.append(jnp.exp(last))
            # (I + N)^-1, N = diag(beta) A strictly lower. Inside the eight
            # 16-row diagonal blocks by forward substitution on the
            # identity: once a block's row i is final, column i's entries
            # take it out of the rows below
            inv = eye
            for i in range(SUB - 1):
                inv = inv - jnp.concatenate([c[i] for c in cols], 0) \
                    * _of_each_block(inv, i)
            # the blocks merged, 16 -> 32 -> 64 rows: [[A, 0], [C, B]]^-1
            # = [[A^-1, 0], [-B^-1 C A^-1, B^-1]]
            N = jnp.concatenate(A, 0)
            for C in (jnp.where(in_32, N, 0.0), jnp.where(in_32, 0.0, N)):
                inv = inv - _dot(_dot(inv, C), inv)
            solved = _dot(inv, jnp.concatenate(rhs, 0))       # (128, dv + dk)
            U, from_state = [], []
            for half, h in enumerate(pair):
                rows = slice(half * CHUNK, (half + 1) * CHUNK)
                # w S and Q+ S in one product over the state
                both = _dot(jnp.concatenate(
                    [solved[rows, dv:], q_plus[half]], 0), st_scr[h], _NT)
                U.append(solved[rows, :dv] - both[:CHUNK])
                from_state.append(both[CHUNK:])
            U = jnp.concatenate(U, 0)                          # (128, dv)
            inside = _dot(jnp.concatenate(B, 0), U)
            U_t = U.T                                          # (dv, 128)
            for half, h in enumerate(pair):
                rows = slice(half * CHUNK, (half + 1) * CHUNK)
                o_ref[:, v_l[half]] = from_state[half] + inside[rows]
                st_scr[h] = decay_end[half] * st_scr[h] \
                    + _dot(U_t, _in_half(k_end[half], half))

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        for h in range(heads):
            s_ref[h] = st_scr[h].T


def heads_a_step(heads):
    """Heads a grid step: four (two pairs, two independent chains beside
    each other's products) where the head count has them, else two."""
    return 4 if heads % 4 == 0 else 2


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _call(live, q, k, v, g, beta, S0, *, heads, interpret):
    """q, k, g (T, n dk), v (T, n dv), beta (n / heads, T, heads), S0 (n,
    dk, dv) or None, live (1,) int32 the chunks to visit; T whole chunks.
    Returns (o (T, n dv), S (n, dk, dv))."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T = q.shape[0]
    groups = beta.shape[0]
    n = groups * heads
    dk, dv = q.shape[1] // n, v.shape[1] // n
    chunks = T // CHUNK

    def visited(c, live):
        # a dead chunk's operands are the last live chunk's: not fetched
        return jnp.maximum(jnp.minimum(c, live[0] - 1), 0)

    def rows(width):
        return pl.BlockSpec((CHUNK, heads * width),
                            lambda hg, c, live: (visited(c, live), hg))

    state = pl.BlockSpec((heads, dk, dv), lambda hg, c, live: (hg, 0, 0))
    in_specs = [rows(dk), rows(dk), rows(dv), rows(dk),
                pl.BlockSpec((None, CHUNK, heads),
                             lambda hg, c, live: (hg, visited(c, live), 0))]
    operands = [q, k, v, g, beta]
    if S0 is not None:
        in_specs.append(state)
        operands.append(S0)
    return pl.pallas_call(
        functools.partial(_kernel, heads=heads, dk=dk, dv=dv,
                          carried=S0 is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(groups, chunks),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((CHUNK, heads * dv),
                                    lambda hg, c, live: (c, hg)),
                       state],
            scratch_shapes=[pltpu.VMEM((heads, dv, dk), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((T, n * dv), jnp.float32),
                   jax.ShapeDtypeStruct((n, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="kda_chunk",
    )(live, *operands)


def kda_chunk(q, k, v, g, beta, S0=None, real_len=None):
    """The recurrence over T rows of one sequence: q, k, g (T, n, dk)
    float32, v (T, n, dv), beta (T, n), S0 (n, dk, dv) float32 or None
    (zeros), `real_len` a traced or a Python count of rows (None: T), the
    rows past it with g = 0 and beta = 0. n is even. Returns (o (T, n,
    dv) float32, zeros in a chunk wholly past `real_len`; S (n, dk, dv)
    at `real_len`; the chunks visited, int32)."""
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise RuntimeError("kda_chunk compiles for TPU (Mosaic) and "
                           "interprets on CPU for tests; the active backend "
                           f"is {platform!r}")
    T, n, dk = q.shape
    dv = v.shape[-1]
    if n % 2:
        raise ValueError(f"kda_chunk pairs heads: {n} is odd")
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    padded = -(-T // CHUNK) * CHUNK
    if padded != T:
        # rows of zeros (g = 0, beta = 0) leave the state as it was
        pad = ((0, padded - T), (0, 0), (0, 0))
        q, k, v, g = (jnp.pad(a, pad) for a in (q, k, v, g))
        beta = jnp.pad(beta, pad[:2])
    heads = heads_a_step(n)
    live = -(-jnp.minimum(jnp.asarray(T if real_len is None else real_len,
                                      jnp.int32), T) // CHUNK)
    o, S = _call(live.reshape(1), q.reshape(padded, n * dk),
                 k.reshape(padded, n * dk), v.reshape(padded, n * dv),
                 g.reshape(padded, n * dk),
                 beta.reshape(padded, n // heads, heads).transpose(1, 0, 2),
                 None if S0 is None else S0.astype(f32), heads=heads,
                 interpret=platform == "cpu")
    return o.reshape(padded, n, dv)[:T], S, live
