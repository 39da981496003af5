"""The DELTA RULE's recurrence in `jax.numpy`, one position and chunked, and
its two dispatches (kernel or XLA): what the delta-rule mixers share
(models/kimi_linear.py's Kimi Delta Attention, a decay a head and KEY
CHANNEL; models/qwen3_next.py's Gated DeltaNet, a SCALAR decay a head,
which is a channel decay whose channels agree; the cells
kimi-linear-longgen-offline and qwen3-next-longmix-offline).

    S' = exp(g)[:, None] S;  S = S' + beta outer(k, v - S'^T k);  o = S^T q

with S (key, value) float32 a head. `kda_step` is one position,
`kda_chunked` the same recurrence over a prompt in chunks (the CPU's path,
odd widths' and the tests' oracle); `step_blocks` moves every slot's state
block of a state group one position on (ops/kda_step.py or XLA's
gather-update-scatter), `scan` runs a prompt's rows (ops/kda_chunk.py or
`kda_chunked`); `note_prefill` / `prefill_paths_taken` keep, a config, which
path each traced prefill took. What a mixer brings itself: its projections,
convolution, activations, decay, gate and scopes.

Imports no model and, at module level, no jax.
"""

from __future__ import annotations

import weakref

from . import _recurrent

__all__ = ["KDA_CHUNK", "KDA_SUB", "KDA_PRECISION", "kda_step",
           "kda_chunked", "step_blocks", "scan", "note_prefill",
           "prefill_paths_taken"]

# Rows a chunk of the prefill's scan, and rows a sub-chunk inside which
# decays are taken elementwise (the published kernels' sizes).
KDA_CHUNK = 64
KDA_SUB = 16
# The chunked form's products are float32 at this precision: it is the
# recurrence to float32 rounding, and a state that four thousand rows of
# bfloat16 products built would be a state kept in a lower precision.
KDA_PRECISION = "highest"


def kda_step(S, q, k, v, g, beta):
    """The recurrence, one position: S (..., dk, dv) float32, q, k, g
    (..., dk), v (..., dv), beta (...,). Returns (S_t, o_t)."""
    import jax.numpy as jnp
    Sd = jnp.exp(g)[..., None] * S
    u = beta[..., None] * (v - jnp.sum(Sd * k[..., None], -2))
    S = Sd + k[..., None] * u[..., None, :]
    return S, jnp.sum(S * q[..., None], -2)


def kda_chunked(q, k, v, g, beta, S0=None, chunk=KDA_CHUNK, sub=KDA_SUB):
    """The recurrence over T positions of one sequence in chunks: q, k, g
    (T, n, dk) float32, v (T, n, dv), beta (T, n), S0 (n, dk, dv) or None
    (zeros). Returns (o (T, n, dv) float32, S_T). Algebraically
    `kda_step` T times. Inside a chunk of C rows with the cumulative
    decay G_r = sum_{i<=r} g_i: the delta rule's corrections U solve the
    unit-lower-triangular system (I + diag(beta) A) U = diag(beta) (V -
    K+ S0), A_ji = sum_c k_j k_i exp(G_j - G_i) for i < j, K+ = k
    exp(G); then O = Q+ S0 + B U with B_rj = sum_c q_r k_j exp(G_r - G_j)
    for j <= r, and S_C = exp(G_C) S0 + (k exp(G_C - G))^T U. Every
    exponent is <= 0: between sub-chunks of `sub` rows the differences
    are taken against the later sub-chunk's first row (two factors, each
    at most 1, and a product the MXU does), inside a sub-chunk
    elementwise. A row with beta = 0 and g = 0 leaves the state as it
    was."""
    import jax
    import jax.numpy as jnp
    hi = KDA_PRECISION
    T, n, dk = q.shape
    dv = v.shape[-1]
    sub = min(sub, chunk)
    C = min(chunk, -(-T // sub) * sub)
    if C % sub:
        raise ValueError(f"a chunk of {C} rows is not whole sub-chunks of "
                         f"{sub}")
    N, ns = -(-T // C), C // sub
    if N * C != T:
        pad = ((0, N * C - T), (0, 0), (0, 0))
        q, k, v, g = (jnp.pad(a, pad) for a in (q, k, v, g))
        beta = jnp.pad(beta, pad[:2])
    # (N, n, C, d): a chunk's rows next to the lanes' axis
    q, k, v, g = (a.reshape(N, C, n, -1).transpose(0, 2, 1, 3)
                  for a in (q, k, v, g))
    beta = beta.reshape(N, C, n).transpose(0, 2, 1)
    G = jnp.cumsum(g, 2)
    Gs = G.reshape(N, n, ns, sub, dk)
    ks = k.reshape(Gs.shape)
    # the rows of both Gram matrices, A's (k) and B's (q), side by side
    rows = jnp.stack([ks, q.reshape(Gs.shape)])          # (2,N,n,ns,sub,dk)
    # inside a sub-chunk, elementwise: sum_c r_j k_i exp(G_j - G_i), i <= j
    # (one reduce; the (sub, sub, dk) terms are never stored)
    low = jnp.tril(jnp.ones((sub, sub), bool))
    inside = jnp.exp(jnp.where(
        low[..., None], Gs[:, :, :, :, None] - Gs[:, :, :, None], -jnp.inf))
    diag = jnp.sum(rows[..., :, None, :] * ks[:, :, :, None] * inside, -1)
    # between sub-chunks, against the LATER one's first row: its own rows
    # decayed from there, the earlier ones' keys decayed up to there
    own = rows * jnp.exp(Gs - Gs[:, :, :, :1])
    blocks = []
    for rb in range(ns):
        parts = []
        if rb:
            back = ks[:, :, :rb] * jnp.exp(Gs[:, :, rb, None, :1]
                                           - Gs[:, :, :rb])
            parts.append(jnp.einsum(
                "xbhjc,bhic->xbhji", own[:, :, :, rb],
                back.reshape(N, n, rb * sub, dk), precision=hi))
        parts.append(diag[:, :, :, rb])
        if rb < ns - 1:
            parts.append(jnp.zeros(diag.shape[:3]
                                   + (sub, (ns - 1 - rb) * sub), diag.dtype))
        blocks.append(jnp.concatenate(parts, -1))
    grams = jnp.concatenate(blocks, -2)                   # (2, N, n, C, C)
    A, B = jnp.tril(grams[0], -1), grams[1]
    k_plus = k * jnp.exp(G)
    q_plus = q * jnp.exp(G)
    k_end = k * jnp.exp(G[:, :, -1:] - G)
    L = jnp.eye(C, dtype=A.dtype) + beta[..., None] * A
    rhs = beta[..., None] * jnp.concatenate([v, k_plus], -1)
    solved = jax.lax.linalg.triangular_solve(
        L, rhs, left_side=True, lower=True, unit_diagonal=True)
    u_v, w = solved[..., :dv], solved[..., dv:]
    decay_end = jnp.exp(G[:, :, -1])                          # (N, n, dk)

    def carry(S, c):
        u_v, w, q_plus, B, k_end, decay_end = c
        U = u_v - jnp.einsum("hck,hkv->hcv", w, S, precision=hi)
        o = jnp.einsum("hck,hkv->hcv", q_plus, S, precision=hi) \
            + jnp.einsum("hrj,hjv->hrv", B, U, precision=hi)
        S = decay_end[..., None] * S \
            + jnp.einsum("hck,hcv->hkv", k_end, U, precision=hi)
        return S, o

    if S0 is None:
        S0 = jnp.zeros((n, dk, dv), jnp.float32)
    S, o = jax.lax.scan(carry, S0, (u_v, w, q_plus, B, k_end, decay_end))
    return o.transpose(0, 2, 1, 3).reshape(N * C, n, dv)[:T], S


def step_blocks(state, lg, ids, done, q, k, v, g, beta, path):
    """Every slot's state, block `ids` (S,) of layer `lg` of the state
    arena `state`, read, moved one position on and written ONCE (a frozen
    slot's to scratch), by the kernel ops/kda_step.py (`path` "kernel") or
    by XLA's gather-update-scatter. q, k, g (S, n, dk), v (S, n, dv), beta
    (S, n), float32. Returns (o (S, n, dv) float32, the arena)."""
    if path == "kernel":
        from ..ops.kda_step import kda_step_blocks
        return kda_step_blocks(state, lg, ids, done, q, k, v, g, beta)
    S, o = kda_step(_recurrent.read_blocks(state, lg, ids), q, k, v, g, beta)
    return o, _recurrent.write_blocks(state, lg, ids, done, S)


def scan(q, k, v, g, beta, real_len, path):
    """A prompt's recurrence from a zero state over its bucket's B rows,
    those at or past `real_len` with g = 0 and beta = 0: by the kernel
    ops/kda_chunk.py (`path` "kernel"), which passes by the chunks wholly
    past `real_len`, or by `kda_chunked`, which visits every chunk.
    Returns (o (B, n, dv) float32, S (n, dk, dv) at `real_len`, the
    chunks visited)."""
    if path == "kernel":
        from ..ops.kda_chunk import kda_chunk
        return kda_chunk(q, k, v, g, beta, real_len=real_len)
    o, S = kda_chunked(q, k, v, g, beta)
    return o, S, -(-q.shape[0] // KDA_CHUNK)


# {cfg: {bucket: path}}: what a leaf's `prefill_pages` took in each bucket it
# was traced for, for `engine.stats()["state"]` to report what RAN
_PREFILLS_TRACED = weakref.WeakKeyDictionary()


def note_prefill(cfg, bucket, path):
    """A prefill of `cfg` is being traced for `bucket` with its scan by
    `path` ("kernel" or "xla")."""
    _PREFILLS_TRACED.setdefault(cfg, {})[bucket] = path


def prefill_paths_taken(cfg, rule):
    """("kernel" if a traced prefill of `cfg` ran the kernel in some bucket
    else "xla", the buckets that did). Before any prefill is traced:
    `rule`, the leaf's verdict for a bucket of whole tiles, and no
    bucket."""
    traced = _PREFILLS_TRACED.get(cfg)
    if not traced:
        return rule, []
    kernel = sorted(b for b, path in traced.items() if path == "kernel")
    return ("kernel" if kernel else "xla"), kernel
