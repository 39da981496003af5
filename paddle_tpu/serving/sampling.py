"""The serving sampler's PRNG: a counter-based threefry2x32 and a Gumbel-max
draw, plain vectorized uint32/float32 math that the engine's fused decode
loop and the scheduler's admission sampler share (it belongs to no
model)."""

from __future__ import annotations

__all__ = ["threefry2x32", "sample_key", "sample_split", "sample_fold",
           "sample_gumbel"]

# -- serving sampler PRNG ---------------------------------------------------
#
# The fused decode loop draws per-slot samples VMAPPED over the slot
# dimension, and resumed/preempted/late-admitted sequences must reproduce
# their streams bit-exactly wherever and whenever they land. The fleet's
# default `rbg` PRNG cannot provide that: under vmap it generates the
# whole batch's bits from ONE key (row r of a vmapped draw follows
# keys[0]'s stream, not keys[r]'s — verified empirically; jax documents
# rbg as not vmap-invariant), so a slot's draw silently depends on every
# OTHER slot's key chain and on its own row index. The serving sampler
# therefore rolls its own counter-based threefry2x32 (the Random123
# function jax's default CPU PRNG is built on, bit-for-bit) and draws via
# Gumbel-max — plain vectorized uint32/float32 ops with no batching rule
# at all, so a row's sample is a pure function of (its key, its logits,
# its temperature): vmap-invariant, slot-independent, and
# schedule-independent by construction. Cost: one 20-round hash per
# lane per draw — noise next to the model matmuls (the rbg default
# exists for DROPOUT-mass generation, not one categorical per slot).

def threefry2x32(key, x0, x1):
    """Random123 threefry2x32 (20 rounds), matching jax's reference
    implementation bit-for-bit. key: (..., 2) uint32 (leading dims
    broadcast); x0/x1: uint32 counters, broadcastable against the key's
    leading dims. Returns (y0, y1) uint32."""
    import jax.numpy as jnp

    k0 = key[..., 0]
    k1 = key[..., 1]
    k2 = k0 ^ k1 ^ jnp.uint32(0x1BD11BDA)
    x0 = (x0 + k0).astype(jnp.uint32)
    x1 = (x1 + k1).astype(jnp.uint32)

    def rotl(v, d):
        return (v << jnp.uint32(d)) | (v >> jnp.uint32(32 - d))

    rots = ((13, 15, 26, 6), (17, 29, 16, 24))
    ks = (k0, k1, k2)
    for g in range(5):
        for r in rots[g % 2]:
            x0 = (x0 + x1).astype(jnp.uint32)
            x1 = rotl(x1, r) ^ x0
        x0 = (x0 + ks[(g + 1) % 3]).astype(jnp.uint32)
        x1 = (x1 + ks[(g + 2) % 3] + jnp.uint32(g + 1)).astype(jnp.uint32)
    return x0, x1


def sample_key(seed):
    """Pack a (traced or static) integer seed into a (2,) uint32
    sampler key — the serving twin of PRNGKey(seed)."""
    import jax.numpy as jnp

    seed = jnp.asarray(seed)
    return jnp.stack([jnp.zeros((), jnp.uint32),
                      seed.astype(jnp.uint32)])


def sample_split(key):
    """Advance a sampler key one step: counter (1, 0) of the current
    key's threefry stream. Draws use counter (0, lane) — disjoint, so a
    key's draw never aliases its successor's."""
    import jax.numpy as jnp

    y0, y1 = threefry2x32(key, jnp.uint32(1), jnp.uint32(0))
    return jnp.stack([y0, y1], axis=-1)


def sample_fold(key, j):
    """The key of lane `j` (uint32) of a draw that takes several rows from
    one key (a block of positions a slot a pass): counter (2, j) of the
    key's stream, disjoint from the split's (1, 0) and the draws' (0, .)."""
    import jax.numpy as jnp

    y0, y1 = threefry2x32(key, jnp.uint32(2), jnp.asarray(j, jnp.uint32))
    return jnp.stack([y0, y1], axis=-1)


def sample_gumbel(key, n):
    """(n,) standard-Gumbel draws from `key`'s counters (0, 0..n-1) —
    argmax(logits/temp + gumbel) IS a categorical(softmax(logits/temp))
    draw (the Gumbel-max trick, the same construction jax.random.
    categorical uses). u is centered on the 2^-24 lattice so log(u) and
    log(-log(u)) are always finite."""
    import jax.numpy as jnp

    lanes = jnp.arange(n, dtype=jnp.uint32)
    bits, _ = threefry2x32(key, jnp.uint32(0), lanes)
    u = ((bits >> jnp.uint32(8)).astype(jnp.float32)
         + jnp.float32(0.5)) * jnp.float32(2.0 ** -24)
    return -jnp.log(-jnp.log(u))
