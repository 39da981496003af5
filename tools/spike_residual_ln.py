"""Spike: fused residual-add + LayerNorm (fwd + recompute-bwd) in Pallas vs
the XLA composition — the BERT memory-bound tail lever named in BASELINE.md
r1's decomposition (VERDICT r3 item 5).

The encoder step `x = LN(x + sublayer_out)` at BERT-base bench shapes is an
HBM-bound elementwise+row-reduce mix.  Strategy under test: one fused pass
computing s = x + r and the row-normalized output while saving ONLY the
per-row (mu, rstd) scalars; the backward recomputes s from x + r instead of
loading a saved activation, trading a cheap re-add for one less full-tensor
round trip.  XLA's schedule saves (x + r) for the backward, so

  XLA   fwd: read x, r        -> write s, out        (4 tensor passes)
        bwd: read s, dout     -> write ds            (3 passes)
  fused fwd: read x, r        -> write out           (3 passes)
        bwd: read x, r, dout  -> write ds            (4 passes)

— equal total traffic EXCEPT the fused form shifts a pass from fwd to bwd
and drops the 25 MB saved-activation residency.  The spike MEASURES whether
the fused schedule (and its dscale/dbias cross-block accumulation) beats
XLA's fusion anyway.  Accept = integrate behind FLAGS_layernorm_impl;
reject = record the table (spike_conv_bn methodology).

Run on the TPU:  python tools/spike_residual_ln.py
"""

import functools
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

EPS = 1e-5


def _make_fused(bm=256):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def fwd_kernel(x_ref, r_ref, sc_ref, b_ref, o_ref, mu_ref, rs_ref):
        s = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
        mu = jnp.mean(s, axis=1, keepdims=True)
        d = s - mu
        var = jnp.mean(d * d, axis=1, keepdims=True)
        rstd = jax.lax.rsqrt(var + EPS)
        o_ref[...] = (d * rstd * sc_ref[...] +
                      b_ref[...]).astype(o_ref.dtype)
        mu_ref[...] = mu
        rs_ref[...] = rstd

    def bwd_kernel(x_ref, r_ref, sc_ref, mu_ref, rs_ref, g_ref,
                   ds_ref, dsc_ref, db_ref, dsc_scr, db_scr):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            dsc_scr[...] = jnp.zeros_like(dsc_scr)
            db_scr[...] = jnp.zeros_like(db_scr)

        s = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
        mu = mu_ref[...]
        rstd = rs_ref[...]
        xhat = (s - mu) * rstd
        g = g_ref[...].astype(jnp.float32)
        gs = g * sc_ref[...]
        h = x_ref.shape[1]
        m1 = jnp.mean(gs, axis=1, keepdims=True)
        m2 = jnp.mean(gs * xhat, axis=1, keepdims=True)
        ds_ref[...] = ((gs - m1 - xhat * m2) * rstd).astype(ds_ref.dtype)
        dsc_scr[...] += jnp.sum(g * xhat, axis=0, keepdims=True)
        db_scr[...] += jnp.sum(g, axis=0, keepdims=True)

        @pl.when(i == pl.num_programs(0) - 1)
        def _fin():
            dsc_ref[...] = dsc_scr[...]
            db_ref[...] = db_scr[...]

    @jax.custom_vjp
    def fused_ln(x, r, scale, bias):
        out, _mu, _rs = _fwd_call(x, r, scale, bias)
        return out

    def _fwd_call(x, r, scale, bias):
        m, h = x.shape
        grid = (m // bm,)
        return pl.pallas_call(
            fwd_kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, h), lambda i: (i, 0)),
                pl.BlockSpec((bm, h), lambda i: (i, 0)),
                pl.BlockSpec((1, h), lambda i: (0, 0)),
                pl.BlockSpec((1, h), lambda i: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((bm, h), lambda i: (i, 0)),
                pl.BlockSpec((bm, 1), lambda i: (i, 0)),
                pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((m, h), x.dtype),
                jax.ShapeDtypeStruct((m, 1), jnp.float32),
                jax.ShapeDtypeStruct((m, 1), jnp.float32),
            ],
        )(x, r, scale.reshape(1, h).astype(jnp.float32),
          bias.reshape(1, h).astype(jnp.float32))

    def fwd_rule(x, r, scale, bias):
        out, mu, rs = _fwd_call(x, r, scale, bias)
        return out, (x, r, scale, mu, rs)

    def bwd_rule(res, g):
        import jax
        x, r, scale, mu, rs = res
        m, h = x.shape
        grid = (m // bm,)
        ds, dsc, db = pl.pallas_call(
            bwd_kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, h), lambda i: (i, 0)),
                pl.BlockSpec((bm, h), lambda i: (i, 0)),
                pl.BlockSpec((1, h), lambda i: (0, 0)),
                pl.BlockSpec((bm, 1), lambda i: (i, 0)),
                pl.BlockSpec((bm, 1), lambda i: (i, 0)),
                pl.BlockSpec((bm, h), lambda i: (i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((bm, h), lambda i: (i, 0)),
                pl.BlockSpec((1, h), lambda i: (0, 0)),
                pl.BlockSpec((1, h), lambda i: (0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((m, h), x.dtype),
                jax.ShapeDtypeStruct((1, h), jnp.float32),
                jax.ShapeDtypeStruct((1, h), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((1, h), jnp.float32),
                pltpu.VMEM((1, h), jnp.float32),
            ],
        )(x, r, scale.reshape(1, h).astype(jnp.float32), mu, rs, g)
        # residual add distributes the same grad to both branches
        return ds, ds, dsc.reshape(h), db.reshape(h)

    fused_ln.defvjp(fwd_rule, bwd_rule)
    return fused_ln


def xla_ln(x, r, scale, bias):
    import jax
    import jax.numpy as jnp
    s = x.astype(jnp.float32) + r.astype(jnp.float32)
    mu = jnp.mean(s, axis=1, keepdims=True)
    d = s - mu
    var = jnp.mean(d * d, axis=1, keepdims=True)
    return ((d * jax.lax.rsqrt(var + EPS)) * scale + bias).astype(x.dtype)


def bench(fn, args, steps=100, repeats=5):
    """min-of-repeats, each repeat timing `steps` async dispatches ended by
    one device sync (the repo's chained-step discipline; min kills the
    run-to-run variance a single pass shows)."""
    import jax
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / steps * 1e3)
    return best


def main():
    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        print("WARNING: not on TPU — numbers below are not the spike's "
              "accept/reject evidence", file=sys.stderr)

    shapes = [(128 * 128, 768), (64 * 128, 768), (256 * 512, 768),
              (128 * 128, 1024)]
    rng = np.random.RandomState(0)
    print(f"{'M':>7} {'H':>5} {'mode':>8} {'pallas ms':>10} "
          f"{'xla ms':>8} {'ratio':>6}")
    for m, h in shapes:
        x = jnp.asarray(rng.randn(m, h), jnp.bfloat16)
        r = jnp.asarray(rng.randn(m, h), jnp.bfloat16)
        sc = jnp.asarray(rng.rand(h), jnp.float32)
        b = jnp.asarray(rng.rand(h), jnp.float32)
        fused = _make_fused()

        f_fwd = jax.jit(fused)
        x_fwd = jax.jit(xla_ln)

        def loss_f(x, r, sc, b, f=fused):
            return jnp.sum(f(x, r, sc, b).astype(jnp.float32))

        def loss_x(x, r, sc, b):
            return jnp.sum(xla_ln(x, r, sc, b).astype(jnp.float32))

        g_f = jax.jit(jax.grad(loss_f, argnums=(0, 1, 2, 3)))
        g_x = jax.jit(jax.grad(loss_x, argnums=(0, 1, 2, 3)))

        # correctness first
        of = np.asarray(f_fwd(x, r, sc, b), np.float32)
        ox = np.asarray(x_fwd(x, r, sc, b), np.float32)
        np.testing.assert_allclose(of, ox, rtol=5e-2, atol=5e-2)
        gf = g_f(x, r, sc, b)
        gx = g_x(x, r, sc, b)
        for a_, b_ in zip(gf, gx):
            np.testing.assert_allclose(np.asarray(a_, np.float32),
                                       np.asarray(b_, np.float32),
                                       rtol=1e-1, atol=1e-1)

        pf = bench(f_fwd, (x, r, sc, b))
        xf = bench(x_fwd, (x, r, sc, b))
        print(f"{m:>7} {h:>5} {'fwd':>8} {pf:>10.3f} {xf:>8.3f} "
              f"{pf / xf:>6.2f}")
        pb = bench(g_f, (x, r, sc, b))
        xb = bench(g_x, (x, r, sc, b))
        print(f"{m:>7} {h:>5} {'fwd+bwd':>8} {pb:>10.3f} {xb:>8.3f} "
              f"{pb / xb:>6.2f}")


if __name__ == "__main__":
    main()
