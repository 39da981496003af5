"""Per-op micro-benchmark harness over the lowering rules.

Reference: paddle/fluid/operators/benchmark/op_tester.cc — time a single
op's kernel from a config. Here: jit the op's lowering on the active
backend (TPU or CPU), run chained steps (output feeds a dependency so
dispatches cannot overlap), report ms/op and
achieved GB/s / GFLOP/s where derivable.

Usage:
  python tools/op_bench.py                        # built-in suite
  python tools/op_bench.py softmax "X:128x1024"   # one op
  python tools/op_bench.py matmul "X:512x512,Y:512x512" transpose_Y=true
"""

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np


def _parse_shapes(spec):
    ins = {}
    for part in spec.split(","):
        slot, dims = part.split(":")
        shape = tuple(int(d) for d in dims.split("x"))
        ins[slot] = shape
    return ins


def _parse_attrs(parts):
    attrs = {}
    for p in parts:
        k, v = p.split("=")
        if v in ("true", "false"):
            attrs[k] = v == "true"
        else:
            try:
                attrs[k] = int(v)
            except ValueError:
                try:
                    attrs[k] = float(v)
                except ValueError:
                    attrs[k] = v
    return attrs


def bench_op(op_type, in_shapes, attrs=None, steps=30, dtype="float32"):
    """Returns (ms_per_op, bytes_moved). The op runs in a chained loop:
    step k's first input is perturbed by a scalar from step k-1's output,
    forcing sequential execution without adding measurable work."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.framework.registry import get_op_def, LowerContext

    attrs = attrs or {}
    rng = np.random.RandomState(0)
    ins = {slot: [jnp.asarray(rng.rand(*shape).astype(dtype))]
           for slot, shape in in_shapes.items()}
    opdef = get_op_def(op_type)
    first_slot = next(iter(ins))

    def run(chain, xs):
        xs = dict(xs)
        xs[first_slot] = [xs[first_slot][0] + chain]
        ctx = LowerContext(rng_key=jax.random.PRNGKey(0))
        outs = opdef.lower(ctx, xs, attrs)
        first_out = next(iter(outs.values()))[0]
        # depend on the WHOLE output: a single-element slice would let
        # XLA sink the slice through elementwise ops and dead-code the
        # benchmarked computation (verified in compiled HLO). The 1e-30
        # scale keeps a true data dependency (x*0 could legally fold)
        # while keeping the chain value negligible.
        return jnp.sum(jnp.real(first_out)).astype(jnp.float32) * 1e-30

    jrun = jax.jit(run)
    chain = jnp.zeros((), jnp.float32)
    chain = jrun(chain, ins)
    chain.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(steps):
        chain = jrun(chain, ins)
    float(chain)  # host sync
    dt = (time.perf_counter() - t0) / steps
    nbytes = sum(v[0].nbytes for v in ins.values())
    return dt * 1e3, nbytes


_SUITE = [
    ("softmax", {"X": (128, 1024)}, {}),
    ("layer_norm", {"X": (128, 1024), "Scale": (1024,), "Bias": (1024,)},
     {"begin_norm_axis": 1}),
    ("matmul", {"X": (512, 512), "Y": (512, 512)}, {}),
    ("relu", {"X": (1024, 1024)}, {}),
    ("reduce_sum", {"X": (1024, 1024)}, {"reduce_all": True}),
    ("transpose", {"X": (512, 1024)}, {"axis": [1, 0]}),
    ("elementwise_add", {"X": (1024, 1024), "Y": (1024, 1024)}, {}),
]


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    import jax
    print(f"backend: {jax.default_backend()}, devices: {jax.devices()}")
    if argv:
        op = argv[0]
        shapes = _parse_shapes(argv[1]) if len(argv) > 1 else {"X": (1024,)}
        attrs = _parse_attrs(argv[2:])
        jobs = [(op, shapes, attrs)]
    else:
        jobs = _SUITE
    print(f"{'op':24s} {'shapes':32s} {'ms/op':>9s} {'GB/s':>8s}")
    for op, shapes, attrs in jobs:
        try:
            ms, nbytes = bench_op(op, shapes, attrs)
            gbps = nbytes / (ms * 1e-3) / 1e9
            shp = ",".join(f"{k}:{'x'.join(map(str, v))}"
                           for k, v in shapes.items())
            print(f"{op:24s} {shp:32s} {ms:9.3f} {gbps:8.1f}")
        except Exception as e:  # keep the suite running past one failure
            print(f"{op:24s} FAILED: {type(e).__name__}: {e}")


if __name__ == "__main__":
    main()
