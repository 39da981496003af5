"""The flash backward alone at the training cells' shape and at a long,
wide one: ms a call and TFLOP/s of the five products, parent beside change.

Times `ops/flash_attention._flash_bwd_call` (what `fused_attention_grad` and
`flash_attention`'s vjp run beyond 512 rows) in the (heads, rows, d) layout
the kernel reads, causal, bfloat16: 96 heads x 1,024 rows x 64 (the cells'
layer: 8 sequences x 12 heads) and 16 heads x 4,096 rows x 128. For each
shape it prints the Mosaic calls a layer and their names, their device time,
the device time of EVERYTHING the call runs (the Mosaic calls and XLA's work
around them: `delta`, the pads), and the TFLOP/s and share of 197 TFLOP/s of
the EXACT triangle's five products (10 x d x rows x (rows + 1) / 2 FLOP a
head) over each of the two times.

The times are device times from a profiler trace of a program that holds
the backward and nothing else (`bench_flash_forward.py`'s reader). A chip is
required: on any other backend it exits 1 with nothing measured.

    chiprun -- python tools/bench_flash_backward.py
    chiprun -- python tools/bench_flash_backward.py --repo .scratch/parent --tag parent

`--repo DIR` times the backward of another checkout (the parent's, unpacked
by `git archive`). Before it times a shape it holds dq, dk and dv of the
first two heads against `jax.grad` of `mha_reference` in float32 on the chip
(within 0.05 of gradients whose largest entries are ~4: bfloat16 results),
and writes `grads_sha256`, a digest of what the call returned (the inputs
are a function of the shape alone). Prints one JSON line a shape; the same
goes to chiprun_out/bench_flash_backward[.tag].json. Run by no cell.
"""

import argparse
import glob
import hashlib
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_flash_forward import PEAK_FLOPS, is_kernel  # noqa: E402

CALLS = 6
# (heads, rows, d)
SHAPES = ((96, 1024, 64), (16, 4096, 128))


def device_seconds(trace_dir):
    """(Mosaic events, their names, their seconds, every operation's
    seconds) on the first chip of a trace."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops" or not line.events:
                continue
            events = list(line.events)
            calls = [ev for ev in events if is_kernel(ev.name)]
            return (len(calls),
                    sorted({ev.name.partition(" = ")[0] for ev in calls}),
                    sum(ev.duration_ns for ev in calls) * 1e-9,
                    sum(ev.duration_ns for ev in events) * 1e-9)
    return 0, [], 0.0, 0.0


def measure(fa, heads, rows, d):
    """One shape's line."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    scale = 1.0 / np.sqrt(d)
    key = jax.random.split(jax.random.PRNGKey(rows), 4)
    q, k, v, do = (jax.random.normal(kk, (heads, rows, d), jnp.bfloat16)
                   for kk in key)

    @jax.jit
    def forward(q, k, v):
        return fa._flash_call(q, k, v, None, True, float(scale), False)

    @jax.jit
    def backward(q, k, v, o, lse, do):
        return fa._flash_bwd_call(q, k, v, None, o, lse, do, True,
                                  float(scale), False)[:3]

    o, lse = forward(q, k, v)
    got = backward(q, k, v, o, lse, do)
    digest = hashlib.sha256(
        b"".join(np.asarray(g).tobytes() for g in got)).hexdigest()

    # the first two heads against the float32 reference's gradients
    f32 = lambda x: x[:2].astype(jnp.float32).swapaxes(0, 1)[None]
    with jax.default_matmul_precision("highest"):
        want = jax.grad(
            lambda q, k, v: (fa.mha_reference(q, k, v, None, True, scale)
                             * f32(do)).sum(), argnums=(0, 1, 2))(
            f32(q), f32(k), f32(v))
    errors = [float(jnp.abs(f32(g) - w).max()) for g, w in zip(got, want)]
    if not max(errors) < 0.05:
        raise SystemExit(f"{heads} x {rows} x {d}: the backward disagrees "
                         f"with mha_reference's gradients: {errors}")

    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        for _ in range(CALLS):
            out = backward(q, k, v, o, lse, do)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        events, names, mosaic, everything = device_seconds(trace_dir)
    if events % CALLS:
        raise SystemExit(f"{events} Mosaic calls in the trace of {CALLS} "
                         "backward calls")
    flops = 10 * d * (rows * (rows + 1) // 2) * heads
    mosaic, everything = mosaic / CALLS, everything / CALLS
    return {
        "heads": heads, "rows": rows, "d": d,
        "mosaic_calls_a_layer": events // CALLS, "mosaic_names": names,
        "mosaic_ms": mosaic * 1e3, "call_ms": everything * 1e3,
        "tflops_mosaic": flops / mosaic * 1e-12,
        "tflops_call": flops / everything * 1e-12,
        "peak_share_mosaic": 100 * flops / PEAK_FLOPS / mosaic,
        "peak_share_call": 100 * flops / PEAK_FLOPS / everything,
        "largest_errors_dq_dk_dv": errors, "grads_sha256": digest[:16]}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--repo",
                    default=os.path.join(os.path.dirname(__file__), ".."))
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.repo))

    import jax
    if jax.default_backend() != "tpu":
        print(f"a chip is required; the backend is {jax.default_backend()!r}",
              file=sys.stderr)
        return 1
    from paddle_tpu.ops import flash_attention as fa

    results = []
    for heads, rows, d in SHAPES:
        jax.clear_caches()
        result = dict(measure(fa, heads, rows, d),
                      device=jax.devices()[0].device_kind)
        print(json.dumps(result), flush=True)
        results.append(result)
    out = os.path.join(os.path.dirname(__file__), "..", "chiprun_out")
    os.makedirs(out, exist_ok=True)
    tag = "." + args.tag if args.tag else ""
    with open(os.path.join(out, f"bench_flash_backward{tag}.json"), "w") as f:
        json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
