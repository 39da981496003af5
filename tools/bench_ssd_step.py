"""One Mamba-2 layer's two programs alone: the recurrent step over a pool of
state blocks, and the chunked scan of one prompt.

The STEP (`ssd/step` of models/granite_hybrid.py's decode step) in both
forms, XLA's gather-update-scatter (`ssd_step` over `arena[layer, 0, ids]`)
and the kernel ops/ssd_step.py (one grid step a slot's whole block), at the
cell's shape: 96 slots (some frozen), 128 heads of 64 x 128 float32, 9 layers, a
shuffled page column. The kernel is first held against the XLA form on the
same chip (the arena compared whole but for scratch block 0). Beside each
time stand the bytes a step must move (a live slot's state read once and
written once: 2 x 4,194,304 B a layer a slot) over the chip's 819 GB/s, and
the share `ssd_decode_hbm_roofline` would read. The CHUNKED SCAN of 256 ..
2,048 rows (`ssd_chunked`, plain `jax.numpy` at `highest`) with its share
of the peak as `ssd_prefill_flops_roofline` counts it (6 x 64 x 128 FLOPs a
row a head), and at 2,048 rows its `y` and final state against the
token-by-token float32 scan (relative Frobenius error: `hold`; beside it
`hold_default_precision`, the same scan with its products at the backend's
default precision, bfloat16 passes on a TPU: what the cell's scan limit is
there to tell).

Device time is the sum of the first chip's operations in a profiler trace;
the host's clock a call stands beside it. A chip is required (`--tiny`
rehearses the program on the CPU at a toy size and reports no time).

    chiprun -- python tools/bench_ssd_step.py

Prints one JSON line; the same goes to chiprun_out/bench_ssd_step.json.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

HBM_BYTES_PER_S = 819e9          # TPU v5e (Google Cloud documentation)
PEAK_FLOPS = 197e12


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--slots", type=int, default=96)
    ap.add_argument("--rows", default="256,512,1024,2048")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    if jax.default_backend() != "tpu" and not args.tiny:
        print(f"a chip is required; the backend is {jax.default_backend()!r}",
              file=sys.stderr)
        return 1
    from bench_kda_step import timed
    from paddle_tpu.models import granite_hybrid as gh
    from paddle_tpu.ops.ssd_step import ssd_step_blocks

    slots, H, P, N, layers = (4, 8, 8, 128, 2) if args.tiny else \
        (args.slots, 128, 64, 128, 9)
    rng = np.random.default_rng(0)
    key = jax.random.split(jax.random.PRNGKey(0), 6)
    # two layers hold the kernel against XLA's form; the timed arena, the
    # cell's nine, is made once and donated from call to call
    small = 0.1 * jax.random.normal(key[0], (2, 1, slots + 1, H, P, N),
                                    jnp.float32)
    ids = jnp.asarray(1 + rng.permutation(slots), jnp.int32)
    done = jnp.arange(slots) % 7 == 5
    x = jax.random.normal(key[1], (slots, H, P))
    dt = jnp.exp(jax.random.uniform(key[2], (slots, H), minval=-7.0,
                                    maxval=0.0))
    A = -jnp.arange(1, H + 1, dtype=jnp.float32)
    B = jax.random.normal(key[3], (slots, N))
    C = jax.random.normal(key[4], (slots, N))

    def xla_step(arena, li, done):
        S, y = gh.ssd_step(arena[li, 0, ids], x, dt, A, B, C)
        return y, arena.at[li, 0, jnp.where(done, 0, ids)].set(S)

    def kernel_step(arena, li, done):
        return ssd_step_blocks(arena, li, ids, done, x, dt, jnp.exp(dt * A),
                               B, C)

    forms = [("xla", xla_step), ("kernel", kernel_step)]
    # the kernel against XLA's form, some slots frozen
    y_x, a_x = jax.jit(xla_step, static_argnums=1)(small, 1, done)
    live = ~np.asarray(done)
    result = {"slots": slots, "heads": H, "head_dim": P, "state": N,
              "layers": layers, "kernel_vs_xla": {}, "step": {}, "prefill": []}
    for name, step in forms[1:]:
        y_k, a_k = jax.jit(step, static_argnums=1)(small + 0, 1, done)
        err_y = float(np.abs(np.asarray(y_x) - np.asarray(y_k))[live].max())
        err_s = float(jnp.abs(a_x[:, :, 1:] - a_k[:, :, 1:]).max())
        if not (err_y < 1e-3 and err_s < 1e-4):
            raise SystemExit(f"{name} disagrees with XLA's form: y {err_y}, "
                             f"state {err_s}")
        result["kernel_vs_xla"][name] = {"y": err_y, "state": err_s}
        del y_k, a_k
    del y_x, a_x, small
    holder = [0.1 * jax.random.normal(key[5], (layers, 1, slots + 1, H, P, N),
                                      jnp.float32)]
    none = jnp.zeros((slots,), bool)
    state_bytes = 2 * H * P * N * 4               # read once, written once
    for name, step in forms:
        def program(arena, step=step):
            total = jnp.zeros((slots, H, P), jnp.float32)
            for li in range(layers):
                y, arena = step(arena, li, none)
                total = total + y
            return total, arena

        program = jax.jit(program, donate_argnums=0)

        def run():
            total, holder[0] = program(holder[0])
            return total

        device, host, largest = timed(run, 8, args.tiny, top=4)
        floor = slots * state_bytes / HBM_BYTES_PER_S
        result["step"][name] = {
            "layer_us": device and device / layers * 1e6,
            "host_layer_us": host and host / layers * 1e6,
            "floor_us": floor * 1e6, "top_operations_us": largest,
            "ssd_decode_hbm_roofline": device
            and 100 * floor * layers / device}
    del holder[0]

    def operands(rows, seed):
        kk = jax.random.split(jax.random.PRNGKey(seed), 4)
        return (jax.random.normal(kk[0], (rows, H, P)),
                jnp.exp(jax.random.uniform(kk[1], (rows, H), minval=-7.0,
                                           maxval=0.0)), A,
                jax.random.normal(kk[2], (rows, N)),
                jax.random.normal(kk[3], (rows, N)))

    chunk = 8 if args.tiny else 256
    chunked = jax.jit(lambda *a: gh.ssd_chunked(*a, chunk=chunk))
    for rows in ([16] if args.tiny else [int(r) for r in args.rows.split(",")]):
        ops = operands(rows, rows)
        device, host, largest = timed(lambda: chunked(*ops)[0], 4, args.tiny,
                                      top=8)
        flops = rows * H * 6 * P * N
        result["prefill"].append({
            "rows": rows, "chunk": chunk, "precision": gh.SSD_PRECISION,
            "top_operations_us": largest, "layer_us": device and device * 1e6,
            "host_layer_us": host and host * 1e6, "flops_counted": flops,
            "ssd_prefill_flops_roofline": device
            and 100 * flops / PEAK_FLOPS / device})
    rows = 16 if args.tiny else 2048
    xs, dts, _, Bs, Cs = operands(rows, 54)

    def scan(x, dt, B, C):
        S, y = jax.lax.scan(
            lambda S, r: gh.ssd_step(S, r[0], r[1], A, r[2], r[3]),
            jnp.zeros((H, P, N), jnp.float32), (x, dt, B, C))
        return y, S

    size = lambda a: float(jnp.sqrt(jnp.sum(a.astype(jnp.float32) ** 2)))
    want = jax.jit(scan)(xs, dts, Bs, Cs)
    got = chunked(xs, dts, A, Bs, Cs)
    held = lambda got: {"rows": rows,
                        "y": size(got[0] - want[0]) / size(want[0]),
                        "state": size(got[1] - want[1]) / size(want[1])}
    result["hold"] = held(got)
    stated, gh.SSD_PRECISION = gh.SSD_PRECISION, None
    try:
        result["hold_default_precision"] = held(jax.jit(
            lambda *a: gh.ssd_chunked(*a, chunk=chunk))(xs, dts, A, Bs, Cs))
    finally:
        gh.SSD_PRECISION = stated
    line = json.dumps(result)
    print(line)
    if not args.tiny:
        out = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                           "chiprun_out")
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, "bench_ssd_step.json"), "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
