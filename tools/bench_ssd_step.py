"""One Mamba-2 layer's two programs alone: the recurrent step over a pool of
state blocks, and the chunked scan of one prompt.

The STEP (`ssd/step` of models/granite_hybrid.py's decode step) in both
forms, XLA's gather-update-scatter (`ssd_step` over `arena[layer, 0, ids]`)
and the kernel ops/ssd_step.py (one grid step a slot's whole block), at the
cell's shape: 96 slots (some frozen), 128 heads of 64 x 128 float32, 9 layers, a
shuffled page column. The kernel is first held against the XLA form on the
same chip: the live slots' blocks EQUAL (both are `state * decay + dx * B` in
float32 on a VPU with no fused multiply-add), and each form's `y` against a
FLOAT64 contraction, on the host, of the state that form wrote
(`y_vs_float64`, relative Frobenius error: the kernel's product is the
MXU's, and a product short of float32's passes shows HERE, where the
interpreter on the CPU computes in float32 whatever the MXU would do; the
kernel may read twice XLA's and no more). Beside each time stand the bytes a
step must move (a live slot's state read once and written once: 2 x 4,194,304
B a layer a slot) over the chip's 819 GB/s, and the share
`ssd_decode_hbm_roofline` would read. Beside the kernel `as_served` stand
three throw-away forms of it, made here by handing the served kernel
stand-ins for its operands and in no path of the program: `no_y` (the
state's update and both block transfers: `y` is never stored, so its
product is dead code), `no_broadcasts` (`y` computed, every read of the
decay and of dt x a constant) and `arithmetic_alone` (every slot sent ONE
block, which the pipeline then fetches once and writes once: what the VPU,
XLU and MXU take with the DMA out of the way). The CHUNKED SCAN of 256 ..
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
import functools
import inspect
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

HBM_BYTES_PER_S = 819e9          # TPU v5e (Google Cloud documentation)
PEAK_FLOPS = 197e12


class _Constant:
    """Stands in for an operand of the kernel: every read is one constant."""

    def __init__(self, value):
        self.value = value

    def __getitem__(self, index):
        import jax.numpy as jnp
        return jnp.float32(self.value)


class _Unwritten:
    """Stands in for an output of the kernel: a store goes nowhere."""

    def __setitem__(self, index, value):
        pass


def throw_away(served, no_y=False, no_broadcasts=False):
    """The served kernel with `y`'s output and / or its two broadcast
    operands replaced by stand-ins: a diagnostic form, timed and thrown
    away."""
    names = list(inspect.signature(served).parameters)

    def kernel(*refs, **static):
        refs = dict(zip(names, refs))
        if no_broadcasts:
            refs["decay_ref"], refs["dx_ref"] = _Constant(0.9), _Constant(0.5)
        if no_y:
            refs["y_ref"] = _Unwritten()
        served(*refs.values(), **static)
    return kernel


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
    from paddle_tpu.ops import ssd_step

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

    def kernel_step(arena, li, done, ids=ids):
        return ssd_step.ssd_step_blocks(arena, li, ids, done, x, dt,
                                        jnp.exp(dt * A), B, C)

    forms = [("xla", xla_step), ("kernel", kernel_step)]
    # the kernel against XLA's form, some slots frozen
    live = ~np.asarray(done)
    result = {"slots": slots, "heads": H, "head_dim": P, "state": N,
              "layers": layers, "y_vs_float64": {}, "step": {}, "prefill": []}
    wrote = {}
    for name, step in forms:
        y, arena = jax.jit(step, static_argnums=1)(small + 0, 1, done)
        wrote[name] = np.asarray(arena[1, 0, ids])[live]
        y64 = np.einsum("shpn,sn->shp", wrote[name].astype(np.float64),
                        np.asarray(C, np.float64)[live])
        result["y_vs_float64"][name] = float(
            np.linalg.norm(np.asarray(y)[live] - y64) / np.linalg.norm(y64))
        del y, arena, y64
    result["state_equals_xla"] = bool((wrote["kernel"] == wrote["xla"]).all())
    # (the CPU may fuse a multiply into the add in one form and not the other)
    if not (result["state_equals_xla"] or args.tiny):
        raise SystemExit("the kernel's blocks are not XLA's: largest "
                         f"{np.abs(wrote['kernel'] - wrote['xla']).max()}")
    if result["y_vs_float64"]["kernel"] > 2 * result["y_vs_float64"]["xla"]:
        raise SystemExit("the kernel's y is further from the float64 "
                         f"contraction than twice XLA's: {result['y_vs_float64']}")
    del wrote, small
    holder = [0.1 * jax.random.normal(key[5], (layers, 1, slots + 1, H, P, N),
                                      jnp.float32)]
    none = jnp.zeros((slots,), bool)
    state_bytes = 2 * H * P * N * 4               # read once, written once
    floor = slots * state_bytes / HBM_BYTES_PER_S

    def layer_time(step):
        """(device, host) seconds a layer and the largest operations of
        nine layers' steps in one program, the arena donated."""
        def program(arena):
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
        return device and device / layers, host and host / layers, largest

    for name, step in forms:
        device, host, largest = layer_time(step)
        result["step"][name] = {
            "layer_us": device and device * 1e6,
            "host_layer_us": host and host * 1e6,
            "floor_us": floor * 1e6, "top_operations_us": largest,
            "ssd_decode_hbm_roofline": device and 100 * floor / device}
    # the three readings, and the arithmetic with the DMA out of the way
    result["step"]["kernel"]["as_served"] = result["step"]["kernel"]["layer_us"]
    served = ssd_step._kernel
    try:
        for name, stand_ins in [("no_y", dict(no_y=True)),
                                ("no_broadcasts", dict(no_broadcasts=True))]:
            ssd_step._kernel = throw_away(served, **stand_ins)
            device = layer_time(kernel_step)[0]
            result["step"]["kernel"][name] = device and device * 1e6
    finally:
        ssd_step._kernel = served
    device = layer_time(functools.partial(kernel_step,
                                          ids=jnp.ones_like(ids)))[0]
    result["step"]["kernel"]["arithmetic_alone"] = device and device * 1e6
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
