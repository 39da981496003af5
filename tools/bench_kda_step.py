"""One KDA layer's two programs alone: the recurrent step over a pool of
state blocks, and the chunked prefill of one prompt.

The STEP (`kda/recur` of models/kimi_linear.py's decode step) in both
forms, XLA's gather-update-scatter (`kda_step` over `arena[layer, 0, ids]`)
and the kernel ops/kda_step.py, at the cell's shape: 128 slots (some
frozen), 32 heads of 128 x 128 float32, 10 layers, a shuffled page column.
The kernel is first held against the XLA form on the same chip (the arena
compared whole but for scratch block 0). Beside each time stand the bytes
a step must move (a live slot's state read once and written once: 2 x
2,097,152 B a layer a slot) over the chip's 819 GB/s, and the share
`kda_decode_hbm_roofline` would read. The CHUNKED PREFILL of 512 .. 4,096
rows in both forms, plain `jax.numpy` (`kda_chunked`) and the kernel
ops/kda_chunk.py, with its share of the peak as
`kda_prefill_flops_roofline` counts it (the recurrence's own three
products, 6 x 128 x 128 FLOPs a row a head). THE NUMERIC HOLD (`hold`): at
4,096 rows each form's `o` and final state against the token-by-token
float32 scan (relative Frobenius error), beside a control whose products'
operands are rounded to bfloat16; and the same under CORRELATED keys (one
token repeated; a shared mean direction), slow decay and beta near 1, where
the triangular system's matrix has powers past 1e9 and only a substitution
keeps its digits (`hold.correlated`).

Device time is the sum of the first chip's operations in a profiler trace;
the host's clock a call stands beside it. A chip is required (`--tiny`
rehearses the program on the CPU at a toy size and reports no time).

    chiprun -- python tools/bench_kda_step.py [--model qwen3next]

`--model qwen3next` (PR 56): models/qwen3_next.py's Gated DeltaNet at ITS
cell's shapes, 64 slots, 9 layers, prompts of 2,048 .. 16,384 rows, a
SCALAR decay a head and 16 key heads feeding 32 value heads (value head h
reads q, k of key head h // 2). Both reach the two kernels as BROADCAST
operands (the decay over a head's 128 key channels, a key head's q, k
over its two value heads), so the kernels are Kimi-Linear's as they are;
what that costs beside the state's 2 MB a head-set a slot is this tool's
`step.kernel.layer_us` against `floor_us`.

Prints one JSON line; the same goes to chiprun_out/bench_kda_step.json.
"""

import argparse
import glob
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

HBM_BYTES_PER_S = 819e9          # TPU v5e (Google Cloud documentation)
PEAK_FLOPS = 197e12
# a served delta-rule mixer's shapes: both have 32 value heads of 128 x 128
MODELS = {
    "kimi": {"slots": 128, "layers": 10, "value_heads_a_key_head": 1,
             "scalar_decay": False, "rows": "512,1024,2048,4096"},
    "qwen3next": {"slots": 64, "layers": 9, "value_heads_a_key_head": 2,
                  "scalar_decay": True, "rows": "2048,4096,8192,16384"}}
# the plain chunked form holds every chunk's Gram matrices at once
XLA_FORM_UP_TO = 4096


def operation_seconds(trace_dir):
    """{operation: seconds} of the first chip's operations in a trace."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    totals = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        name = ev.name.split(" = ")[0].lstrip("%")
                        totals[name] = totals.get(name, 0.0) + ev.duration_ns * 1e-9
            break
    return totals


def timed(run, calls, tiny, top=14):
    """(device seconds, host seconds, [(operation, us)] the largest first) a
    call of `run()`, over `calls` of them."""
    import jax
    run().block_until_ready()
    if tiny:
        return None, None, []
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        t0 = time.perf_counter()
        for _ in range(calls):
            out = run()
        out.block_until_ready()
        host = (time.perf_counter() - t0) / calls
        jax.profiler.stop_trace()
        totals = operation_seconds(trace_dir)
    largest = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return (sum(totals.values()) / calls or None, host,
            [(name, seconds / calls * 1e6) for name, seconds in largest])


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--model", choices=tuple(MODELS), default="kimi",
                    help="kimi: 128 slots, 10 layers, a decay a key channel, "
                    "32 key heads; qwen3next: 64 slots, 9 layers, a SCALAR "
                    "decay a head and 16 key heads feeding 32 value heads "
                    "(value head h reads q, k of key head h // 2), both "
                    "handed to the kernels as broadcast operands, as "
                    "models/qwen3_next.py serves them")
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--rows", default=None)
    ap.add_argument("--precision", default=None,
                    help="replace models/_delta.KDA_PRECISION (a sweep)")
    args = ap.parse_args()
    model = MODELS[args.model]

    import jax
    import jax.numpy as jnp
    import numpy as np
    if jax.default_backend() != "tpu" and not args.tiny:
        print(f"a chip is required; the backend is {jax.default_backend()!r}",
              file=sys.stderr)
        return 1
    from paddle_tpu.models import _delta
    from paddle_tpu.ops.kda_chunk import kda_chunk
    from paddle_tpu.ops.kda_step import kda_step_blocks
    if args.precision:
        _delta.KDA_PRECISION = args.precision

    slots, heads, d, layers = (4, 4, 16, 2) if args.tiny else \
        (args.slots or model["slots"], 32, 128, model["layers"])
    share = model["value_heads_a_key_head"]

    def keyed(key, lead):
        """q or k: a unit vector a KEY head, read by its value heads."""
        x = jax.random.normal(key, (lead, heads // share, d))
        return jnp.repeat(x / jnp.linalg.norm(x, axis=-1, keepdims=True),
                          share, 1)

    def decay(key, lead):
        """g <= 0: a value a key channel, or one a head over its channels."""
        g = -jnp.exp(jax.random.uniform(
            key, (lead, heads, 1 if model["scalar_decay"] else d),
            minval=-7.0, maxval=0.5))
        return jnp.broadcast_to(g, (lead, heads, d))

    rng = np.random.default_rng(0)
    key = jax.random.split(jax.random.PRNGKey(0), 8)
    arena = 0.1 * jax.random.normal(key[0], (layers, 1, slots + 1, heads, d, d),
                                    jnp.float32)
    ids = jnp.asarray(1 + rng.permutation(slots), jnp.int32)
    done = jnp.arange(slots) % 7 == 5
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = keyed(key[1], slots) * d ** -0.5
    k = keyed(key[2], slots)
    v = jax.random.normal(key[3], (slots, heads, d))
    g = decay(key[4], slots)
    beta = jax.nn.sigmoid(jax.random.normal(key[5], (slots, heads)))

    def xla_step(arena, li, done):
        S, o = _delta.kda_step(arena[li, 0, ids], q, k, v, g, beta)
        return o, arena.at[li, 0, jnp.where(done, 0, ids)].set(S)

    def kernel_step(arena, li, done):
        return kda_step_blocks(arena, li, ids, done, q, k, v, g, beta)

    # the kernel against XLA's form, some slots frozen
    o_x, a_x = jax.jit(xla_step, static_argnums=1)(arena, 1, done)
    o_k, a_k = jax.jit(kernel_step, static_argnums=1)(arena + 0, 1, done)
    live = ~np.asarray(done)
    err_o = float(np.abs(np.asarray(o_x) - np.asarray(o_k))[live].max())
    err_s = float(jnp.abs(a_x[:, :, 1:] - a_k[:, :, 1:]).max())
    if not (err_o < 1e-4 and err_s < 1e-4):
        raise SystemExit(f"the kernel disagrees with XLA's form: o {err_o}, "
                         f"state {err_s}")
    result = {"model": args.model, "slots": slots, "heads": heads,
              "key_heads": heads // share,
              "scalar_decay": model["scalar_decay"], "head_dim": d,
              "layers": layers,
              "kernel_vs_xla": {"o": err_o, "state": err_s}, "step": {},
              "prefill": []}
    none = jnp.zeros((slots,), bool)
    state_bytes = 2 * heads * d * d * 4           # read once, written once
    for name, step in (("xla", xla_step), ("kernel", kernel_step)):
        def program(arena, step=step):
            total = jnp.zeros((slots, heads, d), jnp.float32)
            for li in range(layers):
                o, arena = step(arena, li, none)
                total = total + o
            return total, arena

        program = jax.jit(program, donate_argnums=0)
        holder = [arena + 0]

        def run():
            total, holder[0] = program(holder[0])
            return total

        device, host, _ = timed(run, 8, args.tiny)
        floor = slots * state_bytes / HBM_BYTES_PER_S
        result["step"][name] = {
            "layer_us": device and device / layers * 1e6,
            "host_layer_us": host and host / layers * 1e6,
            "floor_us": floor * 1e6,
            "kda_decode_hbm_roofline": device and 100 * floor * layers / device}
    def operands(rows, seed):
        kk = jax.random.split(jax.random.PRNGKey(seed), 5)
        return (keyed(kk[0], rows) * d ** -0.5, keyed(kk[1], rows),
                jax.random.normal(kk[2], (rows, heads, d)),
                decay(kk[3], rows),
                jax.nn.sigmoid(jax.random.normal(kk[4], (rows, heads))))

    forms = {"xla": jax.jit(lambda *a: _delta.kda_chunked(*a)[:2]),
             "kernel": jax.jit(lambda *a: kda_chunk(*a)[:2])}
    for rows in ([64] if args.tiny else [
            int(r) for r in (args.rows or model["rows"]).split(",")]):
        ops = operands(rows, rows)
        for path, form in forms.items():
            if path == "xla" and rows > XLA_FORM_UP_TO:
                continue           # gigabytes of Gram matrices; never served
            device, host, largest = timed(lambda: form(*ops)[0], 4, args.tiny)
            flops = rows * heads * 6 * d * d
            result["prefill"].append({
                "rows": rows, "path": path, "chunk": _delta.KDA_CHUNK,
                "precision": _delta.KDA_PRECISION, "top_operations_us": largest,
                "layer_us": device and device * 1e6,
                "host_layer_us": host and host * 1e6, "flops_counted": flops,
                "kda_prefill_flops_roofline": device
                and 100 * flops / PEAK_FLOPS / device})
    # THE NUMERIC HOLD: both forms against the token-by-token float32 scan
    # on the same rows, beside a control whose products' operands are
    # rounded to bfloat16 (`reduce_precision`: the compiler drops an
    # `astype` pair)
    rows = 64 if args.tiny else 4096
    ops = operands(rows, 46)

    def scan(q, k, v, g, beta):
        S, o = jax.lax.scan(lambda S, x: _delta.kda_step(S, *x),
                            jnp.zeros((heads, d, d), jnp.float32),
                            (q, k, v, g, beta))
        return o, S

    def rounded(*a):
        real = jnp.einsum
        jnp.einsum = lambda spec, *xs, **kw: real(
            spec, *(jax.lax.reduce_precision(x, 8, 7) for x in xs), **kw)
        try:
            return _delta.kda_chunked(*a)[:2]
        finally:
            jnp.einsum = real

    size = lambda a: float(jnp.sqrt(jnp.sum(a.astype(jnp.float32) ** 2)))

    def held(ops, forms):
        want = jax.jit(scan)(*ops)
        return {path: {name: size(x - y) / size(y)
                       for name, x, y in zip(("o", "state"), form(*ops), want)}
                for path, form in forms.items()}

    result["hold"] = dict(
        held(ops, dict(forms, bfloat16_products=jax.jit(rounded))), rows=rows)
    # keys that are NOT nearly orthogonal: one key row a head in every row
    # (a run of one token behind the width-4 convolution), g = -1e-3, beta
    # = 0.9; and random keys about a shared direction, slow random decays
    q, k, v, g, beta = ops
    one = jnp.broadcast_to(k[:1], k.shape)
    result["hold"]["correlated"] = {
        "one_token": held((one * d ** -0.5, one, v, jnp.full_like(g, -1e-3),
                           jnp.full_like(beta, 0.9)), forms),
        "shared_mean": held((q, unit(k + 3.0 * k[:1] * d ** 0.5), v,
                             g * 2e-2, jax.nn.sigmoid(1.0 + beta)), forms)}
    line = json.dumps(result)
    print(line)
    if not args.tiny:
        out = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                           "chiprun_out")
        os.makedirs(out, exist_ok=True)
        name = "bench_kda_step.json" if args.model == "kimi" \
            else f"bench_kda_step.{args.model}.json"
        with open(os.path.join(out, name), "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
