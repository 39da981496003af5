"""The latent decode kernel alone: time against live pages, and the fit
t = S x (a + b x pages).

Times `ops/paged_attention.latent_paged_attention` at the two shapes the
benchmark's latent cells serve (Moonlight: 32 slots, 16 heads, 64 pages a
slot; Xing: 16 slots, 32 heads, 128 pages; block_size 128, rows 640 wide,
bfloat16), every slot at the same length, for a list of lengths in pages.
`a` is what a slot costs whatever its length (priming, the live page, the
write-back), `b` what one more page costs; beside each time stand the
bytes of the fetched pages over the chip's 819 GB/s (the least a walk can
take) and the share `mla_decode_hbm_roofline` would read (1,152 B a live
row over the same peak).

The time is the kernel's own device time, read from a profiler trace by
the kernel's name (what the cells' metric reads); the host's clock per
call stands beside it as a check. A chip is required: on any other
backend it exits 1 with nothing measured.

    chiprun -- python tools/bench_latent_decode.py
    chiprun -- python tools/bench_latent_decode.py --shapes xing --pages 1,2,3,5,8 --walk 2,3

`--walk G,BUFFERS` replaces the kernel's own choice (`_latent_walk`) for
a sweep of variants; nothing but this tool sets it. `--repo DIR` times
the kernel of another checkout (the parent's, unpacked by `git archive`).
Before it times a shape it holds the kernel against a gather and two
einsums on the same chip (slots of every length, some frozen; the arena
compared whole), and after the sweep it times one call with the cell's
own spread of lengths (`mixed_*`). Prints one JSON line a shape; the same
goes to chiprun_out/bench_latent_decode.json.
"""

import argparse
import glob
import json
import os
import sys
import tempfile
import time

HBM_BYTES_PER_S = 819e9          # TPU v5e (Google Cloud documentation)
ROW_BYTES_COUNTED = 1152         # benchmarks/lib/costs_moonlight.py: 576 values
BLOCK, WIDTH, LAYERS = 128, 640, 2
SHAPES = {"moonlight": dict(slots=32, heads=16, pages=64),
          "xing": dict(slots=16, heads=32, pages=128)}
KERNEL = "latent_paged_attention"


def kernel_seconds(trace_dir, kernel=KERNEL):
    """(events, seconds) of the kernel on the first chip of a trace."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            durs = [ev.duration_ns for ev in line.events
                    if kernel in ev.name.split("=")[0]]
            if durs:
                return len(durs), sum(durs) * 1e-9
    return 0, 0.0


def prepare(pa, slots, heads, pages):
    """One shape's operands and its jitted program of 16 calls; returns
    time_at(live_pages) -> seconds a call (device, host), with
    `live_pages` live in every slot and the step's row in the middle of
    the live page. The length is a runtime value: one compile a shape."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(slots)
    blocks = 1 + slots * pages
    arena = jax.random.normal(
        jax.random.PRNGKey(0), (LAYERS, 1, blocks, 1, BLOCK, WIDTH),
        jnp.bfloat16)
    pt = jnp.asarray(1 + rng.permutation(slots * pages).reshape(slots, pages),
                     jnp.int32)
    q = jnp.asarray(rng.normal(0, 0.05, (slots, heads, WIDTH)), jnp.bfloat16)
    row = jnp.asarray(rng.normal(0, 1, (slots, WIDTH)), jnp.bfloat16)
    per_program, programs = 8 * LAYERS, 8

    def program(q, row, arena, pt, ts):
        total = jnp.zeros(q.shape, jnp.float32)
        for i in range(per_program):
            out, arena = pa.latent_paged_attention(
                q, row, arena, i % LAYERS, pt, ts)
            total = total + out
        return total, arena

    program = jax.jit(program, donate_argnums=2)

    def run(ts, times):
        nonlocal arena                       # donated to every call
        for _ in range(times):
            total, arena = program(q, row, arena, pt, ts)
        return total.block_until_ready()

    def check(ts, done):
        """The kernel against a gather and two einsums on the same chip."""
        from paddle_tpu.models._latent import absorbed_attention
        got, after = jax.jit(pa.latent_paged_attention)(
            q, row, arena + 0, 1, pt, ts, done)
        at = jnp.where(done, 0, pt[jnp.arange(slots), ts // BLOCK])
        want_arena = arena.at[1, 0, at, 0, ts % BLOCK].set(row)
        cached = want_arena[1, 0, pt, 0].reshape(slots, pages * BLOCK, WIDTH)
        want = absorbed_attention(
            q, cached, jnp.arange(pages * BLOCK)[None] <= ts[:, None])
        live = ~np.asarray(done)
        err = np.abs(np.asarray(got, np.float32)
                     - np.asarray(want, np.float32))[live].max()
        same = bool((after[:, :, 1:] == want_arena[:, :, 1:]).all())
        if not (err < 0.02 and same and not np.asarray(got)[~live].any()):
            raise SystemExit(f"the kernel disagrees with the gather: largest "
                             f"error {err}, arena equal {same}")
        return float(err)

    def time_at(live_pages):
        """live_pages: one length for every slot, or a length a slot."""
        ts = (jnp.broadcast_to(jnp.asarray(live_pages, jnp.int32), (slots,))
              * BLOCK - BLOCK // 2 - 1)
        if not bool(jnp.isfinite(run(ts, 1)).all()):
            raise SystemExit(
                f"the kernel's output is not finite at {live_pages} pages")
        with tempfile.TemporaryDirectory() as trace_dir:
            jax.profiler.start_trace(trace_dir)
            t0 = time.perf_counter()
            run(ts, programs)
            host = (time.perf_counter() - t0) / (programs * per_program)
            jax.profiler.stop_trace()
            events, seconds = kernel_seconds(trace_dir)
        if events != programs * per_program:
            raise SystemExit(f"{events} events named {KERNEL} in the trace, "
                             f"{programs * per_program} calls made")
        return seconds / events, host

    # slots of every length from one row to the whole table, some frozen
    lengths = rng.integers(1, pages * BLOCK + 1, slots)
    lengths[:4] = (1, BLOCK, BLOCK + 1, pages * BLOCK)
    error = check(jnp.asarray(lengths - 1, jnp.int32),
                  jnp.arange(slots) % 7 == 5)
    return time_at, error


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="moonlight,xing")
    ap.add_argument("--pages", default="1,2,3,4,5,8,16,32,64,120")
    ap.add_argument("--walk", default=None, metavar="G,BUFFERS")
    ap.add_argument("--repo", default=os.path.join(os.path.dirname(__file__), ".."))
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.repo))

    import jax
    import numpy as np
    if jax.default_backend() != "tpu":
        print(f"a chip is required; the backend is {jax.default_backend()!r}",
              file=sys.stderr)
        return 1
    from paddle_tpu.ops import paged_attention as pa

    walk = None
    if args.walk:
        walk = tuple(int(v) for v in args.walk.split(","))
        if not hasattr(pa, "_latent_walk"):
            print("this checkout's kernel has no _latent_walk to replace",
                  file=sys.stderr)
            return 1
        pa._latent_walk = lambda *shape: walk
    results = []
    for name in args.shapes.split(","):
        shape = SHAPES[name]
        slots, pages = shape["slots"], shape["pages"]
        rows = []
        jax.clear_caches()
        time_at, error = prepare(pa, **shape)
        for live in (int(v) for v in args.pages.split(",")):
            if live > pages:
                continue
            device, host = time_at(live)
            fetched = slots * live * BLOCK * WIDTH * 2 / HBM_BYTES_PER_S
            counted = (slots * (live * BLOCK - BLOCK // 2) * ROW_BYTES_COUNTED
                       / HBM_BYTES_PER_S)
            rows.append({"pages": live, "call_us": device * 1e6,
                         "slot_us": device * 1e6 / slots,
                         "host_call_us": host * 1e6,
                         "fetch_floor_us": fetched * 1e6,
                         "fetched_share": 100 * fetched / device,
                         "roofline_as_counted": 100 * counted / device})
        b, a = np.polyfit([r["pages"] for r in rows],
                          [r["slot_us"] for r in rows], 1)
        # the cell's own spread of lengths, one slot each (1k-7.7k rows of
        # 8k; 2k-16k of 16k), the step's row in the middle of its page
        mixed = [max(1, round(pages * (0.125 + 0.83 * i / (slots - 1))))
                 for i in range(slots)]
        device, _ = time_at(mixed)
        mixed_counted = (sum(m * BLOCK - BLOCK // 2 for m in mixed)
                         * ROW_BYTES_COUNTED / HBM_BYTES_PER_S)
        chosen = walk
        if chosen is None and hasattr(pa, "_latent_walk"):
            chosen = pa._latent_walk(shape["heads"], pages, WIDTH, BLOCK, 2)
        result = {"shape": name, **shape, "walk": chosen,
                  "device": jax.devices()[0].device_kind,
                  "a_us_per_slot": float(a), "b_us_per_page": float(b),
                  "mixed_call_us": device * 1e6,
                  "mixed_roofline_as_counted": 100 * mixed_counted / device,
                  "largest_error_against_gather": error,
                  "dma_us_per_page": BLOCK * WIDTH * 2 / HBM_BYTES_PER_S * 1e6,
                  "rows": rows}
        print(json.dumps(result), flush=True)
        results.append(result)
    out = os.path.join(os.path.dirname(__file__), "..", "chiprun_out")
    os.makedirs(out, exist_ok=True)
    tag = "" if walk is None else "." + "x".join(map(str, walk))
    with open(os.path.join(out, f"bench_latent_decode{tag}.json"), "w") as f:
        json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
