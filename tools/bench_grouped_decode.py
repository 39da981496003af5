"""The grouped paged kernel alone: time against live pages, and the fit
t = S x (a + b x pages).

Times `ops/paged_attention.paged_attention` with a group of query heads a
KV head at the shapes the three cells that decode through it serve (pages
of 128 rows, 256 lanes of K|V a KV head, bfloat16):

    mellum.window    48 slots, 4 KV heads x 8 queries, a ring of 9 pages
    mellum.full      the same heads over a table of 128 pages
    commanda.window  32 slots, 8 KV heads x 16 queries, a ring of 33 pages
    commanda.full    the same heads over a table of 80 pages
    sdar             64 slots, 4 KV heads x 8 queries x B = 4 block rows,
                     a table of 32 pages

every slot at the same number of live pages, for a list of them. `a` is
what a slot costs whatever its length (priming, the live page, the
write-back), `b` what one more page costs; beside each time stand the
bytes of the fetched pages over the chip's 819 GB/s (the least a walk can
take) and the share a `gqa_decode_hbm_roofline*` metric would read of that
call (the rows in range, 512 B a row a KV head, over the same peak). After
the sweep it times one call with the cell's own mix of lengths (`mixed_*`),
and at the end prints what each cell's metric would read of its layers
together (Mellum 6 window + 2 full, command-a 3 + 1, SDAR full alone).

The time is the kernel's own device time, read from a profiler trace by
the kernel's name (what the cells' metrics read); the host's clock per
call stands beside it as a check. A chip is required: on any other
backend it exits 1 with nothing measured.

    chiprun -- python tools/bench_grouped_decode.py
    chiprun -- python tools/bench_grouped_decode.py --shapes mellum.window --pages 1,2,3,5,9 --walk 3,3

`--walk G,BUFFERS` replaces the kernel's own pages a group and group
buffers for a sweep of variants; nothing but this tool sets it. `--repo
DIR` times the kernel of another checkout (the parent's, unpacked by `git
archive`); `--tag` names the record. Before it times a shape it holds the
kernel against a gather and two einsums on the same chip (slots of every
length, some frozen; the arena compared whole) and keeps a digest of that
call's outputs and arena: two checkouts that print the same digests
computed the same bits. Prints one JSON line a shape; the same goes to
chiprun_out/bench_grouped_decode.<tag>.json.
"""

import argparse
import hashlib
import json
import os
import sys
import tempfile
import time

from bench_latent_decode import kernel_seconds

HBM_BYTES_PER_S = 819e9          # TPU v5e (Google Cloud documentation)
BLOCK, HD = 128, 128
KERNEL = "paged_attention_grouped"
SHAPES = {
    "mellum.window": dict(slots=48, kv_heads=4, queries=8, pages=9,
                          window=1024, cell="mellum", layers=6),
    "mellum.full": dict(slots=48, kv_heads=4, queries=8, pages=128,
                        cell="mellum", layers=2),
    "commanda.window": dict(slots=32, kv_heads=8, queries=16, pages=33,
                            window=4096, cell="commanda", layers=3),
    "commanda.full": dict(slots=32, kv_heads=8, queries=16, pages=80,
                          cell="commanda", layers=1),
    "sdar": dict(slots=64, kv_heads=4, queries=8, pages=32, block=4,
                 cell="sdar", layers=6),
}
# a cell's prompt lengths, the quarter of its longest answer and its longest
# sequence (benchmarks/traffic/*.json)
CELLS = {"mellum": ((256, 512, 768, 1024, 3072, 4096, 8192, 15360), 128, 16384),
         "commanda": ((1024, 2048, 3072, 4096, 5120, 6144, 7168, 8192), 256,
                      10240),
         "sdar": ((256, 512, 768, 1024, 1536, 2048, 2560, 3072), 256, 4096)}
PAGES = "1,2,3,4,5,8,9,16,32,33,64,80,120"


def prepare(pa, slots, kv_heads, queries, pages, window=None, block=1, **_):
    """One shape's operands and its jitted program of 16 calls; returns
    (time_at, check). Lengths and bounds are runtime values: one compile a
    shape and one for the check."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(slots + pages)
    w, heads = 2 * HD, kv_heads * queries
    arena = jax.random.normal(
        jax.random.PRNGKey(0), (1, 1, 1 + slots * pages, kv_heads, BLOCK, w),
        jnp.bfloat16)
    pt = jnp.asarray(1 + rng.permutation(slots * pages).reshape(slots, pages),
                     jnp.int32)
    lead = (slots, block) if block > 1 else (slots,)
    q = jnp.asarray(rng.normal(0, 1, lead + (heads, HD)), jnp.bfloat16)
    k, v = (jnp.asarray(rng.normal(0, 1, lead + (kv_heads, HD)), jnp.bfloat16)
            for _ in range(2))
    per_program, programs = 16, 8

    def attend(arena, ts, lo, done=None):
        return pa.paged_attention(q, k, v, arena, 0, pt, ts, done,
                                  lo=None if block > 1 else lo)

    def program(arena, ts, lo):
        def body(_, carry):
            total, arena = carry
            out, arena = attend(arena, ts, lo)
            return total + out.astype(jnp.float32), arena
        return jax.lax.fori_loop(
            0, per_program, body, (jnp.zeros(q.shape, jnp.float32), arena))

    program = jax.jit(program, donate_argnums=0)

    def run(ts, lo, times):
        nonlocal arena                       # donated to every call
        for _ in range(times):
            total, arena = program(arena, ts, lo)
        return total.block_until_ready()

    def bounds(lengths):
        """(ts, lo) of slots that hold `lengths` rows each with the step's
        (or the pass's) new rows: a window slot attends its last `window`."""
        ts = jnp.asarray(lengths, jnp.int32) - block
        lo = jnp.maximum(ts - window + 1, 0) if window else jnp.zeros_like(ts)
        return ts, lo

    def check(lengths, done):
        """The kernel against a gather and two einsums on the same chip;
        returns (largest error, a digest of the outputs and the arena)."""
        ts, lo = bounds(lengths)
        got, after = jax.jit(attend)(arena + 0, ts, lo, done)
        new = jnp.concatenate([k, v], -1).reshape(slots, block, kv_heads, w)
        at = ts[:, None] + jnp.arange(block)[None]               # (S, B)
        blk = jnp.where(done[:, None], 0,
                        pt[jnp.arange(slots)[:, None], at // BLOCK % pages])
        want_arena = arena.at[0, 0, blk, :, at % BLOCK].set(new)

        @jax.jit
        def gather(want_arena):
            # a slot's pages in range, from its first: at most the table's
            # width of them, (S, P, kv_heads, BLOCK, w) as they lie
            page = (lo // BLOCK)[:, None] + jnp.arange(pages)[None]
            rows = want_arena[0, 0,
                              pt[jnp.arange(slots)[:, None], page % pages]]
            pos = page[:, :, None] * BLOCK + jnp.arange(BLOCK)
            keep = (pos >= lo[:, None, None]) \
                & (pos < (ts + block)[:, None, None])
            qg = q.reshape(slots, block, kv_heads, queries, HD)
            sc = jnp.einsum("sbkgd,spknd->sbkgpn", qg, rows[..., :HD],
                            preferred_element_type=jnp.float32) / np.sqrt(HD)
            sc = jnp.where(keep[:, None, None, None], sc, -1e30)
            pr = jnp.exp(sc - sc.max((-2, -1), keepdims=True))
            ctx = jnp.einsum("sbkgpn,spknd->sbkgd", pr.astype(rows.dtype),
                             rows[..., HD:],
                             preferred_element_type=jnp.float32)
            return ctx / pr.sum((-2, -1))[..., None]

        want = gather(want_arena)
        live = ~np.asarray(done)
        got32 = np.asarray(got, np.float32).reshape(want.shape)
        err = np.abs(got32 - np.asarray(want))[live].max()
        same = bool((after[:, :, 1:] == want_arena[:, :, 1:]).all())
        if not (err < 0.03 and same and not got32[~live].any()):
            raise SystemExit(f"the kernel disagrees with the gather: largest "
                             f"error {err}, arena equal {same}")
        digest = hashlib.sha256()
        for array in (got, after):
            digest.update(np.asarray(array).view(np.uint8).data)
        return float(err), digest.hexdigest()[:16]

    def time_at(lengths):
        """lengths: the rows a slot holds, one for all or one a slot.
        Returns (seconds a call on the device, on the host's clock, the
        bytes of the rows in range as the metrics count them)."""
        ts, lo = bounds(jnp.broadcast_to(jnp.asarray(lengths), (slots,)))
        if not bool(jnp.isfinite(run(ts, lo, 1)).all()):
            raise SystemExit("the kernel's output is not finite")
        with tempfile.TemporaryDirectory() as trace_dir:
            jax.profiler.start_trace(trace_dir)
            t0 = time.perf_counter()
            run(ts, lo, programs)
            host = (time.perf_counter() - t0) / (programs * per_program)
            jax.profiler.stop_trace()
            events, seconds = kernel_seconds(trace_dir, KERNEL)
        if events != programs * per_program:
            raise SystemExit(f"{events} events named {KERNEL} in the trace, "
                             f"{programs * per_program} calls made")
        counted = int((ts + block - lo).sum()) * kv_heads * w * 2
        return seconds / events, host, counted

    return time_at, check


def mixed_lengths(cell, slots):
    """The cell's own mix: its prompt lengths a slot each in turn, an
    answer partly written behind each."""
    lens, answer, longest = CELLS[cell]
    return [min(lens[i % len(lens)] + 4 * ((37 * i) % answer + 1), longest)
            for i in range(slots)]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--pages", default=PAGES)
    ap.add_argument("--walk", default=None, metavar="G,BUFFERS")
    ap.add_argument("--tag", default="change")
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
        pa._GROUPED_PAGES, pa._GROUPED_BUFFERS = walk
    results = []
    for name in args.shapes.split(","):
        shape = SHAPES[name]
        slots, pages = shape["slots"], shape["pages"]
        window, block = shape.get("window"), shape.get("block", 1)
        # a page's K|V of every KV head at the chip's bandwidth
        page_us = shape["kv_heads"] * BLOCK * 2 * HD * 2 / HBM_BYTES_PER_S * 1e6
        jax.clear_caches()
        time_at, check = prepare(pa, **shape)
        # slots of every length from one row (one block) to the cell's
        # longest sequence (many rings long), some frozen
        longest = CELLS[shape["cell"]][2]
        rng = np.random.default_rng(pages)
        lengths = rng.integers(1, longest // block + 1, slots) * block
        lengths[:4] = (block, BLOCK, BLOCK + block, longest)
        error, digest = check(lengths, np.arange(slots) % 7 == 5)
        rows = []
        for live in (int(v) for v in args.pages.split(",")):
            if live > pages:
                continue
            # the new rows in the middle of the live page. A ring's last
            # page more than the window's own: the slot holds three rings
            # of rows, its range begins mid-page and the ring has wrapped
            held = live * BLOCK - BLOCK // 2
            if window and live == pages:
                held += 3 * pages * BLOCK
            device, host, counted = time_at(held)
            floor = slots * live * page_us * 1e-6
            rows.append({"pages": live, "call_us": device * 1e6,
                         "slot_us": device * 1e6 / slots,
                         "host_call_us": host * 1e6,
                         "fetch_floor_us": floor * 1e6,
                         "fetched_share": 100 * floor / device,
                         "roofline_as_counted":
                             100 * counted / HBM_BYTES_PER_S / device})
        b, a = np.polyfit([r["pages"] for r in rows],
                          [r["slot_us"] for r in rows], 1)
        device, _, counted = time_at(mixed_lengths(shape["cell"], slots))
        result = {"shape": name, **shape, "tag": args.tag,
                  "walk": walk or (min(pa._GROUPED_PAGES, pages),
                                   pa._GROUPED_BUFFERS),
                  "device": jax.devices()[0].device_kind,
                  "a_us_per_slot": float(a), "b_us_per_page": float(b),
                  "mixed_call_us": device * 1e6,
                  "mixed_slot_us": device * 1e6 / slots,
                  "mixed_counted_bytes": counted,
                  "mixed_roofline_as_counted":
                      100 * counted / HBM_BYTES_PER_S / device,
                  "largest_error_against_gather": error,
                  "check_digest": digest,
                  "dma_us_per_page": page_us,
                  "rows": rows}
        print(json.dumps(result), flush=True)
        results.append(result)
    # what a cell's metric would read of its layers together
    cells = {}
    for r in results:
        c = cells.setdefault(r["cell"], [0.0, 0.0, []])
        c[0] += r["layers"] * r["mixed_counted_bytes"] / HBM_BYTES_PER_S
        c[1] += r["layers"] * r["mixed_call_us"] * 1e-6
        c[2].append(r["shape"])
    summary = {cell: {"shapes": names, "step_attention_us": 1e6 * seconds,
                      "gqa_decode_hbm_roofline_of_the_mix":
                          100 * least / seconds}
               for cell, (least, seconds, names) in cells.items()}
    print(json.dumps({"tag": args.tag, "cells": summary}), flush=True)
    out = os.path.join(os.path.dirname(__file__), "..", "chiprun_out")
    os.makedirs(out, exist_ok=True)
    tag = args.tag + ("" if walk is None else "." + "x".join(map(str, walk)))
    with open(os.path.join(out, f"bench_grouped_decode.{tag}.json"), "w") as f:
        json.dump({"shapes": results, "cells": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
