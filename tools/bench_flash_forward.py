"""The flash forward alone at the serving cells' shapes: time against the
tiles it computes, and the fit t = a x computed visits + b x idle steps.

Times `ops/flash_attention._flash_call` (the forward every caller runs:
`flash_causal_rows` for the three serving blocks, `attention_fwd_lse` for
training) in the (heads, rows, d) layout the kernel reads, bfloat16, at:
Xing 32 heads 192/192/128 in buckets 2048-16384 with the cell's eight
prompt lengths; Moonlight 16 heads in its four buckets; Mellum 32 heads
over 4 KV heads of 128, full and `window=1024`; GPT-2 XL 25 x 64 in its
one-tile buckets; training 8 x 12 heads x 64 at 1,024 rows. Beside each time
stand the visits the kernel computes, the grid steps that compute nothing
(the parent's fetch above the diagonal; the change's steps past the dynamic
count), and the share of 197 TFLOP/s over the REAL rows' triangle or band
(2 x (d + dv) x pairs for the two products). `a` and `b` are fitted by least
squares over a family's shapes of one tile size.

The time is the kernel's own device time, read from a profiler trace (the
Mosaic custom calls of a program that holds nothing else of weight). A
chip is required: on any other backend it exits 1 with nothing measured.

    chiprun -- python tools/bench_flash_forward.py
    chiprun -- python tools/bench_flash_forward.py --repo .scratch/parent --families xing

`--repo DIR` times the kernel of another checkout (the parent's, unpacked
by `git archive`); a checkout whose forward takes no `length` runs the
whole bucket, as its prefill does. Before it times a shape it holds the
kernel against `mha_reference` on the chip over the first head or two (real
rows within 0.02 of a float32 reference; rows past `length` zero where the
kernel promises that), and writes `real_rows_sha256`, a digest of every
head's real rows as the kernel returned them (the inputs are a function of
the shape alone): two checkouts that give the same digest gave the same
bits. Prints one JSON line a family; the same goes to
chiprun_out/bench_flash_forward[.tag].json.
"""

import argparse
import glob
import hashlib
import inspect
import json
import os
import re
import sys
import tempfile

PEAK_FLOPS = 197e12              # TPU v5e, bfloat16 (Google Cloud documentation)
CALLS = 6

# family -> (heads, kv_heads, d, dv, window, [(bucket, length), ...])
XING = [(2048, 2048), (4096, 3072), (4096, 4096), (8192, 6144), (8192, 8192),
        (12288, 10240), (12288, 12288), (16384, 15360)]
# the cell's eight prompts, then a bucket of one tile at these widths (no
# cell sends one)
MOONLIGHT = [(2048, 1024), (2048, 1536), (2048, 2048), (4096, 3072),
             (4096, 4096), (6144, 5120), (6144, 6144), (8192, 7168),
             (1024, 1024)]
MELLUM = [(512, 256), (512, 512), (1024, 768), (1024, 1024), (4096, 3072),
          (4096, 4096), (8192, 8192), (16384, 15360)]
FAMILIES = {
    "xing": (32, 32, 192, 128, None, XING),
    "moonlight": (16, 16, 192, 128, None, MOONLIGHT),
    "mellum_full": (32, 4, 128, 128, None, MELLUM),
    "mellum_window": (32, 4, 128, 128, 1024, MELLUM),
    # GPT hands the kernel no length: a bucket is one tile, nothing to skip
    "gpt_xl": (25, 25, 64, 64, None, [(512, None), (768, None), (1024, None)]),
    # 8 sequences x 12 heads through `_pick_blocks`' tiles, as training runs
    "train_s1024": (96, 96, 64, 64, None, [(1024, None)]),
}
# an instruction's opcode: the word before the first parenthesis that follows
# white space (an operand that is a custom call's result names it later)
_OPCODE = re.compile(r"\s([\w\-]+)\(")


def is_kernel(text):
    """A Mosaic call: a custom call that carries its function's name (XLA's
    own, `%custom-call.3`, are buffer tricks of a few hundred ns)."""
    found = _OPCODE.search(text.partition(" = ")[2])
    return (bool(found) and found.group(1) == "custom-call"
            and not text.startswith("%custom-call"))


def kernel_seconds(trace_dir):
    """(events, seconds) of the Mosaic calls on the first chip of a trace."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            calls = [ev for ev in line.events if is_kernel(ev.name)]
            if calls:
                return (len(calls), sum(ev.duration_ns for ev in calls) * 1e-9,
                        sorted({ev.name.partition(" = ")[0] for ev in calls}))
    return 0, 0.0, []


def tiles(rows, window, family, fa):
    """(query tile, KV tile) the checkout cuts `rows` rows of `family` into."""
    if family.startswith("train"):
        return fa._pick_blocks(rows, rows)
    if hasattr(fa, "_causal_rows_blocks"):
        return fa._causal_rows_blocks(rows, window)
    # the parent: one tile a head was `flash_causal_rows`' rule, and the
    # latent block called `_flash_call` itself
    if rows <= fa._ONE_TILE_ROWS and family not in ("xing", "moonlight"):
        return rows, rows
    return fa._pick_blocks(rows, rows)


def steps(rows, bq, bk, window, length, walks):
    """(computed visits, idle grid steps) a head: counted here from the
    rule alone, not by the kernel's code. A checkout that `walks` visits
    the tiles below `length` and idles to the bucket's static count; one
    that does not runs the rectangle (the band's few columns) and computes
    what lies at or below the diagonal."""
    def visits(j, real):
        r0, r1 = j * bq, min(j * bq + bq, real) - 1
        lo = 0 if window is None else max(r0 - (window - 1), 0)
        return r1 // bk - lo // bk + 1

    nq, nk = rows // bq, rows // bk
    in_bucket = sum(visits(j, rows) for j in range(nq))
    if not walks:
        band = nk if window is None else min(nk, -(-(window - 1) // bk) + 1)
        return in_bucket, nq * band - in_bucket
    live = nq if length is None else -(-length // bq)
    computed = sum(visits(j, rows) for j in range(live))
    return computed, in_bucket - computed - (nq - live)


def pairs(length, window):
    """(row, column) pairs the real rows attend."""
    if window is None or window >= length:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def measure(fa, family, takes_length):
    import jax
    import jax.numpy as jnp
    import numpy as np

    heads, kv_heads, d, dv, window, shapes = FAMILIES[family]
    train = family.startswith("train")
    scale = 1.0 / np.sqrt(d)
    rows_out = []
    for bucket, length in shapes:
        key = jax.random.split(jax.random.PRNGKey(bucket), 3)
        q = jax.random.normal(key[0], (heads, bucket, d), jnp.bfloat16)
        k = jax.random.normal(key[1], (kv_heads, bucket, d), jnp.bfloat16)
        v = jax.random.normal(key[2], (kv_heads, bucket, dv), jnp.bfloat16)
        bq, bk = tiles(bucket, window, family, fa)
        kw = {} if train else dict(blocks=(bq, bk),
                                   group=heads // kv_heads, window=window)
        ragged = takes_length and length is not None

        def forward(q, k, v, n, kw=kw, ragged=ragged):
            if ragged:
                kw = dict(kw, length=n)
            return fa._flash_call(q, k, v, None, True, float(scale), False,
                                  **kw)[0]

        forward = jax.jit(forward)
        n = jnp.int32(bucket if length is None else length)
        real = bucket if length is None else length

        # the kernel against a float32 reference, the first head or two
        nh = 2 if bucket <= 8192 else 1
        kv = np.arange(nh) // (heads // kv_heads)
        out = forward(q, k, v, n)
        digest = hashlib.sha256(np.asarray(out[:, :real]).tobytes()).hexdigest()
        got = np.asarray(out[:nh], np.float32)
        i = jnp.arange(bucket)
        mask = i[None, :] <= i[:, None]
        if window is not None:
            mask = mask & (i[:, None] - i[None, :] < window)
        q2, k2, v2 = (x.astype(jnp.float32).swapaxes(0, 1)[None]
                      for x in (q[:nh], k[kv], v[kv]))
        with jax.default_matmul_precision("highest"):
            # mha_reference wants equal widths: zero-extend v, cut the answer
            want = fa.mha_reference(
                q2, k2, jnp.pad(v2, ((0, 0),) * 3 + ((0, d - dv),)),
                bias=jnp.where(mask, 0.0, -1e30)[None, None],
                sm_scale=float(scale))[0]
        want = np.asarray(want.swapaxes(0, 1)[..., :dv])
        del q2, k2, v2, mask
        error = float(np.abs(got[:, :real] - want[:, :real]).max())
        past = float(np.abs(got[:, real:]).max()) if real < bucket else 0.0
        if not error < 0.02 or (ragged and past != 0.0):
            raise SystemExit(
                f"{family} {bucket}/{length}: the kernel disagrees with "
                f"mha_reference: largest error {error}, past the length "
                f"{past}")

        with tempfile.TemporaryDirectory() as trace_dir:
            jax.profiler.start_trace(trace_dir)
            for _ in range(CALLS):
                out = forward(q, k, v, n)
            out.block_until_ready()
            jax.profiler.stop_trace()
            events, seconds, names = kernel_seconds(trace_dir)
        if events != CALLS:
            raise SystemExit(f"{events} Mosaic calls in the trace, "
                             f"{CALLS} made: {names}")
        call = seconds / events
        computed, idle = steps(bucket, bq, bk, window, length, takes_length)
        flops = 2 * (d + dv) * pairs(real, window) * heads
        rows_out.append({
            "bucket": bucket, "length": real, "tile": [bq, bk],
            "call_us": call * 1e6, "head_us": call * 1e6 / heads,
            "computed_visits": computed, "idle_steps": idle,
            "us_per_computed_visit": call * 1e6 / heads / computed,
            "peak_share_real_rows": 100 * flops / PEAK_FLOPS / call,
            "largest_error": error, "largest_past_length": past,
            "real_rows_sha256": digest[:16]})
    result = {"family": family, "heads": heads, "kv_heads": kv_heads,
              "d": d, "dv": dv, "window": window, "rows": rows_out}
    # t = a x computed visits + b x idle steps, a tile size at a time (a
    # family whose long buckets take larger tiles has two fits)
    fits = {}
    for tile in sorted({tuple(r["tile"]) for r in rows_out
                        if r["tile"][1] < r["bucket"]}):
        many = [r for r in rows_out if tuple(r["tile"]) == tile]
        if len(many) < 3:
            continue
        A = np.asarray([[r["computed_visits"], r["idle_steps"]] for r in many],
                       float)
        t = np.asarray([r["head_us"] for r in many])
        if not A[:, 1].any():
            A = A[:, :1]
        fit = np.linalg.lstsq(A, t, rcond=None)[0]
        fits["x".join(map(str, tile))] = {
            "a_us_per_computed_visit": float(fit[0]),
            "b_us_per_idle_step": float(fit[1]) if len(fit) > 1 else None,
            "largest_residual_us": float(np.abs(A @ fit - t).max())}
    result["fits"] = fits
    return result


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--families", default=",".join(FAMILIES))
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

    takes_length = "length" in inspect.signature(fa._flash_call).parameters
    results = []
    for family in args.families.split(","):
        jax.clear_caches()
        result = dict(measure(fa, family, takes_length),
                      walks_visits=takes_length,
                      device=jax.devices()[0].device_kind)
        print(json.dumps(result), flush=True)
        results.append(result)
    out = os.path.join(os.path.dirname(__file__), "..", "chiprun_out")
    os.makedirs(out, exist_ok=True)
    tag = "." + args.tag if args.tag else ""
    with open(os.path.join(out, f"bench_flash_forward{tag}.json"), "w") as f:
        json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
