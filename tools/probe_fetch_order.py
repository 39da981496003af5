"""Does a fetch of program A's scalar return when A ends, with program B
queued behind it? `chiprun -- python tools/probe_fetch_order.py` (a
minute; `--tiny` rehearses it on the CPU, whose numbers mean nothing).

The serving scheduler queues a prefill and its sampler (A, ~20 ms), then
the next decode chunk (B, ~60 ms, reading A's carry), and only then reads
A's first token. Were the copy held behind B, every first token would come
a chunk late. Prints, per way of reading, the median ms from A's dispatch
to the fetch's return and to B's end, and A's and B's own lengths."""
import statistics
import sys
import time

import jax
import jax.numpy as jnp

n, a_steps, b_steps = (256, 2, 6) if "--tiny" in sys.argv else (4096, 30, 90)


def chain(steps):
    def body(x):
        for _ in range(steps):
            x = jnp.tanh(x @ x) * 0.5
        return x, jnp.argmax(x[0]).astype(jnp.int32)
    return jax.jit(body)


prog_a, prog_b = chain(a_steps), chain(b_steps)
x = jnp.eye(n, dtype=jnp.bfloat16)
for prog in (prog_a, prog_b):
    jax.block_until_ready(prog(x))          # compile


def run(way):
    t0 = time.perf_counter()
    carry, first = prog_a(x)
    if way == "copy_before_launch":
        first.copy_to_host_async()
    done = [None] if way == "alone" else prog_b(carry)
    if way == "copy_after_launch":
        first.copy_to_host_async()
    int(first)
    t_fetch = time.perf_counter() - t0
    jax.block_until_ready(done)
    return t_fetch * 1e3, (time.perf_counter() - t0) * 1e3


print("device", jax.devices()[0].device_kind, "n", n)
for way in ("alone", "plain_fetch", "copy_before_launch", "copy_after_launch"):
    runs = [run(way) for _ in range(9)]
    print(f"{way}: fetch_ms {statistics.median(r[0] for r in runs):.3f} "
          f"all_done_ms {statistics.median(r[1] for r in runs):.3f}")
