"""Executor.run's host time by phase, and inside its two widest phases, at the
training cells' own size: GPT-2 small, s1024, 8 sequences a chip, Adam under
AMP, the loss fetched every step (so everything the host does between a fetch
and the next launch is idle chip time).

    chiprun -- python tools/bench_executor_phases.py                (one chip)
    chiprun --chips 4 -- python tools/bench_executor_phases.py --chips 4

It measures twice in one process, the profiler off and then on, and prints one
JSON line a window plus a last line `{"ok": true, ...}`; the whole record goes
to `chiprun_out/executor_phases.<tag>.<chips>.json`.

- `phases_ms`: each `executor/*` span a step, by the host's clock (the spans
  are the program's own; this file only listens to them).
- `place_ms`: `executor/place` split by whoever did the work: `as_feed_array`
  (host array -> device), `shard_feed`, `place_scope` by call (the mutable
  walk, then the read-only one), and `_put` with the number of values it was
  handed a step.
- `dispatch_ms`: the compiled call as the host sees it, and `launch_to_done` =
  the call's start to the end of the fetch. Less the device's own time a step
  (`device_step_ms`, from the traced window's `XLA Modules`) that is what
  PRECEDES the launch; `dispatch` less that is what follows it.
- traced window only: `launch_after_ms` = the first chip's program start less
  `executor/dispatch`'s start, on the profiler's clock; `fetch_after_done_ms` =
  `executor/fetch`'s end less that program's end; and the runtime's own host
  events under `executor/dispatch` by name (`dispatch_events_ms`).

The first eight losses are printed as hex floats: two trees on one seed
compute the same step when they print the same eight.
"""

import argparse
import contextlib
import json
import os
import statistics
import sys
import time
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))


class Clock:
    """Seconds by name and step; `numbered` tells a name's calls within one
    step apart (place_scope's two walks)."""

    def __init__(self):
        self.on = False
        self.step = {}
        self.steps = []

    def add(self, name, seconds, count=None):
        if not self.on:
            return
        self.step[name] = self.step.get(name, 0.0) + seconds
        if count is not None:
            self.step[name + "#"] = self.step.get(name + "#", 0) + count

    def numbered(self, name):
        k = self.step.get("calls:" + name, 0)
        self.step["calls:" + name] = k + 1
        return f"{name}[{k}]"

    def close_step(self):
        if self.on:
            self.steps.append(self.step)
        self.step = {}

    def medians_ms(self):
        names = sorted({n for s in self.steps for n in s if not n.startswith("calls:")})
        out = {}
        for n in names:
            vals = [s.get(n, 0.0) for s in self.steps]
            out[n] = statistics.median(vals) if n.endswith("#") else \
                round(1e3 * statistics.median(vals), 4)
        return out


def timed(clock, name, fn, count=None, numbered=False):
    def wrapper(*a, **kw):
        label = clock.numbered(name) if numbered and clock.on else name
        t = time.perf_counter()
        try:
            return fn(*a, **kw)
        finally:
            clock.add(label, time.perf_counter() - t,
                      None if count is None else count(*a, **kw))
    return wrapper


def instrument(clock):
    """Listen to the executor's spans and to the plan's hooks. Nothing the
    step computes changes: every wrapper calls what it wraps."""
    from paddle_tpu.framework import executor as ex
    from paddle_tpu.parallel import plan as plan_mod

    real_span = ex.trace_span

    @contextlib.contextmanager
    def span(name, cat="", args=None):
        t = time.perf_counter()
        with real_span(name, cat, args) as sp:
            yield sp
        clock.add(name, time.perf_counter() - t)
        if name == "executor/dispatch":
            clock.step["calls:t_dispatch"] = t
        if name == "executor/fetch":
            clock.add("launch_to_done", time.perf_counter() - clock.step.get("calls:t_dispatch", t))

    ex.trace_span = span
    ex._as_feed_array = timed(clock, "as_feed_array", ex._as_feed_array)
    P = plan_mod.ShardingPlan
    P.shard_feed = timed(clock, "shard_feed", P.shard_feed)
    P.place_scope = timed(clock, "place_scope", P.place_scope, numbered=True,
                          count=lambda self, vals: len(vals))
    real_put = P._put

    def put(self, v, sharding):
        cur = getattr(v, "sharding", None)
        moved = not (cur is not None and cur == sharding)
        t = time.perf_counter()
        try:
            return real_put(self, v, sharding)
        finally:
            clock.add("_put", time.perf_counter() - t, int(moved))
    P._put = put


def wrap_compiled(exe, clock):
    """Time the compiled step's call alone, inside executor/dispatch."""
    for key, compiled in list(exe._cache.items()):
        exe._cache[key] = timed(clock, "compiled_call", compiled)


def trace_facts(trace_dir):
    """From the xplane: the first chip's program time a step, the launch's
    distance from executor/dispatch's start, and the runtime's own host events
    under executor/dispatch."""
    from lib import trace_reduce

    path = trace_reduce.find_xplane(trace_dir)
    if path is None:
        return None
    planes = trace_reduce.load(path)
    devices = sorted(p for p in planes if p.startswith(trace_reduce.DEVICE_PLANE))
    if not devices:
        return None
    every = sorted((s, d, n) for n, s, d in planes[devices[0]].get(trace_reduce.MODULES_LINE, []))
    by_program = defaultdict(float)
    for _, d, n in every:
        by_program[n] += d
    # the step is the program with the most device time; a feed re-sharded on
    # the device (`jit__multi_slice`) is a program too, and runs under place
    step_program = max(by_program, key=by_program.get) if by_program else None
    modules = [m for m in every if m[2] == step_program]
    out = {"device_step_ms": round(statistics.median(d for _, d, _ in modules) / 1e6, 4)
           if modules else None,
           "programs_ms": {n: round(v / 1e6 / max(1, len(modules)), 4)
                           for n, v in by_program.items()}}
    ops = planes[devices[0]].get(trace_reduce.OPS_LINE, [])
    busy = trace_reduce.total(trace_reduce.union((s, s + d) for _, s, d in ops))
    out["device_busy_ms_per_step"] = round(busy / 1e6 / max(1, len(modules)), 4)
    after, tail, events, tail_events, n = [], [], defaultdict(float), defaultdict(float), 0
    for plane, lines in planes.items():
        if not plane.startswith("/host:"):
            continue
        for line, evs in lines.items():
            spans = [(s, s + d) for name, s, d in evs if name == "executor/dispatch"]
            if not spans:
                continue
            n += len(spans)
            for end in (s + d for name, s, d in evs if name == "executor/fetch"):
                done = max((ms + md for ms, md, _ in modules if ms + md <= end), default=None)
                if done is None:
                    continue
                tail.append((end - done) / 1e6)
                for other in lines.values():          # whatever any host thread did then
                    for name, s, d in other:
                        if done <= s and s + d <= end and not name.startswith("executor/"):
                            tail_events[name.split("(")[0][:60]] += d
            for lo, hi in spans:
                first = next((s for s, _, _ in modules if s >= lo), None)
                if first is not None:
                    after.append((first - lo) / 1e6)
                for name, s, d in evs:
                    if lo <= s and s + d <= hi and name != "executor/dispatch":
                        events[name.split("(")[0][:60]] += d
    out["launch_after_ms"] = round(statistics.median(after), 4) if after else None
    # the program's end on the first chip to the end of executor/fetch: the
    # loss's way back to the host
    out["fetch_after_done_ms"] = round(statistics.median(tail), 4) if tail else None
    out["fetch_tail_events_ms"] = {k: round(v / 1e6 / max(1, len(tail)), 4) for k, v in
                                   sorted(tail_events.items(), key=lambda kv: -kv[1])[:12]}
    out["dispatch_spans"] = n
    out["dispatch_events_ms"] = {k: round(v / 1e6 / max(1, n), 4) for k, v in
                                 sorted(events.items(), key=lambda kv: -kv[1])[:16]}
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--seed", type=int, default=4100000041)
    ap.add_argument("--warm", type=int, default=8)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--traced-steps", type=int, default=30)
    ap.add_argument("--tiny", action="store_true", help="a CPU rehearsal's size")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    ap.add_argument("--tag", default="", help="part of the record's file name")
    args = ap.parse_args()

    import jax
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu.models.gpt import gpt_lm_program
    from lib import model, traffic

    with open(os.path.join(ROOT, "benchmarks", "configs", "gpt2-small.json")) as f:
        cfg = json.load(f)
    seq, per_chip = 1024, 8
    if args.tiny:
        cfg.update(n_embd=64, n_layer=2, n_head=4, n_inner=128, vocab_size=512, n_positions=64)
        seq, per_chip = 64, 2
    batch = per_chip * args.chips
    main_p, startup, fetches = gpt_lm_program(model.gpt_config(cfg), seq,
                                              learning_rate=1e-4, amp=True)
    startup.random_seed = main_p.random_seed = model.fold_seed(args.seed)
    loss_var = fetches["loss"]
    target = main_p
    if args.chips > 1:
        target = pt.CompiledProgram(main_p).with_data_parallel(loss_name=loss_var.name)
    batches = traffic.train_batches(args.seed, 8, batch, seq, cfg["vocab_size"])

    clock = Clock()
    instrument(clock)
    exe, scope, losses = pt.Executor(), pt.Scope(), []

    def step(k):
        out, = exe.run(target, feed={"tokens": batches[k % len(batches)]},
                       fetch_list=[loss_var])
        losses.append(float(np.asarray(out).reshape(-1)[0]))
        clock.close_step()

    def window(steps, k0):
        clock.steps, clock.on = [], True
        t = time.perf_counter()
        for k in range(k0, k0 + steps):
            step(k)
        wall = time.perf_counter() - t
        clock.on = False
        return {"steps": steps, "step_ms": round(1e3 * wall / steps, 4),
                "medians_ms": clock.medians_ms()}

    dev = jax.devices()[0]
    record = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": jax.device_count()},
              "chips": args.chips, "seed": args.seed, "batch": batch, "seq": seq}
    with pt.scope_guard(scope):
        exe.run(startup)
        for k in range(args.warm):
            step(k)
        record["first_losses_hex"] = [float(x).hex() for x in losses[:8]]
        record["first_losses"] = losses[:8]
        wrap_compiled(exe, clock)
        k = args.warm
        record["profiler_off"] = window(args.steps, k)
        k += args.steps
        name = f"executor_phases.{args.tag or 'tree'}.{args.chips}"
        trace_dir = os.path.join(args.out, name + ".trace")
        from lib import tracing
        traced = tracing.TracedWindow(trace_dir)
        traced.start()
        record["profiler_on"] = window(args.traced_steps, k)
        traced.stop()
        k += args.traced_steps
        # the profiler off again, behind the traced window: the same program,
        # so a drift of the machine shows as a difference between the two
        record["profiler_off_again"] = window(args.steps, k)
        record["compiles"] = exe.compile_count
    record["trace"] = trace_facts(trace_dir)
    import shutil
    shutil.rmtree(trace_dir, ignore_errors=True)    # hundreds of MB: the facts are kept
    from paddle_tpu.observability.metrics import get_registry
    reg = get_registry()
    have = {fam.name for fam in reg.families()}
    for counter in ("executor_scope_vars_placed_total", "executor_scope_in_place_runs_total",
                    "executor_runs_total"):
        # a tree from before the counters reads None, and says so
        record[counter] = reg.counter(counter).value if counter in have else None
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, name + ".json"), "w") as f:
        json.dump(record, f, indent=1)
    for key in ("profiler_off", "profiler_on", "profiler_off_again", "trace"):
        print(json.dumps({key: record[key]}))
    print(json.dumps({"first_losses_hex": record["first_losses_hex"]}))
    print(json.dumps({"ok": True, "device": record["device"], "chips": args.chips,
                      "placed_total": record["executor_scope_vars_placed_total"],
                      "in_place_runs": record["executor_scope_in_place_runs_total"]}))


if __name__ == "__main__":
    main()
