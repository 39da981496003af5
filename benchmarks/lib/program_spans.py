"""The program's own host spans in a traced run's xplane: per span name how
often it ran, how long, how long without its children, and which spans the
host was in while the first chip sat idle.

The program writes them (`paddle_tpu.observability.trace_span`, a
jax.profiler.TraceAnnotation around each phase of `Executor.run` and of an
engine tick), so they lie on the `/host:CPU` lines of whatever profiler session
is open, on the clock of the device's `XLA Ops`. A program without them (the
parent of the PR that added them) leaves a trace in which this module finds
nothing, and every reader built on it then returns None.

As a script on a trace directory, or on one `.xplane.pb` or `.textproto`, it
prints the table an operator reads:

    python3 benchmarks/lib/program_spans.py benchmarks/out/small-train-s1024.trace
"""

import argparse
import bisect
import functools
import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import trace_reduce

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "out")
# what the program's layers call their spans; every other event on a host
# line is the runtime's own
PREFIXES = ("executor/", "serving/", "host/", "comm/", "inference/")
STEP, STEP_WAIT, STEP_PLACE = "executor/run", "executor/fetch", "executor/place"
TICK, TICK_WAIT, TICK_LAUNCH = "serving/engine_step", "serving/tick/collect", "serving/tick/launch"
# every wait for the device inside a tick: its collect phase, and inside its
# admit phase the first token of an admission and the fence before a swap-out
TICK_WAITS = (TICK_WAIT, "serving/wait/first_token", "serving/wait/fence")
IDLE_WAIT = "serving/idle_wait"


def tree(events):
    """[[name, start, end, parent index or None, self_ns]] of one thread's
    spans, in order of start; a span's parent is the innermost span that
    holds it whole."""
    nodes, stack = [], []
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and nodes[stack[-1]][2] <= start:
            stack.pop()
        while stack and nodes[stack[-1]][2] < end:     # overlaps without nesting: no parent
            stack.pop()
        parent = stack[-1] if stack else None
        if parent is not None:
            nodes[parent][4] -= dur
        nodes.append([name, start, end, parent, dur])
        stack.append(len(nodes) - 1)
    return nodes


def program_threads(planes):
    """{(plane, line): tree} of the host threads that hold a program span."""
    threads = {}
    for plane, lines in planes.items():
        if not plane.startswith("/host:"):
            continue
        for line, events in lines.items():
            own = [e for e in events if e[0].startswith(PREFIXES)]
            if own:
                threads[(plane, line)] = tree(own)
    return threads


def under(nodes, i, name):
    """Is node i, or a span around it, called `name`?"""
    while i is not None:
        if nodes[i][0] == name:
            return True
        i = nodes[i][3]
    return False


def holder(nodes, i, picked):
    """The nearest span around node i that is one of `picked`, or None."""
    i = nodes[i][3]
    while i is not None and i not in picked:
        i = nodes[i][3]
    return i


def per_parent(nodes, lo, hi, parent, children, only_with=()):
    """(parents, seconds of parent, seconds inside them of the spans called
    one of `children`) over the `parent` spans that lie whole inside (lo, hi),
    none cut at an edge; with `only_with`, only those that hold a span of one
    of these names."""
    picked = {i for i, n in enumerate(nodes) if n[0] == parent and lo < n[1] and n[2] < hi}
    if only_with:
        picked = {holder(nodes, i, picked) for i, n in enumerate(nodes) if n[0] in only_with} - {None}
    inside = sum(n[2] - n[1] for i, n in enumerate(nodes)
                 if n[0] in children and holder(nodes, i, picked) is not None)
    return len(picked), sum(nodes[i][2] - nodes[i][1] for i in picked) * 1e-9, inside * 1e-9


def idle_by_span(planes, threads, lo, hi):
    """(idle seconds of the first chip in the window, {name: seconds}, seconds
    under a phase). Each gap is cut where a program span starts or ends, and
    each piece put down to the innermost span over it (`none` where there is
    none): a gap of some ms between two steps lies under several phases, and
    put down whole to the span over its middle it would change its name with
    a few microseconds. A phase is a child of a step or of a tick, or the
    driver's wait for work."""
    devices = sorted(p for p in planes if p.startswith(trace_reduce.DEVICE_PLANE)
                     and planes[p].get(trace_reduce.OPS_LINE))
    if not devices:
        return None
    ops = trace_reduce.clip(planes[devices[0]][trace_reduce.OPS_LINE], lo, hi)
    busy = trace_reduce.union((s, s + d) for _, s, d in ops)
    gaps = trace_reduce.subtract([[lo, hi]], busy)
    every = sorted(((n[1], n[2], nodes, i) for nodes in threads.values()
                    for i, n in enumerate(nodes)), key=lambda t: t[0])
    edges = sorted({t for start, end, _, _ in every for t in (start, end)})
    named, phased, open_, at = {}, 0.0, [], 0
    for start, end in gaps:
        cuts = [start] + edges[bisect.bisect_right(edges, start):bisect.bisect_left(edges, end)] + [end]
        # a sweep over the pieces in order, holding the spans open at each middle
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            while at < len(every) and every[at][0] <= mid:
                open_.append(every[at])
                at += 1
            open_ = [t for t in open_ if t[1] > mid]
            label = "none"
            if open_:
                _, _, nodes, i = max(open_, key=lambda t: (t[0], -t[1]))   # the innermost
                label = nodes[i][0]
                parent = nodes[i][3]
                if label == IDLE_WAIT or (parent is not None and (under(nodes, parent, STEP)
                                                                  or under(nodes, parent, TICK))):
                    phased += b - a
            named[label] = named.get(label, 0.0) + (b - a)
    return (trace_reduce.total(gaps) * 1e-9, {k: v * 1e-9 for k, v in named.items()},
            phased * 1e-9)


def summarize(planes):
    """What the readers and the table use, or None where the trace holds no
    program span."""
    window = trace_reduce.window_of(planes)
    threads = program_threads(planes)
    if window is None or not threads:
        return None
    lo, hi = window
    spans = {}
    for nodes in threads.values():
        for name, start, end, _, own in nodes:
            if lo < start and end < hi:     # one that touches an edge was cut there
                row = spans.setdefault(name, {"runs": 0, "total_s": 0.0, "self_s": 0.0})
                row["runs"] += 1
                row["total_s"] += (end - start) * 1e-9
                row["self_s"] += own * 1e-9
    out = {"window_s": (hi - lo) * 1e-9, "spans": spans, "idle_s": None, "idle_by_span": {},
           "idle_phased_s": None}
    steps = ticks = 0
    step_s = step_wait_s = step_place_s = tick_s = tick_wait_s = 0.0
    for nodes in threads.values():
        n, whole, wait = per_parent(nodes, lo, hi, STEP, (STEP_WAIT,))
        steps, step_s, step_wait_s = steps + n, step_s + whole, step_wait_s + wait
        step_place_s += per_parent(nodes, lo, hi, STEP, (STEP_PLACE,))[2]
        n, whole, wait = per_parent(nodes, lo, hi, TICK, TICK_WAITS, (TICK_LAUNCH, TICK_WAIT))
        ticks, tick_s, tick_wait_s = ticks + n, tick_s + whole, tick_wait_s + wait
    out.update(steps=steps, ticks=ticks,
               step_host_ms=1e3 * (step_s - step_wait_s) / steps if steps else None,
               step_place_ms=1e3 * step_place_s / steps if steps else None,
               tick_host_ms=1e3 * (tick_s - tick_wait_s) / ticks if ticks else None)
    idle = idle_by_span(planes, threads, lo, hi)
    if idle is not None:
        out["idle_s"], out["idle_by_span"], out["idle_phased_s"] = idle
    return out


@functools.lru_cache(maxsize=2)
def summary_at(path):
    """The summary of a trace directory, an `.xplane.pb` or a `.textproto`
    (None where there is no trace or no program span in it); kept for the
    readers of one run."""
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    if path is None or not os.path.isfile(path):
        return None
    return summarize(trace_reduce.load(path))


def of_run(run):
    """The summary of the traced run a reader was handed. The run carries no
    cell name, so the cell is the command line's `--workload`, as run.py takes
    it, and the trace lies where Context.out_path("trace") put it."""
    if not run.get("trace"):
        return None
    parser = argparse.ArgumentParser(add_help=False)
    parser.add_argument("--workload")
    cell = parser.parse_known_args()[0].workload
    return summary_at(os.path.join(OUT, f"{cell}.trace")) if cell else None


def value(run, key):
    summary = of_run(run)
    return None if summary is None else summary.get(key)


def idle_named_share(run):
    """% of the first chip's idle seconds in the window whose gap lies under a
    phase span."""
    summary = of_run(run)
    # a program that writes only its dispatches (the parent of PR 24) has no
    # step or tick to hold a phase: nothing to read, not a share of 0
    if summary is None or not summary["idle_s"] or not (summary["steps"] or summary["ticks"]):
        return None
    return 100.0 * summary["idle_phased_s"] / summary["idle_s"]


def table(summary):
    rows = [f"window {summary['window_s']:.3f} s; first chip idle "
            + ("not traced" if summary["idle_s"] is None else
               f"{summary['idle_s']:.4f} s, {summary['idle_phased_s']:.4f} s of it under a phase"),
            f"{'phase':34s} {'runs':>6s} {'ms a run':>10s} {'self ms':>10s} {'idle s under it':>16s}"]
    names = sorted(set(summary["spans"]) | set(summary["idle_by_span"]))
    for name in names:
        row = summary["spans"].get(name, {"runs": 0, "total_s": 0.0, "self_s": 0.0})
        per = lambda s: f"{1e3 * s / row['runs']:10.3f}" if row["runs"] else f"{'':>10s}"
        rows.append(f"{name:34s} {row['runs']:6d} {per(row['total_s'])} {per(row['self_s'])} "
                    f"{summary['idle_by_span'].get(name, 0.0):16.6f}")
    for key in ("steps", "step_host_ms", "step_place_ms", "ticks", "tick_host_ms"):
        if summary.get(key):
            rows.append(f"{key} {summary[key]:.3f}" if isinstance(summary[key], float)
                        else f"{key} {summary[key]}")
    return "\n".join(rows)


if __name__ == "__main__":
    found = summary_at(sys.argv[1]) if len(sys.argv) == 2 else None
    if found is None:
        print("usage: program_spans.py <trace directory | .xplane.pb | .textproto>; "
              "or the trace holds no program span", file=sys.stderr)
        sys.exit(1)
    print(table(found))
