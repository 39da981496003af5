"""From a profiler trace (`.xplane.pb`) to numbers: device busy time, time by
operation and by program, collective time not hidden by compute, and the idle
gaps named by what the host was doing. Checked against the recorded trace in
benchmarks/tests/data/ (`clip_text_proto` made it from a chip run).

Layout of a TPU trace as jax 0.9 writes it: one plane per chip named
`/device:TPU:<n>` with a line `XLA Ops` (one event per executed HLO
operation, named by the instruction's whole text, `%fusion.7 = f32[8,128]{1,0}
fusion(...), kind=kLoop, ...`; a `while` holds the operations of its body), a
line `Async XLA Ops` (copies and collectives in flight beside the compute: not
busy time) and a line `XLA Modules` (one event per executed program, named
`jit_<fn>(<id>)`); a plane `/host:CPU` with one line per thread, where
jax.profiler.TraceAnnotation spans appear by name on the line `python3` or the
thread's own. All lines share one clock (read on the chip in PR 23).
"""

import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench_window"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
CONTAINERS = ("while", "conditional", "call")
# Mosaic (Pallas) kernels reach XLA as custom calls.
KERNEL_OPCODE = "custom-call"
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load(path):
    """{plane name: {line name: [(event name, start_ns, duration_ns)]}}; a
    `.textproto` file is read as the text form of the same message."""
    from jax.profiler import ProfileData

    if path.endswith(".textproto"):
        with open(path) as f:
            data = ProfileData.from_text_proto(f.read())
    else:
        data = ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, float(ev.start_ns), float(ev.duration_ns)) for ev in line.events)
    return planes


def union(intervals):
    """Sorted, merged (start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def total(intervals):
    return sum(end - start for start, end in intervals)


def subtract(intervals, cover):
    """The parts of merged `intervals` that merged `cover` does not touch."""
    out = []
    for start, end in intervals:
        at = start
        for c0, c1 in cover:
            if c1 <= at or c0 >= end:
                continue
            if c0 > at:
                out.append([at, c0])
            at = max(at, c1)
        if at < end:
            out.append([at, end])
    return out


def clip(events, lo, hi):
    return [(name, max(start, lo), min(start + dur, hi) - max(start, lo))
            for name, start, dur in events if start < hi and start + dur > lo]


def self_times(events):
    """(name, self_ns): an operation's time less the time of the operations
    nested in it, so a `while` does not count its body twice."""
    out = []
    stack = []                       # [name, end, self]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    out.extend((name, own) for name, _, own in stack)
    return out


def op_name(text):
    """`%copy_bitcast_fusion.28 = bf16[...] fusion(...)` -> `copy_bitcast_fusion.28`;
    a plain name is returned as it is."""
    if text.startswith("%"):
        return text[1:].split(" ", 1)[0]
    return text


def opcode(text):
    """The HLO opcode of an instruction's text: the word before the first
    parenthesis that follows white space (shapes write `T(8,128)` and `S(1)`
    with none before them). A plain name gives itself less its number."""
    if text.startswith("%") and " = " in text:
        found = _OPCODE.search(text, text.index(" = "))
        if found:
            return found.group(1)
    return family(text)


def family(text):
    """`copy_bitcast_fusion.28` -> `copy_bitcast_fusion`: the operations of one
    kind, whatever number the compiler gave them."""
    return re.sub(r"[.\d]+$", "", op_name(text)) or op_name(text)


def module_name(name):
    """`jit_chunk_impl(123456)` -> `jit_chunk_impl`."""
    return name.split("(", 1)[0]


def is_collective(text):
    return opcode(text).startswith(COLLECTIVES)


def is_kernel(text):
    return opcode(text) == KERNEL_OPCODE


def is_container(text):
    return opcode(text) in CONTAINERS


def window_of(planes):
    """(lo, hi) in ns: the WINDOW_SPAN annotation where the host recorded one,
    else the extent of the device's own events."""
    for name, lines in planes.items():
        if name.startswith("/host:"):
            for events in lines.values():
                for ev, start, dur in events:
                    if ev == WINDOW_SPAN:
                        return start, start + dur
    spans = [(s, s + d) for name, lines in planes.items() if name.startswith(DEVICE_PLANE)
             for _, s, d in lines.get(OPS_LINE, [])]
    if not spans:
        return None
    return min(s for s, _ in spans), max(e for _, e in spans)


def host_spans(planes, names):
    return sorted((start, start + dur, ev) for plane, lines in planes.items()
                  if plane.startswith("/host:") for events in lines.values()
                  for ev, start, dur in events if ev in names)


def reduce(planes, span_names=()):
    """The summary every device_trace metric reads, or None when the trace
    holds no operation on a device."""
    window = window_of(planes)
    devices = sorted(p for p in planes if p.startswith(DEVICE_PLANE) and planes[p].get(OPS_LINE))
    if window is None or not devices:
        return None
    lo, hi = window
    busy_ns, exposed_ns, by_op, by_module, module_runs, whole_ns = [], [], {}, {}, {}, {}
    kernel_ns = 0.0
    first = None
    for plane in devices:
        ops = clip(planes[plane][OPS_LINE], lo, hi)
        if not ops:
            continue
        busy = union((s, s + d) for _, s, d in ops)
        busy_ns.append(total(busy))
        owned = self_times(ops)
        for name, own in owned:
            # one entry for each kind and one for each single operation, as
            # the ledger's earlier breakdowns have them
            for key in ("all_" + family(name), op_name(name)):
                by_op[key] = by_op.get(key, 0.0) + own
            if is_kernel(name):
                kernel_ns += own
        leaves = [(n, s, d) for n, s, d in ops if not is_container(n)]
        compute = union((s, s + d) for n, s, d in leaves if not is_collective(n))
        collective = union((s, s + d) for n, s, d in leaves if is_collective(n))
        exposed_ns.append(total(subtract(collective, compute)))
        modules = clip(planes[plane].get(MODULES_LINE, []), lo, hi)
        for name, _, dur in modules:
            by_module[module_name(name)] = by_module.get(module_name(name), 0.0) + dur
        # a program cut by the window's edge counts towards a share, not
        # towards a time per run
        for name, start, dur in planes[plane].get(MODULES_LINE, []):
            if lo <= start and start + dur <= hi:
                key = module_name(name)
                whole_ns[key] = whole_ns.get(key, 0.0) + dur
                module_runs[key] = module_runs.get(key, 0) + 1
        if first is None:
            first = (busy, modules)
    if not busy_ns:
        return None
    chips = len(busy_ns)
    gaps = _name_gaps(first[0], first[1], lo, hi, host_spans(planes, set(span_names)))
    top = lambda d: [[k, v * 1e-9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "chips": chips,
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy_ns) / chips * 1e-9,
        "kernel_s": kernel_ns / chips * 1e-9,
        "collective_exposed_s": sum(exposed_ns) / chips * 1e-9,
        "module_s": {k: v / chips * 1e-9 for k, v in by_module.items()},
        "module_whole_s": {k: v / chips * 1e-9 for k, v in whole_ns.items()},
        "module_runs": {k: v / chips for k, v in module_runs.items()},
        "device_ops": top({k: v / chips for k, v in by_op.items()}),
        "idle_gaps": top(gaps),
    }


def _name_gaps(busy, modules, lo, hi, spans):
    """Idle time on the first chip by name: the host span that covers the
    gap's middle where the benchmark recorded one, else the programs on either
    side of it."""
    edges = [lo] + [t for pair in busy for t in pair] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    runs = sorted((s, s + d, module_name(n)) for n, s, d in modules)
    named = {}
    for start, end in gaps:
        mid = 0.5 * (start + end)
        label = next((name for s, e, name in spans if s <= mid < e), None)
        if label is None:
            inside = next((name for s, e, name in runs if s <= mid < e), None)
            if inside is not None:
                label = f"between_operations_of_{inside}"
            else:
                before = [name for s, e, name in runs if e <= mid]
                after = [name for s, e, name in runs if s >= mid]
                label = (f"after_{before[-1] if before else 'window_start'}"
                         f"_before_{after[0] if after else 'window_end'}")
        named[label] = named.get(label, 0.0) + (end - start)
    return named


def _short(text):
    """An instruction's text without its shapes and operands, which are most
    of a trace's bytes: `%fusion.7 = fusion()`."""
    if text.startswith("%") and " = " in text:
        return f"%{op_name(text)} = {opcode(text)}()"
    return text


def clip_text_proto(planes, lo, hi, keep_host=(WINDOW_SPAN,)):
    """The text form of a small trace: the device events that start in
    [lo, hi) ns and the named host spans cut to it, on the same clock. It is how the
    recorded trace under benchmarks/tests/data/ was cut from a chip run."""
    out = []
    for pid, (plane, lines) in enumerate(sorted(planes.items()), 1):
        device = plane.startswith(DEVICE_PLANE)
        if not device and not plane.startswith("/host:"):
            continue
        ids, body = {}, []
        for lid, (line, events) in enumerate(sorted(lines.items()), 1):
            if device:
                kept = [(_short(n), s, d) for n, s, d in events if lo <= s < hi]
            else:
                kept = clip([e for e in events if e[0] in keep_host], lo, hi)
            if not kept:
                continue
            body.append(f' lines {{ id: {lid} name: "{line}" timestamp_ns: 0')
            for name, start, dur in kept:
                mid = ids.setdefault(name, len(ids) + 1)
                body.append(f"  events {{ metadata_id: {mid} offset_ps: {int(start * 1000)}"
                            f" duration_ps: {int(dur * 1000)} }}")
            body.append(" }")
        if not body:
            continue
        out.append(f'planes {{ id: {pid} name: "{plane}"')
        out.extend(body)
        for name, mid in ids.items():
            out.append(f' event_metadata {{ key: {mid} value {{ id: {mid} name: "{name}" }} }}')
        out.append("}")
    return "\n".join(out) + "\n"
