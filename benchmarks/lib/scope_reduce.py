"""Device self time by the program's named scopes and by Mosaic kernel, from a
trace directory: what the per-layer readers of a model with `jax.named_scope`s
(`mla/project`, `mla/absorb`, `mla/attend`, `moe/router`, `moe/dispatch`,
`moe/experts`, `moe/shared`, `moe/combine`, `head`) read. Built from
`trace_reduce`'s helpers; it reads the same window, the same `XLA Ops` line and
the same self times.

Where an operation's scope is: not in its event (the trace names an event by
the instruction's text without its metadata, and its own stats are its times),
but in the stat `tf_op` of the event's METADATA in the xplane file
(`jit(chunk_impl)/while/body/closed_call/moe/router/reduce_sum:`, read on the
chip in PR 27), which jax's ProfileData does not hand out. `metadata_ops`
therefore reads the file's protobuf wire format itself: five message types,
the field numbers of tsl/profiler/protobuf/xplane.proto. A trace of a program
without such scopes gives empty tables, and the readers then report nothing."""

import bisect
import re

from . import trace_reduce as tr

# XLA's own grouped-product kernels (what `jax.lax.ragged_dot` compiles to on a
# TPU) come out named for themselves, their scope lost: they are the experts'
KERNEL_SCOPES = {"ragged-dot-none": "moe/experts", "ragged-dot-metadata": "moe/experts"}
SCOPE = re.compile(r"(?:^|[/\"(])((?:mla|moe|ffn)/[a-z_]+|head)(?=[/\")]|$)")


def scope_of(tf_op):
    """The LAST scope named in an operation's `tf_op` (the innermost)."""
    found = SCOPE.findall(tf_op or "")
    return found[-1] if found else None


def _varint(buf, at):
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return value, at


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited field; fixed-width fields are skipped."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
            yield number, value
        elif wire == 2:
            size, at = _varint(buf, at)
            yield number, buf[at:at + size]
            at += size
        elif wire in (1, 5):
            at += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def _first(buf, number):
    return next((v for n, v in _fields(buf) if n == number), None)


def metadata_ops(path):
    """{event name: its `tf_op`} over the device planes of an `.xplane.pb`.
    XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 and .stat_metadata
    = 5 (map entries: key 1, value 2); XEventMetadata.name = 2, .stats = 5;
    XStatMetadata.id = 1, .name = 2; XStat.metadata_id = 1, .str_value = 5."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    ops = {}
    for number, plane in _fields(space):
        if number != 1 or not bytes(_first(plane, 2) or b"").decode().startswith(tr.DEVICE_PLANE):
            continue
        stat_ids = set()
        for n, entry in _fields(plane):
            if n == 5:
                meta = _first(entry, 2)
                if meta is not None and bytes(_first(meta, 2) or b"") == b"tf_op":
                    stat_ids.add(_first(meta, 1))
        for n, entry in _fields(plane):
            if n != 4:
                continue
            meta = _first(entry, 2)
            if meta is None:
                continue
            for m, stat in _fields(meta):
                if m == 5 and _first(stat, 1) in stat_ids:
                    text = _first(stat, 5)
                    if text is not None:
                        ops[bytes(_first(meta, 2)).decode()] = bytes(text).decode()
    return ops


def by_scope(ops, modules, lo, hi, tf_ops):
    """{module: {"scopes": {scope: s}, "kernels": {name: s}, "attend_s": s}}
    over the events (name, start, duration) of one chip that start in [lo,
    hi): self seconds by scope (`tf_ops`: event name -> its `tf_op`), by
    Mosaic kernel (a custom call's instruction name less its number), and in
    `mla/attend` or the latent kernel counted once."""
    runs = sorted((s, s + d, tr.module_name(n)) for n, s, d in modules)
    starts = [r[0] for r in runs]
    inside = [e for e in ops if lo <= e[1] < hi]
    # self time needs the nesting: trace_reduce.self_times drops the start,
    # so walk the same stack here and keep it
    out, stack = [], []
    for name, start, dur in sorted(inside, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            done = stack.pop()
            out.append((done[0], done[3], done[2]))
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur, start])
    out.extend((name, start, own) for name, _, own, start in stack)
    table = {}
    for name, start, own in out:
        i = bisect.bisect_right(starts, start) - 1
        module = runs[i][2] if i >= 0 and start < runs[i][1] else "no_module"
        entry = table.setdefault(module, {"scopes": {}, "kernels": {}, "attend_s": 0.0})
        kernel = tr.family(name) if tr.is_kernel(name) else None
        scope = KERNEL_SCOPES.get(kernel) or scope_of(tf_ops.get(name))
        seconds = own * 1e-9
        if scope:
            entry["scopes"][scope] = entry["scopes"].get(scope, 0.0) + seconds
        if kernel:
            entry["kernels"][kernel] = entry["kernels"].get(kernel, 0.0) + seconds
        if scope == "mla/attend" or kernel == "latent_paged_attention":
            entry["attend_s"] += seconds
    return table


def reduce_dir(trace_dir):
    """The by-scope tables of the first chip of the newest trace under
    `trace_dir`, or None where there is no trace or no device operation."""
    path = tr.find_xplane(trace_dir)
    if path is None:
        return None
    planes = tr.load(path)
    window = tr.window_of(planes)
    devices = sorted(p for p in planes if p.startswith(tr.DEVICE_PLANE)
                     and planes[p].get(tr.OPS_LINE))
    if window is None or not devices:
        return None
    lines = planes[devices[0]]
    return by_scope(lines[tr.OPS_LINE], lines.get(tr.MODULES_LINE, []), *window,
                    metadata_ops(path))


def scope_seconds(run, module, prefix):
    """Self seconds of `module`'s operations under scopes that start with
    `prefix` (every module when `module` is None); None without tables."""
    tables = run.get("scopes")
    if not tables:
        return None
    picked = [t for m, t in tables.items() if module is None or m == module]
    found = [s for t in picked for name, s in t["scopes"].items() if name.startswith(prefix)]
    return sum(found) if found else None


def runs_in_window(run, module):
    """How many runs of `module` the traced window holds, a run cut by its
    edge counted by the part inside: clipped seconds over seconds a whole run."""
    trace = run.get("trace")
    if not trace:
        return None
    whole, count = trace["module_whole_s"].get(module), trace["module_runs"].get(module)
    if not whole or not count:
        return None
    return trace["module_s"][module] / (whole / count)
