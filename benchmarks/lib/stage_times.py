"""Device self time of the first chip by the program's STAGES, from a traced
run's xplane: what a builder reads before predicting a stage's seconds, and
what the `stage_*`, `head_*`, `norm_*`, `loop_*`, `loss_*` and `optimizer_*`
readers under layer_metrics/ read.

A stage is a `jax.named_scope` of the program: `<layer>/<stage>` in the
serving programs (`attn/project`, `moe/experts`, `loop/sample`), a bare word
where a layer is one stage (`head`, `embed`, `norm`); in training, where
`pt.name_scope` stamps an op and `lower_op` traces it under
`<namescope>/<op.type>`, the same words (`attn/fc_grad`, `head/matmul`,
`loss/softmax_with_cross_entropy`, `optimizer/adam`). WHICH names are stages
is data (`STAGES`): a model that brings a new layer adds its family there and
brings no copy of this file. An operation's scope is in the `tf_op` of its
event metadata (`scope_reduce.metadata_ops` reads it); the innermost known
stage wins, as in `scope_reduce.scope_of`; a kernel that lost its scope is put
down by its name (`KERNEL_STAGES`). Self times, the window, the first chip and
the programs' names are `trace_reduce`'s. XLA fuses across scopes and a fusion
carries its root's: the table says where the time is booked, not where every
instruction came from.

As a script on a trace directory or one `.xplane.pb` it prints, per program
(`jit_chunk_impl`, `jit_prefill_impl`, `jit_admit_impl`, the training step) and
stage, seconds, share of busy time and us a run of the program; the same by
layer; and the ten largest operations under no stage:

    python3 benchmarks/lib/stage_times.py benchmarks/out/xl-docs-offline.trace
"""

import argparse
import bisect
import functools
import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import program_spans, scope_reduce
from lib import trace_reduce as tr

# every stage the program has today: `x/*` is a layer with several stages, a
# bare word a layer that is one (or, in training, a name scope whose op types
# fold into it)
STAGES = ("mla/*", "moe/*", "ffn/*", "hc/*", "attn/*", "loop/*",
          "head", "embed", "norm", "loss", "optimizer")
# Mosaic and XLA kernels that reach the trace named for themselves
KERNEL_STAGES = dict(scope_reduce.KERNEL_SCOPES, paged_attention="attn/attend",
                     _causal_rows_call="attn/attend", fused_attention="attn")
RELEASE = "executor/release"
SCOPED_AT_LEAST = 0.05


def stage_of(tf_op, stages=STAGES):
    """The innermost stage named in an operation's `tf_op`
    (`jit(chunk_impl)/while/body/closed_call/attn/project/dot_general:` ->
    `attn/project`; `jit(step)/head/matmul_grad/transpose:` -> `head`), or None."""
    parts = (tf_op or "").rstrip(":").split("/")
    families = _families(stages)
    for i in range(len(parts) - 1, -1, -1):
        if parts[i] in stages:
            return parts[i]
        if i and parts[i - 1] in families:
            return f"{parts[i - 1]}/{parts[i]}"
    return None


@functools.lru_cache(maxsize=None)
def _families(stages):
    return frozenset(s[:-2] for s in stages if s.endswith("/*"))


def kernel_stage(name):
    """The stage of a custom call by its kernel's name, or None."""
    if not tr.is_kernel(name):
        return None
    kernel = tr.family(name)
    return next((stage for prefix, stage in KERNEL_STAGES.items()
                 if kernel.startswith(prefix)), None)


def by_stage(ops, modules, lo, hi, tf_ops, stages=STAGES):
    """{"busy_s", "named_s", "scoped_s", "modules": {program: {"busy_s",
    "runs", "stages": {stage: s}, "kinds": {stage: {kind of operation: s}},
    "unnamed": {operation: s}, "shapes": {unnamed operation: what it
    returns}}}} over one chip's events (name, start, duration) cut to [lo,
    hi): self seconds by stage. `scoped_s` counts what a `tf_op` named,
    `named_s` also what only a kernel's name did; `runs` counts a program cut
    by the window's edge by the part inside; `kinds` says which fusions a
    stage's time is booked on (XLA names a fusion for what it holds:
    `divide_subtract_fusion` under `ffn/mul_grad` is Adam's update riding
    in the weight gradient's product)."""
    inside = tr.clip(ops, lo, hi)
    runs = sorted((s, s + d, tr.module_name(n)) for n, s, d in modules)
    starts = [r[0] for r in runs]
    groups, counts = {}, {}
    for event in inside:
        i = bisect.bisect_right(starts, event[1]) - 1
        module = runs[i][2] if i >= 0 and event[1] < runs[i][1] else "no_module"
        groups.setdefault(module, []).append(event)
    for start, end, module in runs:
        part = min(end, hi) - max(start, lo)
        if part > 0 and end > start:
            counts[module] = counts.get(module, 0.0) + part / (end - start)
    out = {"busy_s": tr.total(tr.union((s, s + d) for _, s, d in inside)) * 1e-9,
           "named_s": 0.0, "scoped_s": 0.0, "modules": {}}
    # a window holds an operation's name thousands of times: name it once
    named = functools.lru_cache(maxsize=None)(
        lambda name: (stage_of(tf_ops.get(name), stages), kernel_stage(name), tr.family(name)))
    for module, events in groups.items():
        entry = {"busy_s": 0.0, "runs": counts.get(module, 0.0), "stages": {}, "kinds": {},
                 "unnamed": {}, "shapes": {}}
        # an operation nests only inside one of its own program's, so the
        # self times of one program's events are trace_reduce's own
        for name, own in tr.self_times(events):
            seconds = own * 1e-9
            scoped, by_kernel, kind = named(name)
            stage = scoped or by_kernel
            entry["busy_s"] += seconds
            if stage:
                entry["stages"][stage] = entry["stages"].get(stage, 0.0) + seconds
                kinds = entry["kinds"].setdefault(stage, {})
                kinds[kind] = kinds.get(kind, 0.0) + seconds
                out["named_s"] += seconds
                out["scoped_s"] += seconds if scoped else 0.0
            else:
                key = tr.op_name(name)
                entry["unnamed"][key] = entry["unnamed"].get(key, 0.0) + seconds
                entry["shapes"][key] = name.partition(" = ")[2].split(" ", 1)[0]
        out["modules"][module] = entry
    return out


def reduce_path(path, stages=STAGES):
    """The tables of the first chip of one `.xplane.pb`, with the host's
    `executor/release` a step beside them ("release": (steps, seconds), by
    `program_spans.per_parent`); None where no device ran an operation."""
    planes = tr.load(path)
    window = tr.window_of(planes)
    devices = sorted(p for p in planes if p.startswith(tr.DEVICE_PLANE)
                     and planes[p].get(tr.OPS_LINE))
    if window is None or not devices:
        return None
    lines = planes[devices[0]]
    out = by_stage(lines[tr.OPS_LINE], lines.get(tr.MODULES_LINE, []), *window,
                   scope_reduce.metadata_ops(path), stages)
    steps = seconds = 0
    for nodes in program_spans.program_threads(planes).values():
        n, _, inside = program_spans.per_parent(nodes, *window, program_spans.STEP, (RELEASE,))
        if any(node[0] == RELEASE for node in nodes):
            steps, seconds = steps + n, seconds + inside
    out["release"] = (steps, seconds)
    return out


@functools.lru_cache(maxsize=2)
def tables_at(path):
    """`reduce_path` of a trace directory or of one `.xplane.pb`, read ONCE a
    run whatever the number of readers (an XL trace is 321 MB)."""
    if os.path.isdir(path):
        path = tr.find_xplane(path)
    if path is None or not os.path.isfile(path):
        return None
    return reduce_path(path)


def traced(run):
    """The tables of the traced run a reader was handed, or None. The run
    carries no cell name, so, as `program_spans.of_run` does, the cell is the
    command line's `--workload` and the trace lies under out/<cell>.trace."""
    if not run.get("trace"):
        return None
    parser = argparse.ArgumentParser(add_help=False)
    parser.add_argument("--workload")
    cell = parser.parse_known_args()[0].workload
    return tables_at(os.path.join(program_spans.OUT, f"{cell}.trace")) if cell else None


def of_run(run):
    """`traced(run)` where the program names its stages: a scope holds at
    least `SCOPED_AT_LEAST` of the busy time. A kernel's name alone is no
    scope, so the parent of the PR that brought the scopes reports nothing;
    nor are a few operations that came scoped out of a compile cache which
    another checkout filled (jax leaves an operation's metadata out of the
    cache's key, so an executable carries the scopes of whoever compiled it
    first: read on the chip, the parent's chat trace held 0.6% so)."""
    tables = traced(run)
    if not tables or tables["scoped_s"] < SCOPED_AT_LEAST * tables["busy_s"]:
        return None
    return tables


def stage_seconds(tables, prefixes, module=None):
    """Self seconds under the stages that are, or lie under, one of
    `prefixes` (`head` takes `head`; `loop` takes `loop/sample`), in `module`
    or in every program; None where there are none."""
    found = [s for name, entry in tables["modules"].items() if module in (None, name)
             for stage, s in entry["stages"].items()
             if any(stage == p or stage.startswith(p + "/") for p in prefixes)]
    return sum(found) if found else None


def share(run, prefixes):
    """% of the first chip's busy time under `prefixes`, or None."""
    tables = of_run(run)
    seconds = stage_seconds(tables, prefixes) if tables else None
    return None if seconds is None or not tables["busy_s"] else 100.0 * seconds / tables["busy_s"]


def named_share(run):
    """% of the first chip's busy time whose operation lies under a stage."""
    tables = of_run(run)
    return 100.0 * tables["named_s"] / tables["busy_s"] if tables and tables["busy_s"] else None


def loop_us_per_step(run):
    """Device us under `loop/*` inside the fused decode program per step it holds."""
    tables = of_run(run)
    chunk = tables and tables["modules"].get("jit_chunk_impl")
    seconds = stage_seconds(tables, ("loop",), "jit_chunk_impl") if chunk else None
    if not seconds or not chunk["runs"]:
        return None
    return 1e6 * seconds / (chunk["runs"] * run["decode_chunk"])


def release_ms(run):
    """Host ms of `executor/release` a step, over the steps whole inside the window."""
    steps, seconds = (traced(run) or {}).get("release", (0, 0.0))
    return 1e3 * seconds / steps if steps else None


def clip_text_proto(path, lo, hi, keep_host=(tr.WINDOW_SPAN,)):
    """`trace_reduce.clip_text_proto` of the `.xplane.pb` at `path` with each
    device operation's `tf_op` kept as a stat of its event metadata, so that
    `ProfileData.text_proto_to_serialized_xspace` gives back a file this
    module reads whole: how the recorded traces under tests/data/ were cut
    from chip runs. Two operations whose short names (`%fusion.7 = fusion()`)
    would collide are told apart by one more number."""
    planes, tf_ops = tr.load(path), scope_reduce.metadata_ops(path)
    short, taken = {}, {}
    for plane, lines in planes.items():
        if not plane.startswith(tr.DEVICE_PLANE):
            continue
        for line, events in lines.items():
            for i, (name, start, dur) in enumerate(events):
                if lo <= start < hi and name not in short:
                    base = tr._short(name)
                    taken[base] = taken.get(base, 0) + 1
                    short[name] = base if taken[base] == 1 else base.replace(
                        " = ", f".{taken[base]} = ", 1)
                events[i] = (short.get(name, name), start, dur)
    stats = {new: tf_ops[old] for old, new in short.items() if old in tf_ops}
    out = []
    for row in tr.clip_text_proto(planes, lo, hi, keep_host).splitlines():
        name = row.split(' name: "', 1)[-1].rsplit('" } }', 1)[0]
        if row.startswith(" event_metadata") and name in stats:
            quoted = stats[name].replace("\\", "\\\\").replace('"', '\\"')
            row = row[:-len(" } }")] + f' stats {{ metadata_id: 1 str_value: "{quoted}" }} }} }}'
        elif row.startswith("planes {") and tr.DEVICE_PLANE in row:
            row += '\n stat_metadata { key: 1 value { id: 1 name: "tf_op" } }'
        out.append(row)
    return "\n".join(out) + "\n"


def table(tables, top=10):
    busy = tables["busy_s"]
    rows = [f"first chip busy {busy:.4f} s; under a stage {tables['named_s']:.4f} s "
            f"({100 * tables['named_s'] / busy:.1f}%), {tables['scoped_s']:.4f} s of it by scope"]
    line = lambda name, s, runs, kinds=(): (
        f"  {name:34s} {s:9.4f} {100 * s / busy:7.2f}% "
        + (f"{1e6 * s / runs:12.1f}" if runs else f"{'':>12s}") + "  "
        + ", ".join(f"{k} {v:.4f}" for k, v in sorted(kinds, key=lambda kv: -kv[1])[:3]))
    for module, entry in sorted(tables["modules"].items(), key=lambda kv: -kv[1]["busy_s"]):
        rows.append(f"{module}: {entry['busy_s']:.4f} s in {entry['runs']:.1f} runs"
                    f"{'':6s}{'seconds':>9s} {'of busy':>8s} {'us a run':>12s}  booked on")
        layers = {}
        for stage, s in entry["stages"].items():
            layers[stage.split("/")[0]] = layers.get(stage.split("/")[0], 0.0) + s
        for group in (entry["stages"], {k + " (layer)": v for k, v in layers.items()
                                        if k not in entry["stages"]}):
            rows.extend(line(k, v, entry["runs"], entry["kinds"].get(k, {}).items())
                        for k, v in sorted(group.items(), key=lambda kv: -kv[1]))
        rows.append(line("(no stage)", sum(entry["unnamed"].values()), entry["runs"]))
    loose = sorted(((s, f"{m}: {k} {e['shapes'][k]}") for m, e in tables["modules"].items()
                    for k, s in e["unnamed"].items()), reverse=True)[:top]
    rows.append(f"the {top} largest operations under no stage:")
    rows.extend(f"  {name[:90]:90s} {s:9.4f} {100 * s / busy:7.2f}%" for s, name in loose)
    kinds = {}
    for entry in tables["modules"].values():
        for name, s in entry["unnamed"].items():
            kinds[tr.family(name)] = kinds.get(tr.family(name), 0.0) + s
    rows.append("under no stage by kind: " + ", ".join(
        f"{k} {v:.4f}" for k, v in sorted(kinds.items(), key=lambda kv: -kv[1])[:top]))
    steps, seconds = tables.get("release", (0, 0.0))
    if steps:
        rows.append(f"{RELEASE}: {1e3 * seconds / steps:.3f} ms a step over {steps} steps")
    return "\n".join(rows)


if __name__ == "__main__":
    found = tables_at(sys.argv[1]) if len(sys.argv) == 2 else None
    if found is None:
        print("usage: stage_times.py <trace directory | .xplane.pb>; or the trace holds "
              "no device operation", file=sys.stderr)
        sys.exit(1)
    print(table(found))
