"""Where a start goes, from the program's own compile log: what the
`setup_*`, `engine_build_s` and `executables_at_setup` readers under
layer_metrics/ read.

The program keeps one record for every executable jax traced, lowered,
compiled or loaded from the persistent cache, with the seconds of each phase
on `time.monotonic_ns` (run.py's clock), and the phases of a start that are
not jax's: `setup/import`, `serving/engine_build`
(`paddle_tpu/observability/compile_log.py`). The server and the executor run
in the benchmark's process, so the log is read in-process; this module keeps
what BEGAN before the window opened (`run["t0"]`), leaves out probes (a
second lowering for a cost analysis), counts as executables the records that
reached the backend (a jitted function traced under `jax.eval_shape` is a
trace alone: its seconds are trace seconds, it is no executable), and takes the union of every named
span inside the start over `setup_s`: coverage, as `stage_named_share` is for
the device. A jit traced inside another's trace is no executable and no
second of its own: it is the outer record's `inner_traces`.

The log hears the whole process, and in a benchmark the process is the
harness's too: its seeded weights are jitted functions (`make`, `<lambda>`,
`layer`, `top`) that compile or load like any other. The metrics are sums over
every executable, so that load + compile is `compile_s`; the account splits
them by the record's `tag`: `tagged` are the executables a layer of the
program named (the scheduler's `prefill:L2048`, the executor's
`program:<id>`), `untagged` are the caller's, here the harness's (and the
program's own eager jax.numpy calls, the arena's zeros: hundredths of a
second). What the program's start costs is the tagged part.

A program without the log (the parent of the PR that added it) gives nothing
to read, and every reader built on this returns None.

A traced run leaves the table in `benchmarks/out/<cell>.setup_phases.json`;
as a script on that file this prints what an operator reads: the seconds by
phase, and the largest executables with theirs:

    python3 benchmarks/lib/setup_phases.py benchmarks/out/small-chat-steady.setup_phases.json
"""

import argparse
import json
import os
import sys

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "out")
IMPORT, ENGINE_BUILD = "setup/import", "serving/engine_build"
PHASES = ("trace", "lower", "backend_compile", "cache_load")


def log_snapshot():
    """The program's compile log as it stands, or None where the program has
    none."""
    try:
        from paddle_tpu.observability import compile_log
    except ImportError:
        return None
    return compile_log().snapshot()


def union_s(intervals):
    """Seconds covered by [begin_ns, end_ns] intervals, overlaps once."""
    covered, reach = 0, None
    for begin, end in sorted(intervals):
        if reach is None or begin > reach:
            covered += end - begin
            reach = end
        elif end > reach:
            covered += end - reach
            reach = end
    return covered * 1e-9


def summarize(snapshot, t0, setup_s, seconds=0.0):
    """The start's account from a log's snapshot: `t0` is the window's first
    instant, `setup_s` the seconds from the process's start to it and
    `seconds` the window's length, all on time.monotonic."""
    cut = int(t0 * 1e9)
    start = cut - int(setup_s * 1e9)
    rows = [r for r in snapshot["executables"] if not r["probe"]]
    before = [r for r in rows if r["begin_ns"] < cut]
    phases = [p for p in snapshot["phases"] if p["begin_ns"] < cut]
    out = {"setup_s": setup_s,
           # an executable reached the backend (compiled or loaded); a trace alone did not (a
           # jitted function under `jax.eval_shape`: the Program's shape inference)
           "executables": sum(r["cache"] is not None for r in before),
           "traces_alone": sum(r["cache"] is None and not r["lower_s"] for r in before),
           "traces_alone_s": sum(r["trace_s"] for r in before
                                 if r["cache"] is None and not r["lower_s"]),
           "listener_calls": snapshot.get("listener_calls"),      # of the whole process so far
           "probes": sum(r["probe"] and r["begin_ns"] < cut for r in snapshot["executables"]),
           "inner_traces": sum(r["inner_traces"] for r in before),
           "inner_trace_s": sum(r["inner_trace_s"] for r in before),
           "in_window": sum(cut <= r["begin_ns"] < cut + int(seconds * 1e9) for r in rows),
           "cache": {k: sum(r["cache"] == k for r in before) for k in ("hit", "miss", "off")}}
    for phase in PHASES:
        out[phase + "_s"] = sum(r[phase + "_s"] for r in before)
    for owner, mine in (("tagged", [r for r in before if r["tag"] is not None]),
                        ("untagged", [r for r in before
                                      if r["tag"] is None and r["cache"] is not None])):
        out[owner] = dict({p + "_s": sum(r[p + "_s"] for r in mine) for p in PHASES},
                          executables=sum(r["cache"] is not None for r in mine))
    for key, name in (("import_s", IMPORT), ("engine_build_s", ENGINE_BUILD)):
        mine = [p["seconds"] for p in phases if p["phase"] == name]
        out[key] = sum(mine) if mine else None      # a program without the phase: nothing
    named = [(b, e) for r in before for _, b, e in r["spans"]]
    named += [(p["begin_ns"], p["begin_ns"] + int(p["seconds"] * 1e9)) for p in phases]
    named = [(max(b, start), min(e, cut)) for b, e in named if e > start and b < cut]
    out["named_s"] = union_s(named)
    out["named_share"] = 100.0 * out["named_s"] / setup_s if setup_s else None
    out["phases"] = [dict(p, at_s=(p["begin_ns"] - start) * 1e-9) for p in phases]
    keep = ("fun_name", "tag", "cause", "thread", "cache", "inner_traces", "inner_trace_s",
            "seconds") + tuple(p + "_s" for p in PHASES)
    out["largest"] = [dict({k: r[k] for k in keep}, at_s=(r["begin_ns"] - start) * 1e-9,
                           inner_by_name=dict(sorted(r["inner_by_name"].items(),
                                                     key=lambda kv: -kv[1][1])[:5]))
                      for r in sorted(before, key=lambda r: -r["seconds"])[:24]]
    return out


def cell_name():
    """The cell is the command line's `--workload`, as run.py takes it (the
    run carries no cell name)."""
    parser = argparse.ArgumentParser(add_help=False)
    parser.add_argument("--workload")
    return parser.parse_known_args()[0].workload


def of_run(run):
    """The account of the run a reader was handed, made once, kept on the run
    and written beside the cell's trace (run.py asks the per-layer readers
    on a traced run only)."""
    if "setup_phases" not in run:
        snapshot = log_snapshot()
        account = None
        if snapshot is not None and run.get("setup_s"):
            account = summarize(snapshot, run["t0"], run["setup_s"], run.get("seconds", 0.0))
            cell = cell_name()
            if cell and os.path.isdir(OUT):
                with open(os.path.join(OUT, f"{cell}.setup_phases.json"), "w") as f:
                    json.dump(account, f, indent=1)
        run["setup_phases"] = account
    return run["setup_phases"]


def value(run, key):
    account = of_run(run)
    return None if account is None else account.get(key)


def table(account):
    rows = [f"setup_s {account['setup_s']:.2f}; named {account['named_s']:.2f} s "
            f"({account['named_share']:.1f}%); executables {account['executables']} "
            f"(hit {account['cache']['hit']}, miss {account['cache']['miss']}, "
            f"off {account['cache']['off']}), traces alone {account['traces_alone']} "
            f"({account['traces_alone_s']:.2f} s), probes {account['probes']}, "
            f"inner traces {account['inner_traces']} ({account['inner_trace_s']:.2f} s), "
            f"begun inside the window {account['in_window']}"]
    show = lambda v: "    -" if v is None else f"{v:8.2f}"
    rows.append("import " + show(account["import_s"]) + "  engine_build "
                + show(account["engine_build_s"])
                + "".join(f"  {p} {account[p + '_s']:.2f}" for p in PHASES))
    for owner, whose in (("tagged", "the program's layers"), ("untagged", "the caller's")):
        mine = account[owner]
        rows.append(f"{owner:8s} ({whose}): executables {mine['executables']}"
                    + "".join(f"  {p} {mine[p + '_s']:.2f}" for p in PHASES))
    rows.append(f"{'phase':34s} {'at s':>8s} {'seconds':>9s}  thread")
    for p in account["phases"]:
        rows.append(f"{p['phase']:34s} {p['at_s']:8.2f} {p['seconds']:9.3f}  {p['thread']}")
    rows.append(f"{'executable (tag)':44s} {'at s':>7s} {'trace':>7s} {'lower':>7s} "
                f"{'compile':>8s} {'load':>7s} {'cache':>5s} {'inner':>6s}")
    for r in account["largest"]:
        name = r["fun_name"] + (f" ({r['tag']})" if r["tag"] else "")
        rows.append(f"{name[:44]:44s} {r['at_s']:7.1f} {r['trace_s']:7.2f} {r['lower_s']:7.2f} "
                    f"{r['backend_compile_s']:8.2f} {r['cache_load_s']:7.2f} "
                    f"{str(r['cache']):>5s} {r['inner_traces']:6d}")
        for inner, (count, seconds) in r["inner_by_name"].items():
            if seconds >= 0.05:
                rows.append(f"    traced inside: {inner} x{count}, {seconds:.2f} s")
    return "\n".join(rows)


if __name__ == "__main__":
    if len(sys.argv) != 2 or not os.path.isfile(sys.argv[1]):
        print("usage: setup_phases.py benchmarks/out/<cell>.setup_phases.json", file=sys.stderr)
        sys.exit(1)
    with open(sys.argv[1]) as f:
        print(table(json.load(f)))
