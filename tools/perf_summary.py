"""Performance-attribution table: compile journal + tick phases.

The report half of the performance-attribution plane
(ServingConfig(tick_profile=True)): where executable time and engine
host time actually went. Input is the /compilez JSON payload (or one
engine's bare CompileJournal snapshot); per engine it renders

* one row per executable family — prefill:L<bucket>, decode_chunk,
  admit_sample, swap_out/in, release_slot — with call count, compile
  count, compile wall seconds and share, and jax cost_analysis()'s
  per-dispatch GFLOPs / MBytes where known;
* the derived gauges: mfu_proxy (FLOPs issued per second over the
  journal's lifetime against the device's published peak or
  PT_SERVING_PEAK_FLOPS; "-" where neither exists) and HBM bytes per
  fused decode dispatch;
* with ``--ticks`` (the /tickz payload), a per-phase host-overhead
  table over the tick flight ring: count, total/mean milliseconds,
  and each phase's share of summed tick wall time.

Usage:
  python tools/perf_summary.py COMPILEZ.json [--ticks TICKZ.json]
      [--json]
"""

import argparse
import json
import os
import sys

_TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_TOOLS, ".."))
sys.path.insert(0, _TOOLS)

from summary_io import (SummaryInputError, read_input,  # noqa: E402
                        report_error)

EMPTY_HINT = ("no compile journal was written there. Run the engine "
              "with ServingConfig(tick_profile=True) and save "
              "/compilez (or engine.compile_journal.snapshot()) as "
              "JSON, then re-run.")

TICKS_EMPTY_HINT = ("no tick records were written there. Save /tickz "
                    "from a tick_profile=True engine, then re-run.")


def _load_json(path: str, hint: str):
    raw = read_input(path, empty_hint=hint)
    try:
        return json.loads(raw)
    except json.JSONDecodeError as e:
        raise SummaryInputError(f"{path!r} is not JSON ({e.msg})")


def load_journals(path: str):
    """{engine label: journal snapshot} from a /compilez payload or a
    bare snapshot (keyed "journal" then)."""
    payload = _load_json(path, EMPTY_HINT)
    if isinstance(payload, dict) and "engines" in payload \
            and isinstance(payload["engines"], dict):
        journals = payload["engines"]
    elif isinstance(payload, dict) and "families" in payload:
        journals = {"journal": payload}
    else:
        raise SummaryInputError(
            f"{path!r} is not a /compilez payload or CompileJournal "
            "snapshot (no 'engines' or 'families' key)")
    journals = {label: snap for label, snap in journals.items()
                if isinstance(snap, dict)
                and isinstance(snap.get("families"), dict)}
    if not journals:
        raise SummaryInputError(
            f"{path!r} holds no journal snapshots — " + EMPTY_HINT)
    return journals


def load_ticks(path: str):
    """Flat tick-record list from a /tickz payload or bare list."""
    payload = _load_json(path, TICKS_EMPTY_HINT)
    if isinstance(payload, dict):
        recs = [rec for records in (payload.get("engines") or {}).values()
                for rec in records]
    elif isinstance(payload, list):
        recs = payload
    else:
        raise SummaryInputError(
            f"{path!r} holds a {type(payload).__name__}; expected a "
            "/tickz payload or a list of tick records")
    recs = [rec for rec in recs if isinstance(rec, dict)
            and isinstance(rec.get("phases"), dict)]
    if not recs:
        raise SummaryInputError(
            f"{path!r} holds no tick records — " + TICKS_EMPTY_HINT)
    return recs


def phase_table(ticks):
    """Per-phase host-overhead rows over tick records: count of ticks
    where the phase spent time, total seconds, share of summed tick
    wall time, mean microseconds per tick."""
    totals: dict = {}
    n = len(ticks)
    for rec in ticks:
        for phase, seconds in rec["phases"].items():
            totals[phase] = totals.get(phase, 0.0) + float(seconds)
    wall = sum(totals.values())
    rows = []
    for phase in sorted(totals, key=lambda p: -totals[p]):
        s = totals[phase]
        rows.append({"phase": phase, "seconds": s,
                     "share": s / wall if wall > 0 else 0.0,
                     "mean_us": s / n * 1e6 if n else 0.0})
    return {"ticks": n, "wall_seconds": wall, "phases": rows}


def _fmt_cost(v, scale, width):
    return f"{'-':>{width}}" if v is None else f"{v / scale:>{width}.3f}"


def _print_journal(label, snap):
    mfu = snap.get("mfu_proxy")
    hbm = snap.get("dispatch_hbm_bytes")
    peak = snap.get("peak_flops")
    print(f"engine {label}: {snap.get('compiles_total', 0)} compiles, "
          f"{snap.get('compile_seconds_total', 0.0):.3f}s compiling, "
          f"peak {'-' if peak is None else format(peak, '.3g')} FLOP/s")
    print(f"  mfu_proxy={'-' if mfu is None else format(mfu, '.3g')}  "
          f"hbm_bytes/dispatch="
          f"{'-' if hbm is None else format(int(hbm), 'd')}")
    fams = snap["families"]
    if not fams:
        print("  (no dispatches journaled)")
        return
    w = max(6, max(len(name) for name in fams))
    print(f"  {'family':<{w}}  {'calls':>6}  {'comp':>4}  "
          f"{'compile_s':>9}  {'share':>6}  {'GFLOP/call':>10}  "
          f"{'MB/call':>8}")
    for name in sorted(fams, key=lambda n: -fams[n]["compile_s"]):
        fam = fams[name]
        print(f"  {name:<{w}}  {fam['calls']:>6}  "
              f"{fam['compiles']:>4}  {fam['compile_s']:>9.3f}  "
              f"{fam['compile_share']:>6.1%}  "
              f"{_fmt_cost(fam['flops'], 1e9, 10)}  "
              f"{_fmt_cost(fam['bytes_accessed'], 1e6, 8)}")


def _print_phases(table):
    print(f"tick phases ({table['ticks']} ticks, "
          f"{table['wall_seconds'] * 1e3:.3f} ms summed wall):")
    print(f"  {'phase':<14}  {'total_ms':>9}  {'share':>6}  "
          f"{'mean_us':>9}")
    for row in table["phases"]:
        print(f"  {row['phase']:<14}  {row['seconds'] * 1e3:>9.3f}  "
              f"{row['share']:>6.1%}  {row['mean_us']:>9.1f}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("compilez", help="/compilez payload (or a bare "
                                     "CompileJournal snapshot) JSON "
                                     "path")
    ap.add_argument("--ticks", default=None, metavar="TICKZ",
                    help="/tickz payload: add the per-phase host-"
                         "overhead table")
    ap.add_argument("--json", action="store_true",
                    help="print the attribution as one JSON object")
    args = ap.parse_args(argv)
    try:
        journals = load_journals(args.compilez)
        ticks = load_ticks(args.ticks) if args.ticks is not None \
            else None
    except SummaryInputError as e:
        return report_error("perf_summary", e)
    phases = phase_table(ticks) if ticks is not None else None
    if args.json:
        out = {"engines": journals}
        if phases is not None:
            out["tick_phases"] = phases
        print(json.dumps(out, indent=2, default=str))
        return 0
    for i, (label, snap) in enumerate(sorted(journals.items())):
        if i:
            print()
        _print_journal(label, snap)
    if phases is not None:
        print()
        _print_phases(phases)
    return 0


if __name__ == "__main__":
    sys.exit(main())
