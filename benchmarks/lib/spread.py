"""How far do a cell's runs repeat, and at which window length?

    python3 benchmarks/lib/spread.py benchmarks/out/<cell>.seed*.trace0.jsonl

Reads the per-request and per-step records that every run leaves under
benchmarks/out/ and prints, for each end-to-end metric the records can give,
the median over the runs and the spread (interquartile distance over the
median, as the driver takes it) at several window lengths: sub-windows of the
same records, each starting at the run's own t0. `run_seconds` is the shortest
length at which five times the widest spread still makes a bound worth having.
A sub-window of an open-loop run does not hold the same totals on every seed
(only the full window does), so its spread is an upper estimate."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import metrics  # noqa: E402

LENGTHS = (20.0, 30.0, 40.0, 51.0)


def read_run(path):
    header, rows = None, []
    with open(path) as f:
        for line in f:
            row = json.loads(line)
            if row.get("header"):
                header = row
            else:
                rows.append(row)
    return header, rows


def window_metrics(header, rows, seconds):
    t0 = header["t0"]
    out = {}
    if header["mode"] == "train":
        out["train_tok_s"] = metrics.train_tok_s(rows, t0, seconds)
    elif header["mode"] == "serve-closed":
        out["serve_tok_s"] = metrics.serve_tok_s(rows, t0, seconds)
    else:
        measured = [r for r in metrics.due_in_window(rows, t0, seconds) if r["measured"]]
        out["ttft_p95_ms"] = metrics.ttft_p95_ms(measured)
        out["tpot_mean_ms"] = metrics.tpot_mean_ms(measured)
    return {k: v for k, v in out.items() if v is not None}


def table(paths, lengths=LENGTHS):
    """{metric: {seconds: (median, spread, runs)}}"""
    import statistics

    runs = [read_run(p) for p in paths]
    out = {}
    for seconds in lengths:
        values = {}
        for header, rows in runs:
            if header["seconds"] + 1e-9 < seconds:
                continue
            for name, value in window_metrics(header, rows, seconds).items():
                values.setdefault(name, []).append(value)
        for name, vals in values.items():
            if len(vals) >= 3:
                out.setdefault(name, {})[seconds] = (
                    statistics.median(vals), metrics.spread(vals), len(vals))
    setups = [h["setup_s"] for h, _ in runs if h.get("setup_s")]
    if len(setups) >= 3:
        out["setup_s"] = {0.0: (statistics.median(setups), metrics.spread(setups), len(setups))}
    return out


def main(argv):
    if not argv:
        print(__doc__, file=sys.stderr)
        return 2
    for name, by_length in table(argv).items():
        for seconds, (median, spr, n) in sorted(by_length.items()):
            print(f"{name:14s} window {seconds:5.1f} s  runs {n}  median {median:12.4f}"
                  f"  spread {100 * spr:6.3f}%  bound at 5x {100 * 5 * spr:6.2f}%")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
