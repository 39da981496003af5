"""Host clock between step completions in the window, median."""
import statistics

LAYER, UNIT, MOVES = "executor", "ms", "train_tok_s"


def read(run):
    done = [s["done"] for s in run.get("steps") or []
            if run["t0"] <= s["done"] < run["t0"] + run["seconds"]]
    gaps = [b - a for a, b in zip(done, done[1:])]
    return 1e3 * statistics.median(gaps) if gaps else None
