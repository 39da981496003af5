"""The benchmark's one command:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It finds everything by name: the cell in BENCHMARK.json, its configuration
under benchmarks/configs/, its traffic under benchmarks/traffic/, the mode's
driver under benchmarks/modes/ and one reader per metric under
benchmarks/end_to_end/ and benchmarks/layer_metrics/. The last line of its
standard output is the result; the line before it holds facts about the run.
It runs on a TPU named in lib/peaks.py and nowhere else.
"""

import time

PROCESS_START = time.monotonic()

import argparse
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for path in (ROOT, HERE):
    if path not in sys.path:
        sys.path.insert(0, path)


def load_module(folder, name):
    path = os.path.join(HERE, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{folder}_{name}".replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metrics_of(bench, cell_name, group):
    """The metrics of `group` that this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


class Context:
    def __init__(self, args, bench, cell, watch):
        from lib import model

        self.seed, self.seconds, self.trace = args.seed, float(args.seconds), bool(args.trace)
        self.cell = cell
        self.config = model.load_json("configs", cell["config"] + ".json")
        self.traffic = model.load_json("traffic", cell["traffic"] + ".json")
        self.watch = watch
        self.setup_s = None
        self.ramp_s = None
        self.compile_at_setup = None
        self.compile_at_window_end = None
        self.memory_stats = None
        self.stages = {}

    def out_path(self, name):
        return os.path.join(HERE, "out", f"{self.cell['name']}.{name}")

    def mark(self, name):
        """Where set-up goes: seconds since the process started, by stage."""
        self.stages[name] = time.monotonic() - PROCESS_START

    def mark_setup_done(self, ramp_s):
        """Called by the mode at the first measured request or step."""
        self.setup_s = time.monotonic() - PROCESS_START
        self.ramp_s = ramp_s
        self.compile_at_setup = self.watch.snapshot()

    def memory_peak(self):
        """Called by the mode when the window has closed: the peak on the
        fullest chip, and the compile events so far (none may fall between
        set-up and here)."""
        import jax

        self.compile_at_window_end = self.watch.snapshot()
        # The TPU's allocator counts live arrays (`peak_bytes_in_use`) apart
        # from the space compiled programs reserve for their temporaries
        # (`peak_bytes_reserved`); what the chip held at its fullest is both.
        # (GPT-2-small's train step: 2.20 + 6.43 GB here, against 1.49 GB of
        # arguments and 6.65 GB of temporaries by memory_analysis(); PR 23.)
        held = lambda s: s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
        self.memory_stats = max((d.memory_stats() or {} for d in jax.local_devices()), key=held)
        return int(held(self.memory_stats))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == args.workload), None)
    if cell is None:
        print(f"no cell {args.workload!r} in BENCHMARK.json", file=sys.stderr)
        return 2

    import paddle_tpu  # noqa: F401  (a checkout without the program fails here)
    from lib import peaks, tracing

    try:
        device, peak_rates = peaks.require_chips(cell["chips"])
    except peaks.NoChip as e:
        print(f"benchmarks/run.py: {e}; this command runs on the chip only",
              file=sys.stderr)
        return 1
    import jax

    # every program goes to the persistent cache, the quick ones too, so that
    # a second run compiles nothing (the program picks the directory:
    # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    watch = tracing.CompileWatch()
    ctx = Context(args, bench, cell, watch)
    os.makedirs(os.path.join(HERE, "out"), exist_ok=True)
    mode = load_module("modes", ctx.traffic["mode"])
    ctx.mark("imports_and_device")
    run = mode.run(ctx)
    run.update(setup_s=ctx.setup_s, config=ctx.config, peaks=peak_rates,
               compile_at_setup=ctx.compile_at_setup, compile_at_end=watch.snapshot())

    group = "per_layer" if args.trace else "end_to_end"
    folder = "layer_metrics" if args.trace else "end_to_end"
    metrics = {}
    for entry in metrics_of(bench, cell["name"], group):
        value = load_module(folder, entry["name"]).read(run)
        if value is not None:
            metrics[entry["name"]] = {"value": float(value), "unit": entry["unit"]}

    device["memory_peak_bytes"] = run["memory_peak_bytes"]
    result = {"correct": bool(run["correct"]), "attempted": int(run["attempted"]),
              "failed": int(run["failed"]), "metrics": metrics, "device": device}
    why = list(run.get("why_incorrect", []))
    if ctx.compile_at_window_end != ctx.compile_at_setup:
        why.append(f"compiled between set-up and the window's end: {ctx.compile_at_setup} "
                   f"then {ctx.compile_at_window_end}")
        result["correct"] = False
    if not result["correct"]:
        print("benchmarks/run.py: NOT CORRECT: " + ("; ".join(why) or "see facts="),
              file=sys.stderr, flush=True)
    trace = run.get("trace")
    if args.trace:
        if trace is None:
            print("benchmarks/run.py: the trace holds no device operation", file=sys.stderr)
            return 1
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    write_records(ctx, run, args)
    programs = lambda snap: snap["cache_hits"] + snap["cache_misses"]
    facts = dict(run["facts"], ramp_s=ctx.ramp_s, setup_s=ctx.setup_s,
                 compiled_in_window=(programs(ctx.compile_at_window_end)
                                     - programs(ctx.compile_at_setup)),
                 memory_stats=ctx.memory_stats, setup_stages=ctx.stages,
                 **ctx.compile_at_setup)
    if trace:
        facts["programs_traced"] = {k: [trace["module_runs"][k], trace["module_whole_s"][k]]
                                    for k in trace["module_runs"]}
    print("facts=" + json.dumps(facts), flush=True)
    print(json.dumps(result), flush=True)
    return 0


def write_records(ctx, run, args):
    """One line per request or step, for lib/spread.py: what the end-to-end
    metrics were computed from, so that another window length can be tried on
    the same run without the chip."""
    path = ctx.out_path(f"seed{args.seed}.trace{args.trace}.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"header": True, "cell": ctx.cell["name"], "mode": run["mode"],
                            "t0": run["t0"], "seconds": run["seconds"], "seed": args.seed,
                            "setup_s": run["setup_s"]}) + "\n")
        for r in run.get("records", []):
            f.write(json.dumps({k: v for k, v in r.items() if k != "output"}) + "\n")
        for s in run.get("steps", []):
            f.write(json.dumps(s) + "\n")


if __name__ == "__main__":
    sys.exit(main())
