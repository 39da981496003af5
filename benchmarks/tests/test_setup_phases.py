"""lib/setup_phases.py on a canned compile log: the cut at t0, probes and
inner traces left out, the union behind `setup_named_share` across two
threads, a hit booked as load and a miss as compile; and the new readers'
LAYER, UNIT, MOVES against their BENCHMARK.json entries."""

import importlib.util
import json
import os

import pytest

from lib import setup_phases

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
S = 10 ** 9
NEW = ("setup_import_s", "engine_build_s", "setup_trace_s", "setup_lower_s",
       "setup_backend_compile_s", "setup_cache_load_s", "executables_at_setup",
       "setup_inner_traces", "setup_named_share")


def record(name, begin_s, trace=0.0, lower=0.0, compile_=0.0, load=0.0, cache="off",
           thread="MainThread", probe=False, tag=None, inner=0, inner_s=0.0):
    """One executable as the program's log keeps it, its phases back to back
    from `begin_s`."""
    spans, at = [], begin_s
    for span, seconds in (("compile/trace", trace), ("compile/lower", lower),
                          ("compile/backend", compile_), ("compile/cache_load", load)):
        if seconds:
            spans.append([span, int(at * S), int((at + seconds) * S)])
            at += seconds
    return {"fun_name": name, "tag": tag, "cause": None, "probe": probe, "nested": False,
            "thread": thread, "tid": 1, "begin_ns": int(begin_s * S), "trace_s": trace,
            "lower_s": lower, "backend_compile_s": compile_, "cache_load_s": load,
            "cache": cache, "inner_traces": inner, "inner_trace_s": inner_s,
            "inner_by_name": {"kernel_body": [inner, inner_s]} if inner else {},
            "spans": spans, "seconds": trace + lower + compile_ + load}


def phase(name, begin_s, seconds, thread="MainThread"):
    return {"phase": name, "thread": thread, "tid": 1, "begin_ns": int(begin_s * S),
            "seconds": seconds}


# the process starts at 100 s of the clock and the window opens at 140 s
T0, SETUP_S = 140.0, 40.0
CANNED = {
    "phases": [phase("setup/import", 101.0, 4.0),
               phase("serving/engine_build", 110.0, 3.0),
               phase("serving/engine_build/jits", 111.0, 1.0, thread="engine"),
               phase("serving/engine_build", 150.0, 2.0)],          # after t0: a second engine
    "executables": [
        # a miss: 1 + 2 + 5 s from 113
        record("prefill_impl", 113.0, 1.0, 2.0, 5.0, cache="miss", tag="prefill:L128",
               inner=7, inner_s=0.5),
        # a hit, on ANOTHER thread, overlapping the miss's compile by 2 s: 116 .. 121
        record("chunk_impl", 116.0, 0.5, 0.5, load=4.0, cache="hit", thread="engine",
               tag="decode_chunk", inner=3, inner_s=0.1),
        # a probe inside the start: in no sum and not in the union
        record("chunk_impl", 125.0, 0.25, 0.75, probe=True, tag="decode_chunk"),
        # inside the window: a recompile, none of the start's
        record("late", 141.0, 0.5, 0.5, 1.0, cache="miss"),
        # shape inference: a trace alone, its seconds count, it is no executable
        record("f", 108.0, 0.25, cache=None),
        # the caller's own (the harness's seeded weights): no layer named it
        record("make", 105.5, 0.125, 0.25, load=0.5, cache="hit"),
        # behind the window (the reference's): neither
        record("reference", 200.0, 1.0, 1.0, 1.0, cache="miss"),
    ],
}


def test_the_cut_at_t0_probes_and_inner_traces():
    a = setup_phases.summarize(CANNED, T0, SETUP_S, seconds=51.0)
    assert a["executables"] == 3 and a["probes"] == 1 and a["in_window"] == 1
    assert a["trace_s"] == 1.875 and a["lower_s"] == 2.75    # top level only, probe out
    assert a["traces_alone"] == 1 and a["traces_alone_s"] == 0.25
    assert a["inner_traces"] == 10 and a["inner_trace_s"] == pytest.approx(0.6)
    assert a["import_s"] == 4.0 and a["engine_build_s"] == 3.0   # not the jits, not the late one
    assert [r["fun_name"] for r in a["largest"]] == ["prefill_impl", "chunk_impl", "make", "f"]
    assert a["largest"][0]["at_s"] == pytest.approx(13.0)
    assert a["largest"][0]["inner_by_name"] == {"kernel_body": [7, 0.5]}


def test_a_hit_is_load_and_a_miss_is_compile():
    a = setup_phases.summarize(CANNED, T0, SETUP_S)
    assert a["cache"] == {"hit": 2, "miss": 1, "off": 0}
    assert a["backend_compile_s"] == 5.0 and a["cache_load_s"] == 4.5
    # with the harness's CompileWatch: the same events
    assert a["backend_compile_s"] + a["cache_load_s"] == 9.5


def test_the_programs_executables_are_told_from_the_callers_by_the_tag():
    a = setup_phases.summarize(CANNED, T0, SETUP_S)
    assert a["tagged"] == {"executables": 2, "trace_s": 1.5, "lower_s": 2.5,
                           "backend_compile_s": 5.0, "cache_load_s": 4.0}
    # the harness's seeded weights; a trace alone (shape inference) is neither's executable
    assert a["untagged"] == {"executables": 1, "trace_s": 0.125, "lower_s": 0.25,
                             "backend_compile_s": 0.0, "cache_load_s": 0.5}
    for phase_ in setup_phases.PHASES:
        rest = a["traces_alone_s"] if phase_ == "trace" else 0.0
        assert a["tagged"][phase_ + "_s"] + a["untagged"][phase_ + "_s"] + rest == \
            a[phase_ + "_s"]


def test_named_share_is_a_union_across_threads():
    a = setup_phases.summarize(CANNED, T0, SETUP_S)
    # import 101..105, the caller's 105.5..106.375, a trace alone 108..108.25, build 110..113
    # (the jits, on the other thread, inside it), the miss 113..121 and the hit 116..121 on
    # the other thread inside it: 4 + 0.875 + 0.25 + 3 + 8
    assert a["named_s"] == pytest.approx(16.125)
    assert a["named_share"] == pytest.approx(100.0 * 16.125 / 40.0)
    assert setup_phases.union_s([(0, 10), (5, 20), (30, 40), (32, 35)]) == pytest.approx(30e-9)


def test_a_span_over_an_edge_is_cut_there():
    log = {"phases": [phase("setup/import", 98.0, 4.0)],            # began before the start
           "executables": [record("f", 139.0, 0.5, 0.5, 3.0, cache="miss")]}     # ends at 143
    a = setup_phases.summarize(log, T0, SETUP_S)
    assert a["named_s"] == pytest.approx(2.0 + 1.0)
    assert a["executables"] == 1 and a["backend_compile_s"] == 3.0   # booked where it began


def test_a_program_without_the_log_gives_nothing(monkeypatch):
    monkeypatch.setattr(setup_phases, "log_snapshot", lambda: None)
    run = {"t0": T0, "setup_s": SETUP_S, "seconds": 51.0}
    assert setup_phases.value(run, "trace_s") is None
    assert run["setup_phases"] is None


def test_a_run_is_read_once_and_written_beside_its_trace(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(setup_phases, "log_snapshot", lambda: calls.append(1) or CANNED)
    monkeypatch.setattr(setup_phases, "OUT", str(tmp_path))
    monkeypatch.setattr(setup_phases, "cell_name", lambda: "some-cell")
    run = {"t0": T0, "setup_s": SETUP_S, "seconds": 51.0}
    assert setup_phases.value(run, "executables") == 3
    assert setup_phases.value(run, "named_share") == pytest.approx(40.3125)
    assert len(calls) == 1
    with open(tmp_path / "some-cell.setup_phases.json") as f:
        written = json.load(f)
    assert written["cache_load_s"] == 4.5
    text = setup_phases.table(written)
    assert "prefill_impl (prefill:L128)" in text and "traced inside: kernel_body x7" in text
    assert "serving/engine_build/jits" in text
    assert "untagged (the caller's): executables 1" in text


@pytest.mark.parametrize("name", NEW)
def test_reader_matches_its_entry_and_reads_the_account(name, monkeypatch):
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    spec = importlib.util.spec_from_file_location("m", os.path.join(BENCH, "layer_metrics",
                                                                    name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert (mod.LAYER, mod.UNIT, mod.MOVES) == (entry["layer"], entry["unit"], entry["moves"])
    assert entry["layer"] == "compile cache" and entry["moves"] == "setup_s"
    serving = [w["name"] for w in bench["workloads"] if not w["name"].startswith("small-train")]
    assert entry.get("workloads") == (serving if name == "engine_build_s" else None)
    monkeypatch.setattr(setup_phases, "log_snapshot", lambda: CANNED)
    monkeypatch.setattr(setup_phases, "cell_name", lambda: None)
    value = mod.read({"t0": T0, "setup_s": SETUP_S, "seconds": 51.0})
    assert value == pytest.approx({"setup_import_s": 4.0, "engine_build_s": 3.0,
                     "setup_trace_s": 1.875, "setup_lower_s": 2.75, "setup_backend_compile_s": 5.0,
                     "setup_cache_load_s": 4.5, "executables_at_setup": 3,
                     "setup_inner_traces": 10, "setup_named_share": 40.3125}[name])
    monkeypatch.setattr(setup_phases, "log_snapshot", lambda: None)
    assert mod.read({"t0": T0, "setup_s": SETUP_S}) is None


def test_the_train_mode_on_the_cpu_is_read_through_the_programs_log(tmp_path, monkeypatch):
    """End to end at a tiny size (counts and control flow only): the executor's
    programs are in the start's account under their tags, nothing begins inside
    the window, and the nine readers agree with the account."""
    import time

    from test_rehearsal import Ctx, mode, reader

    monkeypatch.setattr(setup_phases, "cell_name", lambda: None)
    began = time.monotonic()
    traffic = {"mode": "train", "seq_len": 32, "per_chip_batch": 2, "data_parallel": False,
               "learning_rate": 1e-3, "distinct_batches": 2, "warm_up_steps": 2, "trace_s": 1.0}
    run = mode("train").run(Ctx(tmp_path, traffic, seconds=1.0))
    run["setup_s"] = run["t0"] - began          # run.py's: since the process started
    account = setup_phases.of_run(run)
    assert run["correct"] and account["in_window"] == 0
    tagged = [r for r in account["largest"] if (r["tag"] or "").startswith("program:")]
    assert len(tagged) >= 2                     # the startup program and the step
    assert all(r["cause"] == "first_compile" for r in tagged)
    assert reader("layer_metrics", "executables_at_setup")(run) == account["executables"] >= 2
    assert reader("layer_metrics", "setup_trace_s")(run) == account["trace_s"] > 0
    assert reader("layer_metrics", "setup_backend_compile_s")(run) > 0
    assert reader("layer_metrics", "setup_cache_load_s")(run) == 0      # the tests keep no cache
    assert reader("layer_metrics", "engine_build_s")(run) is None       # no engine in training
    assert 0 < reader("layer_metrics", "setup_named_share")(run) <= 100
