"""paddle_tpu.observability — tracing, metrics registry, chrome export.

Pins the subsystem's contracts: (1) running a program through
Executor.run with tracing enabled produces a chrome-trace JSON with at
least one complete ("ph": "X") event per executed op, loadable in
catapult format; (2) serving-engine metrics are visible in a registry
snapshot after a 10-request continuous-batching run and the Prometheus
text export parses; (3) with no profiler session and the ring off a
span records nothing anywhere — the span count stays zero across full
executor runs and engine ticks and the registry's family set does not
move; (4) the legacy profiler.RecordEvent API is an alias of trace_span
and is thread-safe under concurrent recording; (5) trace_span is the
one source of host spans: under a jax.profiler session the executor's
and the engine's phases are events on a /host:CPU line of the xplane,
children inside their parent and not overlapping, and the sinks of
dispatch_timing / tick_profile read those spans' own durations."""

import contextlib
import glob
import json
import os
import re
import tempfile
import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import observability as obs


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Every test starts and ends with a disabled, empty tracer."""
    obs.disable_tracing()
    obs.get_tracer().clear()
    yield
    obs.disable_tracing()
    obs.get_tracer().clear()


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------

def test_span_is_its_own_stopwatch():
    """The object trace_span yields carries its body's duration, ring
    on or off; with the ring on, the recorded span IS that reading (one
    clock pair, not two)."""
    with obs.trace_span("quiet") as sp:
        time.sleep(0.002)
    assert sp.seconds >= 0.002
    assert obs.get_tracer().span_count == 0      # ring off: not recorded
    obs.enable_tracing()
    with obs.trace_span("heard", "cat", {"k": 1}) as sp:
        time.sleep(0.001)
    (span,) = obs.get_tracer().snapshot()
    assert span.name == "heard" and span.args == {"k": 1}
    assert span.dur_us == pytest.approx(sp.seconds * 1e6, rel=1e-9)
    # the ring-only entry point stays a shared no-op while the ring is off
    obs.disable_tracing()
    assert obs.get_tracer().span("a") is obs.get_tracer().span("b")


def test_nested_spans_depths_and_order():
    obs.enable_tracing()
    with obs.trace_span("outer", "t"):
        with obs.trace_span("inner", "t", {"k": "v"}):
            pass
    spans = obs.get_tracer().snapshot()
    # spans complete inner-first
    assert [s.name for s in spans] == ["inner", "outer"]
    inner, outer = spans
    assert inner.depth == 1 and outer.depth == 0
    assert inner.args == {"k": "v"}
    assert inner.ts_us >= outer.ts_us
    assert inner.dur_us <= outer.dur_us
    assert outer.dur_us >= 0


def test_ring_buffer_caps_memory_and_counts_drops():
    t = obs.Tracer(capacity=4)
    t.enable()
    for i in range(10):
        with t.span(f"s{i}"):
            pass
    assert t.span_count == 4
    assert t.dropped == 6
    assert [s.name for s in t.snapshot()] == ["s6", "s7", "s8", "s9"]


def test_ring_buffer_wraparound_multiple_times():
    """Satellite pin: fill the ring far past capacity — the drop count
    tracks every evicted span exactly, the snapshot is always the newest
    `capacity` spans in completion order, and clear() resets both."""
    cap = 8
    t = obs.Tracer(capacity=cap)
    t.enable()
    for i in range(3 * cap + 5):                 # wraps 3+ times
        with t.span(f"w{i}"):
            pass
        assert t.span_count == min(i + 1, cap)
        assert t.dropped == max(0, i + 1 - cap)
    total = 3 * cap + 5
    names = [s.name for s in t.snapshot()]
    assert names == [f"w{i}" for i in range(total - cap, total)]
    assert t.dropped == total - cap
    # instants ride the same ring
    t.instant("marker")
    assert [s.name for s in t.snapshot()][-1] == "marker"
    assert t.dropped == total - cap + 1
    t.clear()
    assert t.span_count == 0 and t.dropped == 0
    with t.span("fresh"):
        pass
    assert [s.name for s in t.snapshot()] == ["fresh"]
    assert t.dropped == 0


def test_per_thread_tracks():
    obs.enable_tracing()
    def work():
        with obs.trace_span("worker_span"):
            pass
    th = threading.Thread(target=work, name="obs-worker")
    with obs.trace_span("main_span"):
        th.start()
        th.join()
    spans = obs.get_tracer().snapshot()
    by_name = {s.name: s for s in spans}
    assert by_name["worker_span"].tid != by_name["main_span"].tid
    assert by_name["worker_span"].thread == "obs-worker"


def test_concurrent_spans_thread_safe():
    """Hammer the tracer from many threads: every span lands, none torn
    (the old profiler kept an unlocked module-global list; the satellite
    asks for this exact pin)."""
    n_threads, per_thread = 8, 200
    obs.enable_tracing(capacity=n_threads * per_thread + 100)
    def work(idx):
        for i in range(per_thread):
            with obs.trace_span(f"t{idx}", "stress", {"i": i}):
                pass
    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    tracer = obs.get_tracer()
    assert tracer.span_count == n_threads * per_thread
    assert tracer.dropped == 0
    spans = tracer.snapshot()
    per = {f"t{i}": 0 for i in range(n_threads)}
    for s in spans:
        per[s.name] += 1
        assert s.dur_us >= 0
    assert all(v == per_thread for v in per.values())


def test_record_event_delegates_to_tracer():
    obs.enable_tracing()
    with pt.profiler.RecordEvent("legacy/evt", bytes=128):
        pass
    spans = obs.get_tracer().snapshot()
    assert [s.name for s in spans] == ["legacy/evt"]
    assert spans[0].cat == "record_event"
    assert spans[0].args == {"bytes": 128}
    # disabled -> no recording, still usable
    obs.disable_tracing()
    with pt.profiler.RecordEvent("legacy/evt2"):
        pass
    assert obs.get_tracer().span_count == 1


# ---------------------------------------------------------------------------
# executor integration: chrome trace with >= 1 "X" event per executed op
# ---------------------------------------------------------------------------

def _small_program():
    main, startup = pt.Program(), pt.Program()
    with pt.unique_name_guard(), pt.program_guard(main, startup):
        x = pt.layers.data("x", [16])
        y = pt.layers.fc(x, 16, act="relu")
        loss = pt.layers.reduce_mean(y)
    return main, startup, loss


def test_executor_run_emits_chrome_trace_per_op():
    main, startup, loss = _small_program()
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        obs.enable_tracing()
        obs.get_tracer().clear()
        exe.run(main, feed={"x": np.random.rand(4, 16).astype("f")},
                fetch_list=[loss])
    obs.disable_tracing()
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        path = f.name
    obs.export_chrome_trace(path)
    with open(path) as f:
        doc = json.load(f)
    # catapult object form
    assert isinstance(doc, dict) and "traceEvents" in doc
    events = doc["traceEvents"]
    xs = [e for e in events if e.get("ph") == "X"]
    for e in xs:  # complete events carry the catapult-required keys
        assert {"name", "ts", "dur", "pid", "tid"} <= set(e)
    # >= 1 complete event per executed op, named by op type, carrying
    # the op's var names in args
    ops = [op for op in main.global_block.ops
           if op.type not in ("feed", "fetch")]
    assert ops
    for op in ops:
        matching = [e for e in xs if e["name"] == op.type]
        assert matching, f"no span for executed op {op.type!r}"
        assert any("outputs" in e.get("args", {}) for e in matching)
    # run-level span present too, and thread metadata names the track
    assert any(e["name"] == "executor/run" for e in xs)
    assert any(e.get("ph") == "M" and e["name"] == "thread_name"
               for e in events)


def test_disabled_tracer_records_nothing_during_runs():
    """The production path: tracer off, full executor runs, zero spans
    recorded (the disabled trace_span is a no-op, not a buffer)."""
    main, startup, loss = _small_program()
    exe = pt.Executor()
    tracer = obs.get_tracer()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        assert tracer.span_count == 0
        for _ in range(3):
            exe.run(main, feed={"x": np.random.rand(4, 16).astype("f")},
                    fetch_list=[loss])
        assert tracer.span_count == 0
        assert tracer.dropped == 0


def test_trace_ops_flag_suppresses_per_op_spans(monkeypatch):
    monkeypatch.setenv("FLAGS_trace_ops", "0")
    main, startup, loss = _small_program()
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        obs.enable_tracing()
        obs.get_tracer().clear()
        exe.run(main, feed={"x": np.random.rand(4, 16).astype("f")},
                fetch_list=[loss])
    names = {s.name for s in obs.get_tracer().snapshot()}
    assert "executor/run" in names          # run/compile spans stay
    assert "mul" not in names and "relu" not in names


def test_self_time_rollup_subtracts_children():
    obs.enable_tracing()
    import time
    with obs.trace_span("parent"):
        time.sleep(0.002)
        with obs.trace_span("child"):
            # long against what a loaded host oversleeps the parent's 2 ms by
            time.sleep(0.05)
    st = obs.self_times(obs.get_tracer().snapshot())
    assert st["parent"]["total_us"] > st["parent"]["self_us"]
    assert st["child"]["self_us"] == pytest.approx(
        st["child"]["total_us"])
    # child consumed most of parent's wall time
    assert st["parent"]["self_us"] < st["child"]["self_us"] * 2
    rows = obs.summarize(top=1)
    assert rows[0]["name"] == "child"


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_counter_gauge_histogram_snapshot():
    reg = obs.MetricsRegistry()
    reg.counter("steps_total", "steps").inc()
    reg.counter("steps_total").inc(2)
    reg.gauge("depth").set(7)
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 2.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["steps_total"]["type"] == "counter"
    assert snap["steps_total"]["series"][0]["value"] == 3
    assert snap["depth"]["series"][0]["value"] == 7
    hrow = snap["lat_seconds"]["series"][0]
    assert hrow["count"] == 3 and hrow["sum"] == pytest.approx(2.55)
    assert hrow["min"] == 0.05 and hrow["max"] == 2.0
    assert hrow["p50"] == 0.5
    assert hrow["buckets"] == {"0.1": 1, "1.0": 2, "+Inf": 3}
    json.dumps(snap)                     # JSON-able end to end
    # labeled series are distinct
    fam = reg.counter("reqs_total")
    fam.labels(model="a").inc()
    fam.labels(model="b").inc(5)
    vals = {s["labels"]["model"]: s["value"]
            for s in reg.snapshot()["reqs_total"]["series"]}
    assert vals == {"a": 1, "b": 5}


def test_registry_kind_mismatch_and_counter_monotonic():
    reg = obs.MetricsRegistry()
    reg.counter("x_total")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")
    with pytest.raises(ValueError, match="increase"):
        reg.counter("y_total").inc(-1)


def test_registry_histogram_bucket_conflict_raises():
    reg = obs.MetricsRegistry()
    reg.histogram("h_seconds", buckets=(0.1, 1.0))
    reg.histogram("h_seconds", buckets=[0.1, 1.0])   # same layout: fine
    reg.histogram("h_seconds")                       # unspecified: fine
    with pytest.raises(ValueError, match="already registered with"):
        reg.histogram("h_seconds", buckets=(0.5,))   # silent misfile, no


def test_family_remove_retires_labeled_series():
    reg = obs.MetricsRegistry()
    fam = reg.gauge("slots")
    fam.labels(engine="0").set(4)
    fam.labels(engine="1").set(2)
    assert fam.remove(engine="0") is True
    assert fam.remove(engine="0") is False           # already gone
    labels = [s["labels"] for s in reg.snapshot()["slots"]["series"]]
    assert labels == [{"engine": "1"}]


def test_engine_metrics_unregister_drops_registry_series():
    """A retired/replaced engine must not leave ghost series in scrapes
    (tools/bench_serving.py recreates engines per concurrency level)."""
    from paddle_tpu.serving.metrics import EngineMetrics
    reg = obs.MetricsRegistry()
    m = EngineMetrics(registry=reg)
    m.submitted += 1
    m.queue_depth = 3
    label = m.engine_label
    snap = reg.snapshot()
    assert any(s["labels"].get("engine") == label
               for s in snap["serving_submitted_total"]["series"])
    m.unregister()
    for fam in reg.snapshot().values():
        assert not any(s["labels"].get("engine") == label
                       for s in fam["series"]), fam
    # the detached instance still answers locally
    assert m.submitted == 1 and m.snapshot()["queue_depth"] == 3


def test_histogram_quantiles_nearest_rank():
    h = obs.Histogram(buckets=(1.0,))
    assert h.quantile(0.5) is None       # empty -> None, not a crash
    for v in range(1, 101):
        h.observe(float(v))
    assert h.quantile(0.0) == 1.0
    assert h.quantile(0.5) == 50.0
    assert h.quantile(0.99) == 99.0
    assert h.quantile(1.0) == 100.0


_PROM_LINE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})? [0-9eE.+-]+|\+Inf|-Inf$')


def test_prometheus_text_export_parses():
    reg = obs.MetricsRegistry()
    reg.counter("a_total", "with a\nnewline in help").labels(m="x").inc()
    reg.gauge("b").set(1.5)
    reg.histogram("c_seconds", buckets=(0.5,)).observe(0.1)
    text = reg.to_prometheus()
    assert text.endswith("\n")
    for line in text.strip().split("\n"):
        if line.startswith("#"):
            assert re.match(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* ",
                            line), line
            assert "\n" not in line
        else:
            assert _PROM_LINE.match(line), line
    # histogram exposition: cumulative buckets + sum + count
    assert 'c_seconds_bucket{le="0.5"} 1' in text
    assert 'c_seconds_bucket{le="+Inf"} 1' in text
    assert "c_seconds_count 1" in text


def test_prometheus_label_value_escaping():
    """Satellite pin: backslash, double-quote, and newline in label
    values must be escaped per the exposition format 0.0.4 — raw
    interpolation lets a quote terminate the value early and a newline
    split the sample into two bogus lines."""
    reg = obs.MetricsRegistry()
    reg.counter("esc_total").labels(
        path='C:\\tmp\\"quoted"\nnext').inc(2)
    text = reg.to_prometheus()
    line = next(l for l in text.split("\n") if l.startswith("esc_total{"))
    # exactly the escaped form: \\ for backslash, \" for quote, \n for LF
    assert line == ('esc_total{path="C:\\\\tmp\\\\\\"quoted\\"\\nnext"} 2')
    # one sample per series: the newline did NOT split the line
    assert sum(1 for l in text.split("\n")
               if l.startswith("esc_total")
               and not l.startswith("#")) == 1
    # HELP text escapes backslash + newline too
    reg2 = obs.MetricsRegistry()
    reg2.gauge("g", help="multi\nline \\ help").set(1)
    help_line = next(l for l in reg2.to_prometheus().split("\n")
                     if l.startswith("# HELP"))
    assert help_line == "# HELP g multi\\nline \\\\ help"


def test_prometheus_label_names_sanitized():
    """Label names allow [a-zA-Z0-9_] only — colons are reserved for
    metric names (recording rules), and arbitrary chars must not leak
    into the exposition."""
    reg = obs.MetricsRegistry()
    reg.counter("n_total").labels(**{"a:b": "x", "0bad-key": "y"}).inc()
    text = reg.to_prometheus()
    line = next(l for l in text.split("\n") if l.startswith("n_total{"))
    assert line == 'n_total{_0bad_key="y",a_b="x"} 1'


# ---------------------------------------------------------------------------
# serving integration: 10-request run lands in the global registry
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_engine_params():
    from paddle_tpu.models.gpt import GPTConfig, gpt_lm_program
    from paddle_tpu.models import gpt_decode as gd
    cfg = GPTConfig(vocab_size=97, hidden=32, layers=2, heads=4,
                    max_pos=64, dropout=0.0, attn_impl="xla")
    main, startup, _ = gpt_lm_program(cfg, 8, is_test=True)
    exe = pt.Executor()
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup)
        params = gd.collect_gpt_params(scope, cfg)
    return cfg, params


def test_serving_metrics_in_registry_snapshot(tiny_engine_params):
    cfg, params = tiny_engine_params
    eng = pt.serving.ServingEngine(
        params, cfg, pt.serving.ServingConfig(
            num_slots=2, max_queue=16, prefill_buckets=(4, 8), max_len=32))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (3 + i % 5,)).astype(np.int32)
               for i in range(10)]
    outs = eng.generate(prompts, max_new_tokens=4)
    assert len(outs) == 10
    label = eng.stats()["engine_label"]

    snap = obs.get_registry().snapshot()

    def series(name):
        rows = [r for r in snap[name]["series"]
                if r["labels"].get("engine") == label]
        assert len(rows) == 1, (name, rows)
        return rows[0]

    assert series("serving_submitted_total")["value"] == 10
    assert series("serving_completed_total")["value"] == 10
    assert series("serving_tokens_out_total")["value"] == 40
    assert series("serving_active_slots")["value"] == 0   # drained
    ttft = series("serving_ttft_seconds")
    assert ttft["count"] == 10 and ttft["p50"] is not None
    tpot = series("serving_tpot_seconds")
    assert tpot["count"] == 10 and tpot["p99"] is not None
    assert tpot["max"] != float("inf")
    # the same numbers flow out the Prometheus pipe
    text = obs.get_registry().to_prometheus()
    assert f'serving_submitted_total{{engine="{label}"}} 10' in text
    assert "serving_ttft_seconds_bucket" in text
    # and the engine's own snapshot agrees with the registry
    s = eng.stats()
    assert s["p50_ttft"] == ttft["p50"]
    assert s["mean_tpot"] == pytest.approx(tpot["sum"] / tpot["count"])


# ---------------------------------------------------------------------------
# degenerate request metrics (satellite): None, never inf / raise
# ---------------------------------------------------------------------------

def test_engine_close_retires_registry_series(tiny_engine_params):
    cfg, params = tiny_engine_params
    eng = pt.serving.ServingEngine(
        params, cfg, pt.serving.ServingConfig(
            num_slots=1, prefill_buckets=(4,), max_len=16))
    eng.generate([np.asarray([1, 2], np.int32)], max_new_tokens=2)
    label = eng.stats()["engine_label"]
    eng.close()
    for fam in obs.get_registry().snapshot().values():
        assert not any(s["labels"].get("engine") == label
                       for s in fam["series"]), fam
    assert eng.stats()["completed"] == 1     # local stats still answer


def test_start_profiler_double_start_absorbed(tmp_path):
    """A second start while profiling must neither repoint the active dir
    nor leave the tracer stuck enabled after stop."""
    d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")
    pt.profiler.start_profiler(log_dir=d1)
    pt.profiler.start_profiler(log_dir=d2)     # absorbed
    assert pt.profiler.stop_profiler() == d1   # first dir wins
    assert not obs.tracing_enabled()           # restored, not stuck on
    assert pt.profiler.stop_profiler() is None


def test_request_metrics_single_token_generation():
    from paddle_tpu.serving.metrics import RequestMetrics
    t = [0.0]
    rm = RequestMetrics(clock=lambda: t[0])
    rm.mark_submitted()
    t[0] = 1.0
    rm.mark_token()
    rm.mark_finished()
    d = rm.to_dict()
    assert d["ttft"] == 1.0
    assert d["tpot"] is None            # undefined, not ZeroDivisionError
    assert d["output_tps"] is None
    json.dumps(d)                        # no inf/nan leaks into export


def test_request_metrics_zero_duration_window():
    from paddle_tpu.serving.metrics import RequestMetrics
    rm = RequestMetrics(clock=lambda: 5.0)   # frozen clock: 0-width window
    rm.mark_submitted()
    rm.mark_admitted()
    rm.mark_token()
    rm.mark_token()
    rm.mark_token()
    rm.mark_finished()
    assert rm.tpot == 0.0                # well-defined: zero elapsed
    assert rm.output_tps is None         # a rate over 0 s is NOT inf
    assert rm.total == 0.0


def test_request_metrics_backwards_clock_rejected():
    from paddle_tpu.serving.metrics import RequestMetrics
    t = [10.0]
    rm = RequestMetrics(clock=lambda: t[0])
    rm.mark_submitted()
    rm.mark_token()
    t[0] = 3.0                           # clock stepped backwards
    rm.mark_token()
    rm.mark_finished()
    assert rm.tpot is None               # nonsense sample suppressed
    assert rm.output_tps is None


def test_request_metrics_unstamped_everything_none():
    from paddle_tpu.serving.metrics import RequestMetrics
    rm = RequestMetrics()
    d = rm.to_dict()
    assert d == {"queue_wait": None, "ttft": None, "tpot": None,
                 "output_tps": None, "total": None, "tokens_out": 0}


# ---------------------------------------------------------------------------
# registry rollup helper (/varz blocks deduped — observability PR satellite)
# ---------------------------------------------------------------------------

def test_registry_rollup_counters_and_ratio():
    """registry_rollup joins labeled counter families into per-label
    rows and ratio() derives safe divisions (None on an empty
    denominator, never a ZeroDivisionError)."""
    from paddle_tpu.observability.debug_server import (ratio,
                                                       registry_rollup)
    snap = {
        "hits_total": {"series": [
            {"labels": {"engine": "a"}, "value": 3},
            {"labels": {"engine": "b"}, "value": 0}]},
        "misses_total": {"series": [
            {"labels": {"engine": "a"}, "value": 1}]},
    }
    out = registry_rollup(snap, {"hits": "hits_total",
                                 "misses": "misses_total"},
                          derived=[("hit_ratio",
                                    ratio("hits", ("hits", "misses")))])
    assert out == {
        "a": {"hits": 3, "misses": 1, "hit_ratio": 0.75},
        "b": {"hits": 0, "misses": 0, "hit_ratio": None},
    }
    # absent families roll up to an empty dict, not a KeyError
    assert registry_rollup({}, {"x": "nope_total"}) == {}


def test_registry_rollup_histogram_fields_and_label_sums():
    """Histogram columns join on sum/count with a float cast, and a
    family whose series split the join label further (tenant AND
    objective) SUMS into the per-label row instead of overwriting."""
    from paddle_tpu.observability.debug_server import (ratio,
                                                       registry_rollup)
    snap = {
        "lat_seconds": {"series": [
            {"labels": {"engine": "a"}, "count": 4, "sum": 0.02}]},
        "slo_met_total": {"series": [
            {"labels": {"tenant": "t", "objective": "ttft"}, "value": 2},
            {"labels": {"tenant": "t", "objective": "e2e"}, "value": 3}]},
    }
    out = registry_rollup(
        snap, {"n": ("lat_seconds", "count", int),
               "total_s": ("lat_seconds", "sum", float)},
        derived=[("mean_ms", ratio("total_s", "n", digits=3,
                                   scale=1e3))])
    assert out == {"a": {"n": 4, "total_s": 0.02, "mean_ms": 5.0}}
    out = registry_rollup(snap, {"met": "slo_met_total"},
                          label_key="tenant")
    assert out == {"t": {"met": 5}}            # objectives aggregated


def test_serving_varz_uses_rollup_for_every_block(tiny_engine_params):
    """The deduped _serving_varz keeps the exact pre-refactor shape for
    the PR 6/9/10 blocks (other tests pin the values) and grows the
    host-overhead, SLO, and migration blocks — empty dicts while those
    planes are dormant, never missing keys."""
    from paddle_tpu.observability.debug_server import _serving_varz
    varz = _serving_varz(obs.get_registry().snapshot())
    assert set(varz) == {"prefix_hit_ratio", "spec_accept_ratio",
                         "prefill", "preemption", "mesh",
                         "host_overhead_per_dispatch",
                         "slo", "migration"}
    # the migration plane is dormant here: the rollup key exists but
    # carries no rows (its registry families are created lazily on the
    # first migration — the disabled-noop discipline)
    assert varz["migration"] == {}


# ---------------------------------------------------------------------------
# histogram meta-test (observability PR satellite): every registered
# histogram family has sane buckets and loses no observation
# ---------------------------------------------------------------------------

def test_every_registered_histogram_has_monotone_buckets():
    """Guard on the per-series `_buckets=` override machinery: drive
    engines with DIFFERENT count-scaled layouts through one registry,
    then assert for every histogram series in the process registry —
    strictly monotone bucket bounds, non-decreasing cumulative counts,
    and a +Inf bucket equal to the observation count (every observed
    sample landed in a bucket; silent misfiling would break one of
    these)."""
    import math
    from paddle_tpu.serving.metrics import EngineMetrics

    # two engines with different per-series layouts + the split hists
    m1 = EngineMetrics(max_tokens_per_dispatch=24, speculate_k=2,
                       dispatch_timing=True)
    m2 = EngineMetrics(max_tokens_per_dispatch=640, speculate_k=6)
    for m, runs in ((m1, (0, 1, 2)), (m2, (0, 3, 6))):
        for i, n in enumerate(runs):
            m.observe_dispatch_tokens(1 + 7 * i)
            m.observe_spec_run(n)
            m.observe_swap("swap_out", 0.001 * (i + 1))
            m.observe_swap("swap_in", 0.002)
    m1.observe_dispatch_split(0.0005, 0.004)
    m1.observe_dispatch_split(0.0008, 0.0)     # boundary-ish values
    checked = 0
    for fam in obs.get_registry().families():
        if fam.kind != "histogram":
            continue
        for labels, series in fam.series_items():
            bounds = series._bounds
            assert all(a < b for a, b in zip(bounds, bounds[1:])), \
                (fam.name, labels, bounds)
            cum = series.cumulative_buckets()
            counts = [c for _, c in cum]
            assert counts == sorted(counts), (fam.name, labels, cum)
            assert cum[-1][0] == "+Inf"
            assert cum[-1][1] == series.count, (fam.name, labels, cum)
            assert series.count == 0 or series.sum != math.inf
            checked += 1
    assert checked >= 9   # the meta-test really walked the families
    m1.unregister()
    m2.unregister()


# ---------------------------------------------------------------------------
# request event log (observability PR tentpole)
# ---------------------------------------------------------------------------

def test_request_log_events_ring_inflight_and_jsonl(tmp_path):
    """RequestLog unit contract: events stamp wall + monotonic clocks,
    the ring serves recent(), in-flight tracking adds on the first
    non-terminal event and retires on terminal kinds AND on
    rerouted_from links, and the JSONL file carries one record per
    event."""
    from paddle_tpu.observability.request_log import (
        RequestLog, get_request_log, install_request_log,
        uninstall_request_log)

    assert get_request_log() is None
    log = install_request_log(RequestLog(log_dir=str(tmp_path),
                                         run_name="r"))
    try:
        assert get_request_log() is log
        log.event("submitted", request_id="e-0", engine="e")
        log.event("queued", request_id="e-0", queue_depth=1)
        log.event("submitted", request_id="e-1", engine="e")
        assert log.inflight_ids() == ["e-0", "e-1"]
        log.event("finished", request_id="e-0", finish_reason="length",
                  tokens=3)
        assert log.inflight_ids() == ["e-1"]
        # failover: the new id supersedes the stranded one
        log.event("routed", request_id="f-7", rerouted_from="e-1",
                  tenant="t")
        assert log.inflight_ids() == ["f-7"]
        log.event("stream_closed", request_id="f-7", reason="length")
        assert log.inflight_ids() == []
        recent = log.recent()
        assert [r["kind"] for r in recent] == [
            "submitted", "queued", "submitted", "finished", "routed",
            "stream_closed"]
        assert all("ts" in r and "t_mono" in r for r in recent)
        monos = [r["t_mono"] for r in recent]
        assert monos == sorted(monos)
        assert log.event_count == 6
        assert log.recent(2)[-1]["kind"] == "stream_closed"
    finally:
        uninstall_request_log()
    assert get_request_log() is None
    lines = [json.loads(l) for l in
             open(str(tmp_path / "r.jsonl")) if l.strip()]
    assert len(lines) == 6
    assert lines[0]["kind"] == "submitted"
    assert lines[4]["rerouted_from"] == "e-1"


def test_request_log_rotation_bounded(tmp_path):
    """The JSONL rotates at max_bytes keeping max_files generations —
    the StepLogger discipline, so a chatty serving fleet can never grow
    the log without bound."""
    import os
    from paddle_tpu.observability.request_log import RequestLog

    log = RequestLog(log_dir=str(tmp_path), run_name="rot",
                     max_bytes=600, max_files=2)
    for i in range(60):
        log.event("decode", request_id=f"e-{i % 4}", slot=i % 4,
                  dispatch=i, tokens=8)
    log.close()
    names = sorted(os.listdir(str(tmp_path)))
    assert "rot.jsonl" in names
    gens = [n for n in names if n.startswith("rot.jsonl.")]
    assert gens and len(gens) <= 2             # bounded retention
    assert all(os.path.getsize(str(tmp_path / n)) <= 600 + 200
               for n in names)


def test_requestz_endpoint_serves_inflight_and_filter(tiny_engine_params,
                                                      tmp_path):
    """/requestz serves the installed log's in-flight ids + recent
    events, filters by ?request_id=, and reports enabled=false with no
    log installed."""
    import urllib.request
    from paddle_tpu.observability.request_log import (
        RequestLog, install_request_log, uninstall_request_log)

    cfg, params = tiny_engine_params
    server = obs.DebugServer(port=0)
    try:
        def get(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{server.port}{path}",
                    timeout=10) as r:
                return json.loads(r.read())

        off = get("/requestz")
        assert off["enabled"] is False and off["events"] == []
        log = install_request_log(RequestLog(log_dir=str(tmp_path)))
        try:
            eng = pt.serving.ServingEngine(
                params, cfg, pt.serving.ServingConfig(
                    num_slots=2, prefill_buckets=(4, 8), max_len=32))
            r1 = eng.submit(np.asarray([1, 2, 3], np.int32), 4)
            r2 = eng.submit(np.asarray([4, 5], np.int32), 4)
            mid = get("/requestz")
            assert mid["enabled"] is True
            assert set(mid["inflight"]) == {r1.request_id,
                                            r2.request_id}
            eng.run_until_drained()
            done = get(f"/requestz?request_id={r1.request_id}")
            assert done["inflight"] == []
            kinds = [e["kind"] for e in done["events"]]
            assert kinds[0] == "submitted" and kinds[-1] == "finished"
            assert all(e["request_id"] == r1.request_id
                       for e in done["events"])
            import urllib.error
            with pytest.raises(urllib.error.HTTPError) as ei:
                get("/requestz?limit=bogus")
            assert ei.value.code == 400
            eng.close()
        finally:
            uninstall_request_log()
    finally:
        server.stop()


def test_flight_record_meta_joins_inflight_requests(tiny_engine_params,
                                                    tmp_path):
    """Watchdog satellite: a flight record's meta.json snapshots the
    in-flight request ids at dump time, so a stall/overload dump joins
    against the request event log — the dumped id has a full lifecycle
    prefix in the log, and a post-drain dump carries none."""
    import os
    from paddle_tpu.observability.request_log import (
        RequestLog, install_request_log, uninstall_request_log)

    cfg, params = tiny_engine_params
    log = install_request_log(RequestLog(log_dir=str(tmp_path / "lg")))
    try:
        eng = pt.serving.ServingEngine(
            params, cfg, pt.serving.ServingConfig(
                num_slots=2, prefill_buckets=(4, 8), max_len=32))
        req = eng.submit(np.asarray([1, 2, 3], np.int32), 6)
        rec = obs.FlightRecorder(base_dir=str(tmp_path / "f"))
        path = rec.dump("stall", {"stalled": {"engine:x": {}}})
        meta = json.load(open(os.path.join(path, "meta.json")))
        assert req.request_id in meta["inflight_request_ids"]
        # the join: the dumped id's lifecycle prefix is in the log
        kinds = [e["kind"] for e in log.recent()
                 if e["request_id"] == req.request_id]
        assert "submitted" in kinds and "queued" in kinds
        eng.run_until_drained()
        path2 = rec.dump("manual")
        meta2 = json.load(open(os.path.join(path2, "meta.json")))
        assert meta2["inflight_request_ids"] == []
        eng.close()
    finally:
        uninstall_request_log()
    # with no log installed the field is present and empty (meta shape
    # is stable for tooling)
    rec2 = obs.FlightRecorder(base_dir=str(tmp_path / "f2"))
    meta3 = json.load(open(os.path.join(rec2.dump("manual"),
                                        "meta.json")))
    assert meta3["inflight_request_ids"] == []


# ---------------------------------------------------------------------------
# performance-attribution plane (tick profiler + compile journal +
# /metricz exposition)
# ---------------------------------------------------------------------------

_TICK_PHASE_NAMES = {"admit", "prefill_chunk", "launch", "collect",
                     "stream", "bookkeeping"}

_PROFILE_FAMILIES = {"serving_tick_phase_seconds",
                     "serving_compiles_total",
                     "serving_compile_seconds",
                     "serving_mfu_proxy",
                     "serving_dispatch_hbm_bytes"}


def _attr_engine(params, cfg, **kw):
    return pt.serving.ServingEngine(
        params, cfg, pt.serving.ServingConfig(
            num_slots=2, max_queue=16, prefill_buckets=(4, 8),
            max_len=32, **kw))


def _attr_prompts(cfg, n=6):
    rng = np.random.RandomState(0)
    return [rng.randint(0, cfg.vocab_size, (3 + i % 5,))
            .astype(np.int32) for i in range(n)]


def test_tick_profile_disabled_is_noop(tiny_engine_params):
    """The off path is PINNED byte-identical: a default engine
    registers no profile families, holds no journal or tick ring, and
    its token streams + compile events match a tick_profile=True twin
    exactly — flipping the knob changes observability only."""
    cfg, params = tiny_engine_params
    # materialize the standard serving families once so the before/
    # after family-set comparison isolates THIS engine's additions
    warm = _attr_engine(params, cfg)
    warm.generate(_attr_prompts(cfg, 2), max_new_tokens=2)
    warm.close()
    before = set(obs.get_registry().snapshot())
    assert not before & _PROFILE_FAMILIES     # nobody leaked them
    eng = _attr_engine(params, cfg)
    outs_off = eng.generate(_attr_prompts(cfg), max_new_tokens=4)
    assert eng.compile_journal is None
    assert eng._tick_records() == []
    assert set(obs.get_registry().snapshot()) == before
    # the profiled twin: identical streams, identical compile events
    eng2 = _attr_engine(params, cfg, tick_profile=True)
    outs_on = eng2.generate(_attr_prompts(cfg), max_new_tokens=4)
    assert [list(map(int, o)) for o in outs_on] == \
        [list(map(int, o)) for o in outs_off]
    assert eng2.stats()["compiled_executables"] == \
        eng.stats()["compiled_executables"]
    assert eng2.compile_journal is not None
    assert _PROFILE_FAMILIES <= set(obs.get_registry().snapshot())
    label = eng2.stats()["engine_label"]
    eng.close()
    eng2.close()
    # close() retires every profile series the twin registered
    for fam in obs.get_registry().snapshot().values():
        assert not any(s["labels"].get("engine") == label
                       for s in fam["series"]), fam


def test_tick_profile_phase_sum_matches_wall(tiny_engine_params):
    """Every flight-ring record decomposes its tick exactly: the phase
    seconds sum to the recorded wall time, phases come from the fixed
    vocabulary, stamps are monotone, and the registry histograms carry
    the same totals."""
    cfg, params = tiny_engine_params
    eng = _attr_engine(params, cfg, tick_profile=True)
    try:
        eng.generate(_attr_prompts(cfg), max_new_tokens=4)
        recs = eng._tick_records()
        assert recs
        for rec in recs:
            assert set(rec["phases"]) == _TICK_PHASE_NAMES
            assert all(v >= 0.0 for v in rec["phases"].values()), rec
            assert rec["wall_s"] == pytest.approx(
                sum(rec["phases"].values()), abs=1e-9)
            for key in ("step", "t_mono", "emitted", "active", "queue"):
                assert key in rec, rec
        stamps = [r["t_mono"] for r in recs]
        assert stamps == sorted(stamps)
        # registry agreement: per-phase histogram sums == ring totals
        label = eng.stats()["engine_label"]
        snap = obs.get_registry().snapshot()
        series = {r["labels"]["phase"]: r
                  for r in snap["serving_tick_phase_seconds"]["series"]
                  if r["labels"].get("engine") == label}
        assert set(series) == _TICK_PHASE_NAMES
        for phase, row in series.items():
            assert row["count"] == len(recs)
            assert row["sum"] == pytest.approx(
                sum(r["phases"][phase] for r in recs), rel=1e-9)
        # the /varz rollup renders the same attribution with shares
        from paddle_tpu.observability.debug_server import _serving_varz
        varz = _serving_varz(snap)
        assert set(varz["tick_phases"]) == _TICK_PHASE_NAMES
        shares = [row["share"] for row in varz["tick_phases"].values()]
        assert sum(shares) == pytest.approx(1.0, abs=0.01)
    finally:
        eng.close()


def test_compile_journal_families_and_gauges(tiny_engine_params,
                                             monkeypatch):
    """The journal attributes every jit dispatch: family rows for both
    prefill buckets, the fused decode chunk and the sampler, compile
    wall seconds with shares summing to 1, cost_analysis-derived
    per-dispatch FLOPs, and the live mfu-proxy / HBM gauges. The proxy
    divides by a peak the operator states or the device's published
    one; the CPU has neither, so without the variable there is none."""
    from paddle_tpu.serving.scheduler import CompileJournal
    bare = CompileJournal()
    bare.note_call("decode_chunk", 0.1, True, {"flops": 1e9})
    assert bare.peak_flops is None and bare.mfu_proxy() is None
    monkeypatch.setenv("PT_SERVING_PEAK_FLOPS", "1e12")
    cfg, params = tiny_engine_params
    eng = _attr_engine(params, cfg, tick_profile=True)
    try:
        eng.generate(_attr_prompts(cfg), max_new_tokens=4)
        snap = eng.compile_journal.snapshot()
        fams = snap["families"]
        assert "decode_chunk" in fams and "admit_sample" in fams
        assert any(n.startswith("prefill:L") for n in fams)
        for name, fam in fams.items():
            assert fam["calls"] >= fam["compiles"] >= 1, (name, fam)
            assert fam["compile_s"] >= 0.0
            assert 0.0 <= fam["compile_share"] <= 1.0
        assert snap["compiles_total"] == sum(
            f["compiles"] for f in fams.values())
        assert snap["compile_seconds_total"] > 0
        assert sum(f["compile_share"] for f in fams.values()) == \
            pytest.approx(1.0, abs=1e-6)
        # cost model landed for the decode chunk -> derived gauges live
        assert fams["decode_chunk"]["flops"] and \
            fams["decode_chunk"]["flops"] > 0
        assert 0 < snap["mfu_proxy"] < 1
        assert snap["dispatch_hbm_bytes"] > 0
        # the registry carries the same compile counts per family
        label = eng.stats()["engine_label"]
        reg = obs.get_registry().snapshot()
        counts = {r["labels"]["family"]: r["value"]
                  for r in reg["serving_compiles_total"]["series"]
                  if r["labels"].get("engine") == label}
        assert counts == {n: f["compiles"] for n, f in fams.items()}
        assert next(
            r for r in reg["serving_mfu_proxy"]["series"]
            if r["labels"].get("engine") == label)["value"] > 0
    finally:
        eng.close()


def _parse_prom_samples(text):
    """Strict exposition parse: {family: {"help", "type"}} +
    [(name, {label: value}, float)] samples; asserts HELP/TYPE precede
    any sample of their family."""
    metas, samples, seen_meta = {}, [], set()
    for line in text.strip().split("\n"):
        if line.startswith("# "):
            kind, name, rest = line[2:].split(" ", 2)
            assert kind in ("HELP", "TYPE"), line
            metas.setdefault(name, {})[kind.lower()] = rest
            seen_meta.add(name)
            continue
        m = re.match(r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'
                     r'(?:\{(.*)\})? (\S+)$', line)
        assert m, f"malformed sample line: {line!r}"
        name, labelstr, value = m.groups()
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        assert base in seen_meta or name in seen_meta, \
            f"sample before HELP/TYPE: {line!r}"
        labels = {}
        for lm in re.finditer(r'([a-zA-Z_][a-zA-Z0-9_]*)='
                              r'"((?:[^"\\]|\\.)*)"', labelstr or ""):
            labels[lm.group(1)] = (lm.group(2)
                                   .replace("\\n", "\n")
                                   .replace('\\"', '"')
                                   .replace("\\\\", "\\"))
        samples.append((name, labels,
                        float(value) if value != "+Inf"
                        else float("inf")))
    return metas, samples


def test_metricz_strict_exposition(tiny_engine_params):
    """/metricz satisfies a strict text-format 0.0.4 parse: HELP+TYPE
    per family before its samples, per-series bucket monotonicity with
    +Inf == _count, label escaping that round-trips, and
    ?aggregate=engine folds the per-replica label away."""
    import urllib.request
    cfg, params = tiny_engine_params
    nasty = 'C:\\tmp\\"q"\nnext'
    obs.get_registry().counter(
        "exposition_roundtrip_total",
        "label-escape probe").labels(path=nasty).inc(3)
    eng = _attr_engine(params, cfg, tick_profile=True)
    server = obs.DebugServer(port=0)
    try:
        eng.generate(_attr_prompts(cfg), max_new_tokens=4)

        def get(path):
            with urllib.request.urlopen(
                    f"{server.url}{path}", timeout=10) as r:
                return r.headers, r.read().decode()

        headers, text = get("/metricz")
        assert headers["Content-Type"].startswith(
            "text/plain; version=0.0.4")
        metas, samples = _parse_prom_samples(text)
        for name, meta in metas.items():
            assert set(meta) == {"help", "type"}, name
            assert meta["type"].split()[-1] in (
                "counter", "gauge", "histogram"), (name, meta)
        # bucket monotonicity per series; +Inf bucket == _count
        counts = {(n[:-6], tuple(sorted(l.items()))): v
                  for n, l, v in samples if n.endswith("_count")}
        buckets = {}
        for n, labels, v in samples:
            if not n.endswith("_bucket"):
                continue
            key = (n[:-7], tuple(sorted(
                (k, lv) for k, lv in labels.items() if k != "le")))
            buckets.setdefault(key, []).append(
                (float(labels["le"]) if labels["le"] != "+Inf"
                 else float("inf"), v))
        assert buckets            # the profiled engine exported some
        for key, rows in buckets.items():
            rows.sort()
            bounds = [b for b, _ in rows]
            assert bounds == sorted(set(bounds)), (key, rows)
            vals = [c for _, c in rows]
            assert vals == sorted(vals), (key, rows)
            assert rows[-1][0] == float("inf")
            assert rows[-1][1] == counts[key], (key, rows)
        # tick-phase histograms made it out the pipe
        assert any(n == "serving_tick_phase_seconds_bucket"
                   for n, _, _ in samples)
        # label escaping round-trips through the strict parser
        probe = [(l, v) for n, l, v in samples
                 if n == "exposition_roundtrip_total"]
        assert probe == [({"path": nasty}, 3.0)]
        # aggregation folds the engine label into fleet totals
        _, agg = get("/metricz?aggregate=engine")
        assert 'engine="' not in agg
        agg_samples = _parse_prom_samples(agg)[1]
        label = eng.stats()["engine_label"]
        sub = next(v for n, l, v in samples
                   if n == "serving_submitted_total"
                   and l.get("engine") == label)
        agg_sub = next(v for n, l, v in agg_samples
                       if n == "serving_submitted_total")
        assert agg_sub >= sub
    finally:
        server.stop()
        eng.close()


def test_every_ring_endpoint_rejects_malformed_limit():
    """Meta-test (satellite): EVERY ring-serving endpoint routes
    ?limit= through _parse_limit — negative and non-integer values are
    a 400 with a remediation message, never a 500 or a silent
    full-ring dump."""
    import urllib.error
    import urllib.request
    server = obs.DebugServer(port=0)
    try:
        for ep in ("/tracez", "/trainz", "/requestz", "/tickz",
                   "/compilez", "/alertz", "/statusz"):
            for bad in ("-1", "x", "1.5"):
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(
                        f"{server.url}{ep}?limit={bad}", timeout=10)
                assert ei.value.code == 400, (ep, bad)
                body = json.loads(ei.value.read())
                assert "limit" in body["error"], (ep, bad, body)
            for good in ("0", "5"):
                with urllib.request.urlopen(
                        f"{server.url}{ep}?limit={good}",
                        timeout=10) as r:
                    assert r.status == 200, (ep, good)
    finally:
        server.stop()


def test_tickz_compilez_endpoints_serve_and_filter(tiny_engine_params):
    """/tickz and /compilez serve the live engine's rings with
    ?engine= filtering, ?limit= slicing and the chrome-trace download;
    close() deregisters the perf sources so the endpoints report
    enabled=false afterwards."""
    import urllib.request
    cfg, params = tiny_engine_params
    server = obs.DebugServer(port=0)
    eng = _attr_engine(params, cfg, tick_profile=True)
    try:
        eng.generate(_attr_prompts(cfg), max_new_tokens=4)
        label = eng.stats()["engine_label"]

        def get(path):
            with urllib.request.urlopen(
                    f"{server.url}{path}", timeout=10) as r:
                return json.loads(r.read())

        tickz = get("/tickz")
        assert tickz["enabled"] is True
        assert label in tickz["engines"] and tickz["count"] > 0
        assert all(set(r["phases"]) == _TICK_PHASE_NAMES
                   for r in tickz["engines"][label])
        one = get(f"/tickz?engine={label}&limit=1")
        assert list(one["engines"]) == [label]
        assert len(one["engines"][label]) == 1
        assert get("/tickz?engine=nope")["engines"] == {}
        chrome = get(f"/tickz?chrome=1&engine={label}")
        phs = [ev["ph"] for ev in chrome["traceEvents"]]
        assert "X" in phs and set(phs) <= {"X", "M"}
        compilez = get("/compilez")
        assert compilez["enabled"] is True
        snap = compilez["engines"][label]
        assert "decode_chunk" in snap["families"]
        assert snap["records"]
        sliced = get("/compilez?limit=1")["engines"][label]
        assert len(sliced["records"]) == 1
        assert sliced["records"][0] == snap["records"][-1]
        eng.close()
        off = get("/tickz")
        assert off["enabled"] is False and off["engines"] == {}
        assert get("/compilez")["enabled"] is False
    finally:
        server.stop()
        eng.close()


def test_metric_name_lint_clean_and_catches_violations(
        tiny_engine_params):
    """tools/check_metrics as a tier-1 contract: the fully-populated
    process registry (serving + profile + router families) lints
    clean, and synthetic convention breaks are each reported."""
    import os
    import sys as _sys
    _sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    import check_metrics
    cfg, params = tiny_engine_params
    eng = _attr_engine(params, cfg, tick_profile=True)
    try:
        eng.generate(_attr_prompts(cfg), max_new_tokens=4)
        problems = check_metrics.lint_registry(obs.get_registry())
        assert problems == []
    finally:
        eng.close()
    bad = {
        "foo_seconds": {"type": "counter", "help": "counter suffix"},
        "bar_stuff": {"type": "gauge", "help": "no unit"},
        "baz_seconds": {"type": "histogram", "help": "  "},
        "qux_seconds": {"type": "histogram",
                        "help": "latency with undocumented layout"},
    }
    msgs = check_metrics.lint_families(bad)
    assert len(msgs) == 4
    assert any("counter must end in _total" in m for m in msgs)
    assert any("no unit suffix" in m for m in msgs)
    assert any("help text is required" in m for m in msgs)
    # a histogram whose help never mentions its bucket layout is a
    # finding — but only ONE finding per family (blank help doesn't
    # double-report)
    assert any("bucket" in m and "qux_seconds" in m for m in msgs)
    assert sum("baz_seconds" in m for m in msgs) == 1


# ---------------------------------------------------------------------------
# fleet health & alerting plane (timeseries + alerts)
# ---------------------------------------------------------------------------

class _FakeClock:
    """Injectable monotonic clock for the health plane."""

    def __init__(self, t=1000.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


def test_health_plane_disabled_is_noop():
    """Acceptance pin: a process that never builds a FleetHealth/
    AlertEngine has no sampler thread and no live health-plane series
    in the registry — the disabled path stays byte-identical."""
    assert "pt-health-sampler" not in {
        t.name for t in threading.enumerate()}
    snap = obs.get_registry().snapshot()
    for name, fam in snap.items():
        if name.startswith(("server_alerts", "server_alert",
                            "server_health", "timeseries_")):
            assert fam["series"] == [], name


def test_timeseries_store_rate_delta_quantile_ring():
    """TimeSeriesStore core: counters/gauges record `value`, histograms
    their cumulative count+sum sub-series; rings are bounded at
    `capacity`; rate/delta/p_quantile derive over the window and
    aggregate across series with labels=None."""
    reg = obs.MetricsRegistry()
    clk = _FakeClock()
    store = obs.TimeSeriesStore(registry=reg, capacity=8, clock=clk)
    store.track("demo_total", "demo_gauge", "demo_seconds")
    assert store.tracked() == ("demo_total", "demo_gauge",
                               "demo_seconds")
    ctr = reg.counter("demo_total", "h").labels(engine="e0")
    gauge = reg.gauge("demo_gauge", "h").labels(engine="e0")
    hist = reg.histogram("demo_seconds", "h (bucket)").labels(
        engine="e0")
    for i in range(20):
        ctr.inc(5)
        gauge.set(i)
        hist.observe(0.25)
        store.sample(now=clk.advance(1.0))
    # ring bound: only the newest `capacity` points survive
    pts = store.points("demo_total", {"engine": "e0"})
    assert len(pts) == 8
    assert pts == sorted(pts)
    # counter rate: 5 increments per second
    assert store.rate("demo_total", 6.0, now=clk.t) == \
        pytest.approx(5.0)
    # histogram count sub-series rates like a counter (1 observe/s)
    assert store.rate("demo_seconds", 6.0, field="count",
                      now=clk.t) == pytest.approx(1.0)
    assert store.rate("demo_seconds", 6.0, field="sum",
                      now=clk.t) == pytest.approx(0.25)
    # gauge delta over the last 5 s: 5 in-window steps of +1
    assert store.delta("demo_gauge", 5.0, now=clk.t) == \
        pytest.approx(5.0)
    # nearest-rank quantile pools in-window values
    assert store.p_quantile("demo_gauge", 1.0, 5.0, now=clk.t) == 19.0
    assert store.p_quantile("demo_gauge", 0.0, 5.0, now=clk.t) == 14.0
    # latest() sums each series' newest point across label sets
    reg.gauge("demo_gauge", "h").labels(engine="e1").set(100)
    store.sample(now=clk.advance(1.0))
    assert store.latest("demo_gauge") == pytest.approx(119.0)
    assert store.latest("demo_gauge", {"engine": "e1"}) == 100.0
    # empty window / unknown family degrade to None, never raise
    assert store.rate("demo_total", 0.0, now=clk.t) is None
    assert store.rate("nope_total", 60.0, now=clk.t) is None
    assert store.delta("nope_total", 60.0, now=clk.t) is None
    assert store.p_quantile("nope_total", 0.5, 60.0, now=clk.t) is None
    with pytest.raises(ValueError):
        store.p_quantile("demo_gauge", 1.5, 60.0)


def test_timeseries_counter_reset_aware_rate():
    """A tracked value that decreases reads as a restart from zero
    (Prometheus counter semantics), not a negative rate."""
    reg = obs.MetricsRegistry()
    clk = _FakeClock()
    store = obs.TimeSeriesStore(registry=reg, clock=clk)
    store.track("demo_gauge")
    g = reg.gauge("demo_gauge", "h").labels(k="a")
    for v in (10.0, 20.0, 2.0, 4.0):       # reset between 20 and 2
        g.set(v)
        store.sample(now=clk.advance(1.0))
    # increase = 10 (10->20) + 2 (restart) + 2 (2->4) over 3 s
    assert store.rate("demo_gauge", 10.0, now=clk.t) == \
        pytest.approx(14.0 / 3.0)


def test_timeseries_cardinality_cap_and_eviction():
    """Series past `max_series` are counted in dropped_series and never
    stored; rings whose labels retire from the registry are evicted on
    the next poll (a rebuilt engine reusing the label starts clean)."""
    reg = obs.MetricsRegistry()
    clk = _FakeClock()
    store = obs.TimeSeriesStore(registry=reg, capacity=4, max_series=2,
                                clock=clk)
    store.track("demo_total")
    fam = reg.counter("demo_total", "h")
    for i in range(4):
        fam.labels(engine=f"e{i}").inc()
    store.sample(now=clk.advance(1.0))
    assert store.series_count() == 2
    assert store.stats()["dropped_series"] == 2
    # retire a ring-holding series: the next poll evicts its ring and
    # the freed slot admits a previously-dropped series on the poll
    # after that
    assert fam.remove(engine="e0")
    store.sample(now=clk.advance(1.0))
    assert store.stats()["evicted_series"] == 1
    assert store.series_count() == 1
    store.sample(now=clk.advance(1.0))
    assert store.series_count() == 2
    # untrack drops the family's rings wholesale
    store.untrack("demo_total")
    assert store.series_count() == 0


def test_prometheus_aggregate_mixed_bucket_layouts_unaggregated():
    """Regression (satellite): folding a histogram family whose series
    carry DIFFERENT per-series bucket layouts must not silently merge
    cumulative counts over mismatched bounds — those series are emitted
    unaggregated under their original labels, while a same-layout
    family still folds."""
    reg = obs.MetricsRegistry()
    mixed = reg.histogram("demo_mixed_tokens",
                          "per-engine bucket layouts")
    mixed.labels(engine="e0", _buckets=(1.0, 2.0)).observe(1.5)
    mixed.labels(engine="e1", _buckets=(1.0, 4.0)).observe(3.0)
    same = reg.histogram("demo_same_seconds", "one bucket layout",
                         buckets=(0.1, 1.0))
    same.labels(engine="e0").observe(0.05)
    same.labels(engine="e1").observe(0.5)
    text = reg.to_prometheus(aggregate_label="engine")
    # mismatched layouts: both engine-labelled series survive verbatim
    assert 'demo_mixed_tokens_bucket{engine="e0"' in text
    assert 'demo_mixed_tokens_bucket{engine="e1"' in text
    mixed_counts = [ln for ln in text.splitlines()
                    if ln.startswith("demo_mixed_tokens_count")]
    assert len(mixed_counts) == 2
    assert all('engine="' in ln for ln in mixed_counts)
    # a uniform layout still folds into one fleet series
    same_lines = [ln for ln in text.splitlines()
                  if ln.startswith("demo_same_seconds")]
    assert same_lines and all('engine="' not in ln
                              for ln in same_lines)
    assert "demo_same_seconds_count 2" in text
    # and the raw export is untouched by the fallback
    raw = reg.to_prometheus()
    assert raw.count("demo_mixed_tokens_count{") == 2


def test_registry_rollup_ratio_edges():
    """Satellite pin: zero denominators, absent families, and degraded
    (None) columns all read as None from ratio() — never 0.0, never a
    KeyError — and a rollup over only-absent families is empty."""
    from paddle_tpu.observability.debug_server import (registry_rollup,
                                                       ratio)
    reg = obs.MetricsRegistry()
    reg.counter("hits_total", "h").labels(engine="e0").inc(0)
    snap = reg.snapshot()
    rows = registry_rollup(
        snap, {"hits": "hits_total", "misses": "misses_total"},
        derived=[("ratio", ratio("hits", ("hits", "misses")))])
    assert rows == {"e0": {"hits": 0, "misses": 0, "ratio": None}}
    # a rollup where NO named family exists has no labels at all
    assert registry_rollup(snap, {"x": "nope_total"}) == {}
    fn = ratio("num", "den")
    assert fn({"num": None, "den": 5}) is None    # degraded numerator
    assert fn({"den": 5}) is None                 # absent numerator
    assert fn({"num": 3, "den": 0}) is None       # zero denominator
    assert fn({"num": 3, "den": None}) is None    # degraded denominator
    assert fn({"num": 3}) is None                 # absent denominator
    assert fn({"num": 3, "den": 4}) == pytest.approx(0.75)


def test_alert_engine_state_machine_hold_downs():
    """ok -> pending -> firing with the for_s hold-down; clear_for_s
    keeps a flapping rule firing until it stays clean; exactly one
    on_fire per episode; a broken expr never pages; unregister()
    retires every minted series."""
    reg = obs.MetricsRegistry()
    clk = _FakeClock()
    store = obs.TimeSeriesStore(registry=reg, clock=clk)
    probe = {"v": None}
    rule = obs.AlertRule("probe", lambda ctx: probe["v"], for_s=10.0,
                         clear_for_s=10.0, severity="page",
                         labels={"team": "serving"})
    fired = []
    eng = obs.AlertEngine(store, [rule], registry=reg, clock=clk,
                          label="t",
                          on_fire=lambda r, s: fired.append((r, s)))
    assert eng.evaluate() == []
    probe["v"] = 1.0
    assert eng.evaluate(now=clk.advance(1.0)) == []     # pending
    assert eng.evaluate(now=clk.advance(5.0)) == []     # 5s < for_s
    assert eng.evaluate(now=clk.advance(5.0)) == ["probe"]
    assert fired == [("probe", "page")]
    assert eng.pressure_hint() == 1.0
    assert eng.health() == {"status": "page", "score": 60.0,
                            "firing": ["probe"]}

    def firing_gauge():
        rows = reg.snapshot()["server_alerts_firing"]["series"]
        return {tuple(sorted(r["labels"].items())): r["value"]
                for r in rows}

    assert firing_gauge() == {(("rule", "probe"), ("severity", "page"),
                               ("source", "t")): 1}
    # flapping: a brief clean stretch does NOT clear (ok_since resets
    # on re-violation)
    probe["v"] = None
    assert eng.evaluate(now=clk.advance(5.0)) == ["probe"]
    probe["v"] = 2.0
    assert eng.evaluate(now=clk.advance(1.0)) == ["probe"]
    probe["v"] = None
    assert eng.evaluate(now=clk.advance(5.0)) == ["probe"]
    assert eng.evaluate(now=clk.advance(10.0)) == []    # held clean
    assert fired == [("probe", "page")]                 # one episode
    assert eng.pressure_hint() == 0.0
    assert firing_gauge()[(("rule", "probe"), ("severity", "page"),
                           ("source", "t"))] == 0
    trans = eng.transitions()
    assert [(t["from"], t["to"]) for t in trans] == [
        ("ok", "pending"), ("pending", "firing"), ("firing", "ok")]
    assert all(t["rule"] == "probe" and t["severity"] == "page"
               and t["labels"] == {"team": "serving"} for t in trans)
    assert eng.transitions(limit=1)[0]["to"] == "ok"
    assert eng.transitions(limit=0) == []
    # a broken expr evaluates as not-violating, never raises or pages
    eng.add_rule(obs.AlertRule("broken", lambda ctx: 1 / 0,
                               severity="page"))
    assert eng.evaluate(now=clk.advance(1.0)) == []
    with pytest.raises(ValueError):
        eng.add_rule(obs.AlertRule("probe", lambda ctx: None))
    eng.unregister()
    snap = reg.snapshot()
    for fam in ("server_alerts_firing", "server_alert_transitions_total",
                "server_health_score"):
        assert snap.get(fam, {}).get("series") == [], fam


def test_slo_burn_storm_fires_one_flight_record_and_clears(
        tmp_path, monkeypatch):
    """Acceptance: an induced SLO-miss storm under a fake clock fires
    the multi-window burn-rate rules within their windows, emits
    exactly ONE watchdog flight record for the episode, surfaces at
    /alertz and /statusz, and clears with the hold-down once the storm
    stops. close() tears the whole plane down."""
    import urllib.request
    from paddle_tpu.observability import watchdog as wd_mod
    reg = obs.MetricsRegistry()
    clk = _FakeClock()
    wd = obs.Watchdog(stall_threshold=30.0, base_dir=str(tmp_path),
                      registry=reg)
    monkeypatch.setattr(wd_mod, "_WATCHDOG", wd)   # installed, no thread
    fh = obs.FleetHealth(config=obs.HealthConfig(interval_s=15.0),
                         registry=reg, clock=clk, label="t")
    met = reg.counter("server_slo_met_total", "h").labels(router="0")
    missed = reg.counter("server_slo_missed_total",
                         "h").labels(router="0")
    server = obs.DebugServer(port=0)

    def get(path):
        with urllib.request.urlopen(f"{server.url}{path}",
                                    timeout=10) as r:
            return json.loads(r.read())

    try:
        fh.start()
        assert fh.sampler.running
        assert "pt-health-sampler" in {t.name
                                       for t in threading.enumerate()}
        # 90%-miss storm, one tick per 15 s of fake time: the page tier
        # (14.4x budget over 1h AND 5m) must fire within its short
        # window once both windows carry >= 2 points
        firing = []
        for tick in range(40):                     # 10 min of storm
            met.inc(1)
            missed.inc(9)
            firing = fh.tick(now=clk.advance(15.0))
            if "slo_burn_rate_page" in firing:
                break
        assert "slo_burn_rate_page" in firing
        assert tick * 15.0 <= 300.0                # within the 5m window
        assert fh.pressure_hint() == 1.0
        assert fh.health()["status"] == "page"
        # the transition value is the short-window burn rate: 90% miss
        # against a 1% budget reads ~90x
        page_fire = [t for t in fh.engine.transitions()
                     if t["rule"] == "slo_burn_rate_page"
                     and t["to"] == "firing"]
        assert len(page_fire) == 1
        assert page_fire[0]["value"] == pytest.approx(90.0, rel=0.05)
        # keep storming: the episode stays ONE episode
        for _ in range(10):
            met.inc(1)
            missed.inc(9)
            fh.tick(now=clk.advance(15.0))
        assert wd.check() is not None              # drains the pending dump
        assert len(wd.recorder.records()) == 1     # exactly one record
        meta = json.loads(open(os.path.join(
            wd.recorder.records()[0], "meta.json")).read())
        assert meta["reason"] == "alert"
        assert meta["details"]["rule"].startswith("slo_burn_rate")
        assert wd.check() is None                  # nothing else queued
        assert len(wd.recorder.records()) == 1
        # the plane surfaces over HTTP while firing
        alertz = get("/alertz")
        assert alertz["enabled"] is True
        assert "slo_burn_rate_page" in alertz["firing"]
        src = alertz["sources"]["t"]
        assert src["label"] == "t" and src["transitions"]
        assert src["store"]["series"] > 0
        assert get("/alertz?source=nope")["sources"] == {}
        statusz = get("/statusz")
        assert statusz["enabled"] is True
        assert statusz["status"] == "page"
        assert statusz["health_score"] <= 60.0
        assert "slo_burn_rate_page" in statusz["firing"]
        assert statusz["sources"]["t"]["status"] == "page"
        assert statusz["process"]["pid"] == os.getpid()
        # storm ends: the page tier needs clear_for_s=300s of clean
        # short-window burn before resolving — count the clean time
        clean_ticks = 0
        while clean_ticks < 200:
            met.inc(10)
            firing = fh.tick(now=clk.advance(15.0))
            clean_ticks += 1
            if "slo_burn_rate_page" not in firing:
                break
        assert clean_ticks < 200
        assert clean_ticks * 15.0 >= 300.0         # hold-down respected
        assert "slo_burn_rate_page" not in firing
        # the health-plane stat series advanced under the storm
        snap = reg.snapshot()
        pts = snap["timeseries_points_total"]["series"]
        assert pts and pts[0]["value"] > 0
    finally:
        server.stop()
        fh.close()
        wd_mod.stop_watchdog()
    # close(): sampler joined, endpoints dormant, every series retired
    assert not fh.sampler.running
    fh.close()                                     # idempotent
    snap = reg.snapshot()
    for name, fam in snap.items():
        if name.startswith(("server_alerts", "server_alert",
                            "server_health", "timeseries_")):
            assert fam["series"] == [], name
    with pytest.raises(RuntimeError):
        fh.start()


def test_alertz_statusz_endpoints_dormant_and_close_deregistered():
    """/alertz and /statusz report enabled=false with empty rollups
    when no FleetHealth source is registered, and a started plane
    deregisters on close() (the /tickz close-discipline, satellite
    sweep)."""
    import urllib.request
    server = obs.DebugServer(port=0)

    def get(path):
        with urllib.request.urlopen(f"{server.url}{path}",
                                    timeout=10) as r:
            return json.loads(r.read())

    try:
        alertz = get("/alertz")
        assert alertz["enabled"] is False
        assert alertz["firing"] == [] and alertz["sources"] == {}
        statusz = get("/statusz")
        assert statusz["enabled"] is False
        assert statusz["status"] == "ok"
        assert statusz["health_score"] == 100.0
        assert statusz["transitions"] == []
        # /statusz doubles as a registry dump check_metrics can lint
        assert isinstance(statusz["metrics"], dict)
        reg = obs.MetricsRegistry()
        fh = obs.FleetHealth(config=obs.HealthConfig(interval_s=3600.0),
                             registry=reg, label="zz")
        fh.start()
        assert get("/alertz")["enabled"] is True
        assert "zz" in get("/alertz")["sources"]
        assert get("/statusz")["sources"]["zz"]["status"] == "ok"
        fh.close()
        assert get("/alertz")["enabled"] is False
        assert not fh.sampler.running
    finally:
        server.stop()


def test_builtin_anomaly_rules_fire_on_their_signals():
    """The non-SLO built-ins each fire on their induced signal:
    throughput collapse (active slots, zero token flow), queue growth,
    compile storm, prefix-hit-ratio drop."""
    reg = obs.MetricsRegistry()
    clk = _FakeClock()
    fh = obs.FleetHealth(config=obs.HealthConfig(interval_s=15.0),
                         registry=reg, clock=clk, label="t")
    active = reg.gauge("serving_active_slots", "h").labels(engine="e")
    queue = reg.gauge("serving_queue_depth", "h").labels(engine="e")
    compiles = reg.counter("serving_compiles_total",
                           "h").labels(engine="e")
    hits = reg.counter("serving_prefix_cache_hits_total",
                       "h").labels(engine="e")
    misses = reg.counter("serving_prefix_cache_misses_total",
                         "h").labels(engine="e")
    tokens = reg.counter("serving_tokens_out_total",
                         "h").labels(engine="e")
    tokens.inc(0)
    active.set(4)                      # slots busy, no tokens flowing
    depth = 0
    firing = []
    for _ in range(40):
        depth += 3
        queue.set(depth)               # monotone queue growth
        compiles.inc(10)               # ~0.67/s >> 0.1/s ceiling
        hits.inc(1)
        misses.inc(9)                  # 10% hit ratio < 50% floor
        firing = fh.tick(now=clk.advance(15.0))
        if len(firing) >= 4:
            break
    assert set(firing) >= {"throughput_collapse", "queue_growth",
                           "compile_storm", "prefix_hit_ratio_drop"}
    assert fh.health()["status"] == "page"     # collapse is page-tier
    fh.close()


# ---------------------------------------------------------------------------
# one source of host spans: the phases of Executor.run and of an engine
# tick in the profiler's own trace (the CPU gives names and nesting,
# never a time)
# ---------------------------------------------------------------------------

_EXEC_PHASES = {"executor/prepare", "executor/place", "executor/dispatch",
                "executor/writeback", "executor/fetch", "executor/release"}
_TICK_SPANS = {"serving/tick/admit", "serving/tick/launch",
               "serving/tick/collect", "serving/tick/stream"}
_FIRST_TOKEN_WAIT = "serving/wait/first_token"


@contextlib.contextmanager
def _profiler_session(trace_dir):
    """A jax.profiler session of somebody else's: nothing of the
    program is told about it, as benchmarks/run.py --trace 1 tells
    nothing."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _host_lines(trace_dir):
    """{line name: [(event name, start_ns, end_ns, {stat: value})]} of
    the xplane's /host:CPU plane, program spans only."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            found = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                      dict(ev.stats))
                     for ev in line.events
                     if ev.name.startswith(("executor/", "serving/"))]
            if found:
                lines.setdefault(line.name, []).extend(found)
    return lines


def _children_of(events, parent):
    """The events that lie inside `parent`'s interval (itself apart), in
    order of start."""
    lo, hi = parent[1:3]
    return sorted((e for e in events
                   if e is not parent and lo <= e[1] and e[2] <= hi),
                  key=lambda e: e[1])


def _assert_disjoint(spans):
    for a, b in zip(spans, spans[1:]):
        assert a[2] <= b[1], (a, b)


def _executor_case():
    main, startup, loss = _small_program()
    exe = pt.Executor()
    scope = pt.Scope()
    feed = {"x": np.random.rand(4, 16).astype("f")}
    with pt.scope_guard(scope):
        exe.run(startup)                                # run 0
        exe.run(main, feed=feed, fetch_list=[loss])     # run 1 compiles

    def once():                                         # run 2, 3, ...
        with pt.scope_guard(scope):
            exe.run(main, feed=feed, fetch_list=[loss])
    return once, lambda: None


def _engine_case(params_cfg, **kw):
    cfg, params = params_cfg
    eng = _attr_engine(params, cfg, **kw)
    eng.generate(_attr_prompts(cfg, 2), max_new_tokens=3)   # compiles

    def once():
        for p in _attr_prompts(cfg, 3):
            eng.submit(p, max_new_tokens=4)
        eng.run_until_drained()
    return once, eng.close


@pytest.mark.parametrize("layer", ["executor", "engine"])
def test_phase_spans_reach_the_profiler_trace(layer, tiny_engine_params,
                                              tmp_path):
    """Under a profiler session that the program knows nothing of, one
    Executor.run and the ticks of one small batch each leave their phase
    spans by name on a /host:CPU line of the xplane: children inside the
    parent's interval, none overlapping another."""
    once, close = (_executor_case() if layer == "executor"
                   else _engine_case(tiny_engine_params))
    try:
        with _profiler_session(tmp_path):
            once()
    finally:
        close()
    assert obs.get_tracer().span_count == 0     # the ring stayed off
    lines = _host_lines(tmp_path)
    assert len(lines) == 1, list(lines)         # one thread drove it
    (events,) = lines.values()
    if layer == "executor":
        (run,) = [e for e in events if e[0] == "executor/run"]
        assert int(run[3]["step"]) == 2         # the run's ordinal rides
        kids = _children_of(events, run)
        assert [k[0] for k in kids] == [
            "executor/prepare", "executor/place", "executor/dispatch",
            "executor/writeback", "executor/fetch", "executor/release"]
        _assert_disjoint(kids)
        assert len(events) == 1 + len(_EXEC_PHASES) <= 8
        return
    ticks = [e for e in events if e[0] == "serving/engine_step"]
    assert ticks
    _assert_disjoint(ticks)
    seen, tokens_waited = set(), []
    for tick in ticks:
        inside = _children_of(events, tick)
        phases = [k for k in inside if k[0].startswith("serving/tick/")]
        _assert_disjoint(phases)
        seen |= {k[0] for k in phases}
        # a dispatch lies inside its phase: a prefill in the admission,
        # the decode dispatch in the launch
        for name, phase in (("serving/prefill", "serving/tick/admit"),
                            ("serving/decode_dispatch",
                             "serving/tick/launch")):
            for k in (k for k in inside if k[0] == name):
                assert any(p[0] == phase and p[1] <= k[1] and k[2] <= p[2]
                           for p in phases), (k, phases)
        # an admission waits for nothing: a tick that admitted has ONE
        # wait for all its first tokens, a phase's sibling behind the
        # admissions and before the collect, inside no phase; it lies
        # behind the launch too where a dispatch was in flight (the
        # tick collects one besides the one it launched)
        prefills = [k for k in inside if k[0] == "serving/prefill"]
        _assert_disjoint(prefills)
        waits = [k for k in inside if k[0] == _FIRST_TOKEN_WAIT]
        assert len(waits) == bool(prefills)
        for wait in waits:
            assert int(wait[3]["tokens"]) == len(prefills)
            _assert_disjoint(sorted(phases + [wait], key=lambda k: k[1]))
            before = {p[0] for p in phases if p[2] <= wait[1]}
            assert "serving/tick/admit" in before
            assert "serving/tick/collect" not in before
            in_flight = {"serving/tick/launch", "serving/tick/collect"} \
                <= {p[0] for p in phases}
            assert ("serving/tick/launch" in before) == in_flight
            tokens_waited.append((len(prefills), in_flight))
        # at most 9 spans a tick, and one more a prefill
        assert len(inside) - len(prefills) + 1 <= 9, inside
    assert seen == _TICK_SPANS                  # monolithic prefill:
    #                                             no prefill_chunk phase
    assert sum(e[0] == "serving/prefill" for e in events) == 3
    # two admissions into the empty engine, then the third into the
    # engine they left empty: both waits ahead of their tick's launch
    assert tokens_waited == [(2, False), (1, False)]
    assert "serving/wait/fence" not in {e[0] for e in events}
    # every event of the line lies inside some tick
    assert all(any(t[1] <= e[1] and e[2] <= t[2] for t in ticks)
               for e in events)


def test_first_token_wait_lies_behind_the_launch(tiny_engine_params):
    """With a dispatch in flight a tick that admits reads the first
    tokens BEHIND its launch: one serving/wait/first_token carrying the
    tick's count of tokens, after serving/tick/launch has ended and
    before serving/tick/collect begins, every prefill inside the admit
    phase before it."""
    cfg, params = tiny_engine_params
    obs.enable_tracing()
    eng = _attr_engine(params, cfg, decode_chunk=4)
    try:
        first, *late = _attr_prompts(cfg, 2)
        eng.submit(first, max_new_tokens=14)
        eng.step()
        eng.step()
        assert eng.scheduler.inflight_count == 1
        obs.get_tracer().clear()
        eng.submit(late[0], max_new_tokens=3)
        eng.step()
        spans = {}
        for sp in obs.get_tracer().snapshot():
            spans.setdefault(sp.name, []).append(sp)
        eng.run_until_drained()
    finally:
        eng.close()
    end = lambda sp: sp.ts_us + sp.dur_us
    (admit,), (launch,), (collect,), (wait,), (prefill,) = (
        spans[name] for name in (
            "serving/tick/admit", "serving/tick/launch",
            "serving/tick/collect", "serving/wait/first_token",
            "serving/prefill"))
    assert wait.args == {"tokens": 1}
    assert admit.ts_us <= prefill.ts_us and end(prefill) <= end(admit)
    assert end(admit) <= launch.ts_us
    assert end(launch) <= wait.ts_us and end(wait) <= collect.ts_us


@pytest.mark.parametrize("layer", ["executor", "engine"])
def test_nothing_listening_records_nothing(layer, tiny_engine_params):
    """No profiler session, ring off: the spans of a step and of a tick
    record nothing anywhere, and the registry's family set is what it
    was before."""
    once, close = (_executor_case() if layer == "executor"
                   else _engine_case(tiny_engine_params))
    try:
        once()                                  # families materialize
        before = set(obs.get_registry().snapshot())
        once()
        assert set(obs.get_registry().snapshot()) == before
        tracer = obs.get_tracer()
        assert tracer.span_count == 0 and tracer.dropped == 0
    finally:
        close()


@pytest.mark.parametrize("option", ["dispatch_timing", "tick_profile"])
def test_option_sinks_read_the_spans(option, tiny_engine_params):
    """What dispatch_timing=True and tick_profile=True publish is the
    duration of the phase spans themselves (seen here through the
    ring): one sample a collected dispatch, host seconds = the
    serving/decode_dispatch spans, device seconds = the
    serving/tick/collect spans; the tick ring's phases = the
    serving/tick/* spans, and bookkeeping what they leave of the
    serving/engine_step span."""
    obs.enable_tracing()
    once, close = _engine_case(tiny_engine_params, **{option: True})
    try:
        obs.get_tracer().clear()
        snap0 = obs.get_registry().snapshot()
        once()
        snap = obs.get_registry().snapshot()
    finally:
        close()
    total = {}
    for sp in obs.get_tracer().snapshot():
        total[sp.name] = total.get(sp.name, 0.0) + sp.dur_us * 1e-6
    count = sum(sp.name == "serving/tick/collect"
                for sp in obs.get_tracer().snapshot())

    def grown(family, **labels):
        """sum and count a histogram family grew by during once(),
        over the series that carry `labels`."""
        def of(s):
            rows = [r for r in s.get(family, {}).get("series", [])
                    if all(r["labels"].get(k) == v
                           for k, v in labels.items())]
            return (sum(r["sum"] for r in rows),
                    sum(r["count"] for r in rows))
        (s1, c1), (s0, c0) = of(snap), of(snap0)
        return s1 - s0, c1 - c0

    if option == "dispatch_timing":
        host = grown("serving_dispatch_host_seconds")
        dev = grown("serving_dispatch_device_seconds")
        assert host[1] == dev[1] == count > 0
        assert host[0] == pytest.approx(total["serving/decode_dispatch"],
                                        rel=1e-6)
        assert dev[0] == pytest.approx(total["serving/tick/collect"],
                                       rel=1e-6)
        return
    ticks = sum(sp.name == "serving/engine_step"
                for sp in obs.get_tracer().snapshot())
    rest = total["serving/engine_step"]
    for phase in ("admit", "launch", "collect", "stream"):
        seconds, n = grown("serving_tick_phase_seconds", phase=phase)
        assert n == ticks
        assert seconds == pytest.approx(total[f"serving/tick/{phase}"],
                                        rel=1e-6)
        rest -= seconds
    assert grown("serving_tick_phase_seconds",
                 phase="bookkeeping")[0] == pytest.approx(rest, rel=1e-6)
    assert "serving/tick/prefill_chunk" not in total


def test_fence_inside_admit_is_no_collect_phase(tiny_engine_params):
    """Under page pressure the admission's fence collects the dispatches
    in flight INSIDE the admit phase. That wait has a span of its own
    (serving/wait/fence) and is no serving/tick/collect: the phases of a
    tick still do not overlap, still sum to its wall time with nothing
    counted twice (bookkeeping, what they leave, never negative), and
    dispatch_timing still lands one sample a dispatch."""
    cfg, params = tiny_engine_params
    obs.enable_tracing()
    eng = pt.serving.ServingEngine(
        params, cfg, pt.serving.ServingConfig(
            num_slots=4, max_queue=16, block_size=4, kv_blocks=12,
            decode_chunk=4, preempt=True, prefill_buckets=(4, 8),
            max_len=32, tick_profile=True, dispatch_timing=True))
    try:
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
                   for n in (5, 7, 4, 6)]
        # two fill the arena and leave a dispatch in flight; the third's
        # admission then has to fence it before it can swap a victim out
        for p in prompts[:2]:
            eng.submit(p, max_new_tokens=12)
        eng.step()
        for p in prompts[2:]:
            eng.submit(p, max_new_tokens=12)
        eng.run_until_drained()
        stats = eng.stats()
        recs = eng._tick_records()
        label = stats["engine_label"]
        snap = obs.get_registry().snapshot()
    finally:
        eng.close()
    assert stats["preemptions"] >= 1, "arena not tight enough to preempt"
    spans = obs.get_tracer().snapshot()
    by_name = {}
    for sp in spans:
        by_name.setdefault(sp.name, []).append(sp)
    fences = by_name["serving/wait/fence"]
    admits = by_name["serving/tick/admit"]
    for sp in fences:       # every fence wait lies inside an admit phase
        assert any(a.ts_us <= sp.ts_us and
                   sp.ts_us + sp.dur_us <= a.ts_us + a.dur_us
                   for a in admits), sp
    for sp in by_name["serving/tick/collect"]:      # and no collect does
        assert not any(a.ts_us <= sp.ts_us < a.ts_us + a.dur_us
                       for a in admits), sp
    for rec in recs:
        assert all(v >= 0.0 for v in rec["phases"].values()), rec
        assert rec["wall_s"] == pytest.approx(
            sum(rec["phases"].values()), abs=1e-9)
    assert sum(r["phases"]["collect"] for r in recs) == pytest.approx(
        sum(sp.dur_us for sp in by_name["serving/tick/collect"]) * 1e-6,
        rel=1e-6)
    (device,) = [r for r in
                 snap["serving_dispatch_device_seconds"]["series"]
                 if r["labels"].get("engine") == label]
    assert device["count"] == stats["dispatches"] == \
        len(by_name["serving/tick/collect"]) + len(fences)


if __name__ == "__main__":
    import sys
    sys.exit(pytest.main([__file__, "-x", "-q"]))
