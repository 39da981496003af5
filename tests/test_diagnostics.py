"""Live diagnostics plane: debug HTTP server, request-scoped trace
propagation, stall watchdog flight recorder.

Pins the PR-3 contracts: (1) `start_debug_server(port=0)` serves
/metrics, /healthz, /varz, /tracez, /stacksz over plain stdlib
http.client; (2) `/tracez?request_id=` reconstructs exactly one
request's end-to-end timeline (queue-wait, prefill, per-iteration
decode) out of a 3-concurrent-request engine run; (3) a watchdog
pointed at an artificially stalled engine produces a flight-record
directory with stacks + spans + a metrics snapshot within the
configured threshold, once per stall episode, with bounded retention;
(4) with tracing disabled and no debug server, the serving hot path
stays on the PR-2 no-op singleton — zero spans, zero clock stamps."""

import http.client
import json
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import observability as obs
from paddle_tpu.observability import debug_server as dbg_mod
from paddle_tpu.observability import watchdog as wd_mod


@pytest.fixture(autouse=True)
def _clean_plane():
    """Every test starts/ends with tracer off+empty, no global debug
    server, no global watchdog."""
    obs.disable_tracing()
    obs.get_tracer().clear()
    yield
    obs.disable_tracing()
    obs.get_tracer().clear()
    obs.stop_debug_server()
    obs.stop_watchdog()


def _get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, dict(r.getheaders()), r.read()
    finally:
        conn.close()


def _get_json(port, path, expect=200):
    status, headers, body = _get(port, path)
    assert status == expect, (path, status, body[:500])
    return json.loads(body)


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


def _make_engine(tiny_engine_params, slots=3, max_queue=16):
    cfg, params = tiny_engine_params
    return pt.serving.ServingEngine(
        params, cfg, pt.serving.ServingConfig(
            num_slots=slots, max_queue=max_queue, prefill_buckets=(4, 8),
            max_len=32))


# ---------------------------------------------------------------------------
# debug HTTP server
# ---------------------------------------------------------------------------

def test_debug_server_serves_all_endpoints():
    port = obs.start_debug_server(port=0)
    assert port > 0
    # idempotent while running; a conflicting fixed port refuses
    assert obs.start_debug_server(port=0) == port
    with pytest.raises(RuntimeError, match="already bound"):
        obs.start_debug_server(port=port + 1)

    status, headers, body = _get(port, "/metrics")
    assert status == 200
    assert headers["Content-Type"].startswith("text/plain")
    assert b"debug_server_requests_total" in body

    health = _get_json(port, "/healthz")
    assert health["status"] == "ok"
    assert health["watchdog"] == {"running": False}

    varz = _get_json(port, "/varz")
    assert varz["process"]["pid"] == os.getpid()
    assert varz["tracer"]["enabled"] is False
    assert "metrics" in varz and isinstance(varz["metrics"], dict)
    # paged-KV rollup: the derived prefix-hit-ratio column is always
    # present (empty dict when no engine has registered cache counters)
    assert "prefix_hit_ratio" in varz["serving"]

    tracez = _get_json(port, "/tracez")
    assert tracez["count"] == 0 and tracez["spans"] == []

    status, headers, body = _get(port, "/stacksz")
    assert status == 200
    text = body.decode()
    assert "MainThread" in text and "test_debug_server" in text

    missing = _get_json(port, "/no_such", expect=404)
    assert "/metrics" in missing["endpoints"]

    obs.stop_debug_server()
    assert obs.get_debug_server() is None
    # a stopped server releases the port binding; restart gets a port
    port2 = obs.start_debug_server(port=0)
    assert _get_json(port2, "/healthz")["status"] == "ok"


def test_tracez_modes_limit_and_chrome_download():
    port = obs.start_debug_server(port=0)
    obs.enable_tracing()
    for i in range(6):
        with obs.trace_span(f"s{i}", "t"):
            pass
    obs.disable_tracing()

    doc = _get_json(port, "/tracez?limit=2")
    assert [s["name"] for s in doc["spans"]] == ["s4", "s5"]  # newest last
    assert _get_json(port, "/tracez?limit=junk", expect=400)["error"]

    status, headers, body = _get(port, "/tracez?chrome=1")
    assert status == 200
    assert "attachment" in headers.get("Content-Disposition", "")
    trace = json.loads(body)
    names = {e["name"] for e in trace["traceEvents"] if e.get("ph") == "X"}
    assert names == {f"s{i}" for i in range(6)}
    # explicit false values mean the JSON listing, not the download
    for flag in ("0", "false"):
        doc = _get_json(port, f"/tracez?chrome={flag}")
        assert "spans" in doc and doc["count"] == 6

    # /healthz validates its threshold: typo'd units are a 400, and a
    # negative threshold can't flag healthy components as stalled
    for bad in ("30s", "-1", "0"):
        err = _get_json(port, f"/healthz?stall_threshold={bad}",
                        expect=400)
        assert "stall_threshold" in err["error"]


def test_metrics_endpoint_carries_serving_series(tiny_engine_params):
    eng = _make_engine(tiny_engine_params, slots=2)
    eng.generate([np.asarray([1, 2, 3], np.int32)], max_new_tokens=3)
    port = obs.start_debug_server(port=0)
    text = _get(port, "/metrics")[2].decode()
    label = eng.stats()["engine_label"]
    assert f'serving_completed_total{{engine="{label}"}} 1' in text
    assert "serving_ttft_seconds_bucket" in text
    assert "executor_runs_total" in text     # executor heartbeat scrapes
    eng.close()


# ---------------------------------------------------------------------------
# request-scoped trace propagation
# ---------------------------------------------------------------------------

def test_tracez_request_id_reconstructs_one_timeline(tiny_engine_params):
    """Acceptance: 3 concurrent requests through one engine; /tracez?
    request_id= returns only that request's spans, covering queue-wait,
    prefill, and every decode iteration."""
    eng = _make_engine(tiny_engine_params, slots=3)
    port = obs.start_debug_server(port=0)
    obs.enable_tracing()
    rng = np.random.RandomState(0)
    reqs = [eng.submit(rng.randint(0, 97, (4,)).astype(np.int32),
                       max_new_tokens=4) for _ in range(3)]
    eng.run_until_drained()
    obs.disable_tracing()

    assert len({r.request_id for r in reqs}) == 3   # unique, minted ids
    label = eng.stats()["engine_label"]
    for r in reqs:
        assert r.request_id.startswith(f"{label}-")

    for r in reqs:
        doc = _get_json(port, f"/tracez?request_id={r.request_id}")
        assert doc["count"] == len(doc["spans"]) > 0
        # only THIS request's spans came back
        for s in doc["spans"]:
            assert s["args"]["request_id"] == r.request_id, s
        names = [s["name"] for s in doc["spans"]]
        assert names.count("serving/queue_wait") == 1
        assert names.count("serving/prefill") == 1
        # one decode_iter per token after the first (prefill samples #1)
        assert names.count("serving/decode_iter") == len(r.tokens) - 1
        # the timeline is reconstructable: spans are timestamped and
        # ordered queue_wait -> prefill -> decode iterations
        by = {n: next(s for s in doc["spans"] if s["name"] == n)
              for n in ("serving/queue_wait", "serving/prefill")}
        assert by["serving/queue_wait"]["ts_us"] <= \
            by["serving/prefill"]["ts_us"]
    # an unknown id returns an empty, well-formed answer
    assert _get_json(port, "/tracez?request_id=nope")["count"] == 0
    eng.close()


def test_streamed_token_callback_on_request_timeline(tiny_engine_params):
    eng = _make_engine(tiny_engine_params, slots=1)
    seen = []
    obs.enable_tracing()
    req = eng.submit(np.asarray([5, 6, 7], np.int32), max_new_tokens=3,
                     on_token=lambda r, t: seen.append(t))
    eng.run_until_drained()
    obs.disable_tracing()
    assert seen == req.tokens
    cb = [s for s in obs.get_tracer().snapshot()
          if s.name == "serving/on_token"]
    assert len(cb) == len(seen)
    assert all(s.args["request_id"] == req.request_id for s in cb)
    eng.close()


def test_request_scope_tags_executor_run_spans():
    """The ambient request id crosses layers: an executor run issued
    inside a request scope lands on that request's timeline."""
    main, startup = pt.Program(), pt.Program()
    with pt.unique_name_guard(), pt.program_guard(main, startup):
        x = pt.layers.data("x", [8])
        loss = pt.layers.reduce_mean(pt.layers.fc(x, 8))
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        obs.enable_tracing()
        obs.get_tracer().clear()
        assert obs.current_request_id() is None
        with obs.request_scope("inf-42"):
            assert obs.current_request_id() == "inf-42"
            exe.run(main, feed={"x": np.zeros((2, 8), "f")},
                    fetch_list=[loss])
        assert obs.current_request_id() is None
    obs.disable_tracing()
    run = [s for s in obs.get_tracer().snapshot()
           if s.name == "executor/run"]
    assert run and run[-1].args["request_id"] == "inf-42"
    # explicit args win over the ambient id
    obs.enable_tracing()
    with obs.request_scope("outer"):
        with obs.trace_span("explicit", args={"request_id": "inner"}):
            pass
    assert obs.get_tracer().snapshot()[-1].args["request_id"] == "inner"


def test_request_scope_nests_and_is_per_thread():
    obs.enable_tracing()
    with obs.request_scope("a"):
        with obs.request_scope("b"):
            assert obs.current_request_id() == "b"
        assert obs.current_request_id() == "a"
        ids = []
        th = threading.Thread(
            target=lambda: ids.append(obs.current_request_id()))
        th.start()
        th.join()
        assert ids == [None]           # scopes don't leak across threads


# ---------------------------------------------------------------------------
# watchdog + flight recorder
# ---------------------------------------------------------------------------

def test_watchdog_stalled_engine_flight_record(tiny_engine_params,
                                               tmp_path):
    """Acceptance: an engine with admitted-but-undriven work trips the
    watchdog within the threshold; the record has stacks, spans, and a
    metrics snapshot; one record per stall episode."""
    reg = obs.MetricsRegistry()
    eng = _make_engine(tiny_engine_params, slots=1)
    eng.metrics.unregister()
    eng.metrics = pt.serving.EngineMetrics(registry=reg)  # isolated
    obs.enable_tracing()
    with obs.trace_span("pre_stall_marker"):
        pass
    eng.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=4)
    # ... and never step(): queued work, zero progress — a stall
    obs.disable_tracing()

    base = str(tmp_path / "flight")
    wd = obs.Watchdog(stall_threshold=0.2, poll_interval=0.05,
                      base_dir=base, max_records=3, registry=reg)
    wd.start()
    t0 = time.monotonic()
    deadline = t0 + 10.0
    recorder = wd.recorder
    while not recorder.records() and time.monotonic() < deadline:
        time.sleep(0.02)
    records = recorder.records()
    assert records, "watchdog produced no flight record"
    assert time.monotonic() - t0 < 10.0

    d = records[0]
    assert sorted(os.listdir(d)) == ["meta.json", "metrics.json",
                                     "spans.json", "stacks.txt"]
    stacks = open(os.path.join(d, "stacks.txt")).read()
    assert "pt-watchdog" in stacks and "MainThread" in stacks
    spans = json.load(open(os.path.join(d, "spans.json")))
    assert any(e.get("name") == "pre_stall_marker"
               for e in spans["traceEvents"])
    metrics = json.load(open(os.path.join(d, "metrics.json")))
    assert "serving_queue_depth" in metrics
    meta = json.load(open(os.path.join(d, "meta.json")))
    assert meta["reason"] == "stall"
    key = f"engine:{eng.metrics.engine_label}"
    assert key in meta["details"]["stalled"]
    assert meta["details"]["stalled"][key]["age_s"] >= 0.2

    # one dump per stall episode: still stalled, but no second record
    time.sleep(0.5)
    assert len(recorder.records()) == 1
    # the dump counter went through the watchdog's registry
    rows = reg.snapshot()["watchdog_dumps_total"]["series"]
    assert [(r["labels"], r["value"]) for r in rows] == \
        [({"reason": "stall"}, 1)]
    wd.stop()
    assert not wd.running
    eng.close()


def test_watchdog_ignores_idle_engine(tiny_engine_params, tmp_path):
    """No work admitted -> never a stall, however long the silence."""
    reg = obs.MetricsRegistry()
    eng = _make_engine(tiny_engine_params, slots=1)
    eng.metrics.unregister()
    eng.metrics = pt.serving.EngineMetrics(registry=reg)
    wd = obs.Watchdog(stall_threshold=0.05, poll_interval=0.02,
                      base_dir=str(tmp_path / "f"), registry=reg)
    wd.start()
    time.sleep(0.3)
    wd.stop()
    assert wd.recorder.records() == []
    eng.close()


def test_executor_heartbeat_visible_during_first_run(monkeypatch):
    """A hang in the very FIRST Executor.run must already be visible to
    the monitor: both series exist (runs=0, inflight=1) before the run
    body executes, and a raising run leaves inflight at 0 without
    counting as progress."""
    from paddle_tpu.observability import metrics as metrics_mod
    reg = obs.MetricsRegistry()
    monkeypatch.setattr(metrics_mod, "_GLOBAL", reg)
    exe = pt.Executor()
    observed = {}

    def wedged_impl(*a, **kw):
        mon = obs.ProgressMonitor(reg)
        observed.update(mon.observe().get("executor") or {})
        raise RuntimeError("wedged on device")

    monkeypatch.setattr(exe, "_run_impl", wedged_impl)
    with pytest.raises(RuntimeError, match="wedged"):
        exe.run(pt.Program())
    assert observed["busy"] is True and observed["value"] == 0
    snap = reg.snapshot()
    assert snap["executor_inflight_runs"]["series"][0]["value"] == 0
    assert snap["executor_runs_total"]["series"][0]["value"] == 0


def test_flight_recorder_shared_dir_keeps_other_writers(tmp_path):
    """Retention is per-recorder: a flapping recorder bounded at 2 must
    not evict another writer's record in the same base_dir."""
    base = str(tmp_path / "shared")
    theirs = obs.FlightRecorder(base, max_records=2).dump("stall")
    mine = obs.FlightRecorder(base, max_records=2)
    for i in range(5):
        mine.dump("overload", {"i": i})
    survivors = mine.records()
    assert theirs in survivors           # evidence preserved
    assert len(survivors) == 3           # their 1 + my newest 2


def test_progress_monitor_executor_inflight_stall():
    """A run stuck on-device: inflight > 0, runs_total frozen."""
    reg = obs.MetricsRegistry()
    reg.counter("executor_runs_total").inc(5)
    reg.gauge("executor_inflight_runs").set(1)
    t = [100.0]
    mon = obs.ProgressMonitor(reg, clock=lambda: t[0])
    first = mon.observe()["executor"]
    assert first["busy"] and first["age_s"] == 0.0
    t[0] = 130.0
    assert "executor" in mon.stalled(30.0)
    # progress re-arms: counter moves, age resets
    reg.counter("executor_runs_total").inc()
    t[0] = 131.0
    assert mon.stalled(30.0) == {}
    # idle executor never stalls even when frozen
    reg.gauge("executor_inflight_runs").set(0)
    t[0] = 500.0
    assert mon.stalled(30.0) == {}


def test_watchdog_retries_dump_after_write_failure(tmp_path, monkeypatch):
    """A failed flight-record write (disk full) must not permanently
    swallow the stall episode — the next poll retries."""
    reg = obs.MetricsRegistry()
    reg.counter("serving_decode_steps_total").labels(engine="z")  # = 0
    reg.gauge("serving_queue_depth").labels(engine="z").set(1)    # busy
    wd = obs.Watchdog(stall_threshold=0.01, poll_interval=60,
                      base_dir=str(tmp_path / "f"), registry=reg)
    wd._monitor.observe()                # baseline observation
    time.sleep(0.05)
    orig_dump, calls = wd.recorder.dump, []

    def flaky_dump(reason, details=None):
        calls.append(reason)
        if len(calls) == 1:
            raise OSError("disk full")
        return orig_dump(reason, details)

    monkeypatch.setattr(wd.recorder, "dump", flaky_dump)
    with pytest.raises(OSError):
        wd.check()                       # first attempt fails ...
    path = wd.check()                    # ... and is retried, not lost
    assert path is not None and os.path.isdir(path)
    assert calls == ["stall", "stall"]
    assert wd.check() is None            # episode now marked dumped


def test_flight_recorder_manual_dump_and_retention(tmp_path):
    base = str(tmp_path / "fl")
    rec = obs.FlightRecorder(base, max_records=2)
    paths = [rec.dump("manual", {"i": i}) for i in range(4)]
    assert len(set(paths)) == 4          # same-second dumps get suffixes
    kept = rec.records()
    assert len(kept) == 2                # bounded retention
    assert kept == sorted(paths[-2:])    # newest survive
    meta = json.load(open(os.path.join(kept[-1], "meta.json")))
    assert meta["reason"] == "manual" and meta["details"] == {"i": 3}
    # module-level convenience drives the same dump path (its own
    # recorder, default retention)
    p = obs.dump_flight_record("incident", base_dir=base)
    assert os.path.isdir(p) and p in rec.records()
    assert json.load(open(os.path.join(p, "meta.json")))["reason"] == \
        "incident"


def test_overload_shed_triggers_flight_record(tiny_engine_params,
                                              tmp_path):
    eng = _make_engine(tiny_engine_params, slots=1, max_queue=1)
    base = str(tmp_path / "ovl")
    wd = obs.start_watchdog(stall_threshold=600, base_dir=base,
                            dump_on_overload=True, overload_cooldown=600)
    eng.submit(np.asarray([1, 2], np.int32), max_new_tokens=2)  # fills q
    for _ in range(2):
        with pytest.raises(pt.serving.EngineOverloadError):
            eng.submit(np.asarray([3, 4], np.int32), max_new_tokens=2)
    # the dump happens on the WATCHDOG thread (the shedding submit must
    # not pay for it); it is woken promptly rather than next poll
    deadline = time.monotonic() + 10.0
    while not wd.recorder.records() and time.monotonic() < deadline:
        time.sleep(0.02)
    time.sleep(0.2)                      # would catch an (unwanted) 2nd
    records = wd.recorder.records()
    assert len(records) == 1             # cooldown: one record, not two
    meta = json.load(open(os.path.join(records[0], "meta.json")))
    assert meta["reason"] == "overload"
    assert meta["details"]["engine"] == eng.stats()["engine_label"]
    obs.stop_watchdog()
    # with no watchdog installed, shedding is hook-free and still raises
    with pytest.raises(pt.serving.EngineOverloadError):
        eng.submit(np.asarray([5, 6], np.int32), max_new_tokens=2)
    eng.run_until_drained()
    eng.close()


def test_healthz_reports_stall_with_503(tiny_engine_params):
    reg = obs.MetricsRegistry()
    eng = _make_engine(tiny_engine_params, slots=1)
    eng.metrics.unregister()
    eng.metrics = pt.serving.EngineMetrics(registry=reg)
    eng.submit(np.asarray([1, 2], np.int32), max_new_tokens=2)  # undriven
    server = obs.DebugServer(port=0, registry=reg)
    try:
        key = f"engine:{eng.metrics.engine_label}"
        h1 = _get_json(server.port, "/healthz")    # baseline observation
        assert h1["progress"][key]["busy"] is True
        time.sleep(0.25)
        status, _, body = _get(server.port, "/healthz?stall_threshold=0.2")
        assert status == 503
        doc = json.loads(body)
        assert doc["status"] == "stalled" and key in doc["stalled"]
        assert doc["progress"][key]["age_s"] >= 0.2
        # drive it: progress clears the stall
        eng.run_until_drained()
        doc = _get_json(server.port, "/healthz?stall_threshold=0.2")
        assert doc["status"] == "ok"
    finally:
        server.stop()
        eng.close()


# ---------------------------------------------------------------------------
# wiring: create_engine(debug_port=) / close()
# ---------------------------------------------------------------------------

def test_create_engine_debug_port_plumb_through(tiny_engine_params,
                                                tmp_path):
    cfg, params = tiny_engine_params
    import paddle_tpu.inference as inference
    model_dir = str(tmp_path / "model")
    with pt.unique_name_guard():
        from paddle_tpu.models.gpt import gpt_lm_program
        main, startup, fetches = gpt_lm_program(cfg, 8, is_test=True)
    exe = pt.Executor()
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup)
        pt.io.save_inference_model(model_dir, ["tokens"],
                                   [fetches["logits"]], exe,
                                   main_program=main)
    eng = inference.create_engine(
        model_dir, cfg,
        serving=pt.serving.ServingConfig(num_slots=1, prefill_buckets=(4,),
                                         max_len=16),
        debug_port=0)
    try:
        assert eng.debug_port and eng.debug_port > 0
        assert _get_json(eng.debug_port, "/healthz")["status"] == "ok"
        out = eng.generate([np.asarray([1, 2, 3], np.int32)],
                           max_new_tokens=2)
        assert out[0].shape == (5,)
    finally:
        eng.close()
    # close() released the last reference: the server is down
    assert obs.get_debug_server() is None
    with pytest.raises((ConnectionRefusedError, OSError)):
        _get(eng.debug_port, "/healthz")

    # rolling replacement: two engines share the server by refcount —
    # closing the FIRST must not kill diagnostics under the second
    mk = lambda: inference.create_engine(
        model_dir, cfg,
        serving=pt.serving.ServingConfig(num_slots=1,
                                         prefill_buckets=(4,),
                                         max_len=16),
        debug_port=0)
    eng_a = mk()
    eng_b = mk()
    assert eng_b.debug_port == eng_a.debug_port
    eng_a.close()
    assert _get_json(eng_b.debug_port, "/healthz")["status"] == "ok"
    # a failing server start must not leak the already-built engine's
    # registry series

    def labels():
        snap = obs.get_registry().snapshot()
        return {s["labels"]["engine"] for s in
                snap["serving_submitted_total"]["series"]}
    before = labels()
    with pytest.raises(RuntimeError, match="already bound"):
        inference.create_engine(
            model_dir, cfg,
            serving=pt.serving.ServingConfig(num_slots=1,
                                             prefill_buckets=(4,),
                                             max_len=16),
            debug_port=eng_b.debug_port + 1)
    assert labels() == before            # failed create left no ghosts
    eng_b.close()                        # last reference: server stops
    assert obs.get_debug_server() is None
    # an operator-started server holds a standing ref engines never drop
    port = obs.start_debug_server(port=0)
    eng_c = mk()
    eng_c.close()
    assert _get_json(port, "/healthz")["status"] == "ok"
    obs.stop_debug_server()
    # ... including when the operator JOINS an engine-started server
    eng_d = mk()
    assert obs.start_debug_server(port=0) == eng_d.debug_port
    eng_d.close()
    assert _get_json(eng_d.debug_port, "/healthz")["status"] == "ok"
    obs.stop_debug_server()
    # a stale release (engine outlives a force-stop + restart) must not
    # steal the new server's reference
    eng_e = mk()
    obs.stop_debug_server()
    port2 = obs.start_debug_server(port=0)
    eng_e.close()                        # token from the dead generation
    assert _get_json(port2, "/healthz")["status"] == "ok"
    obs.stop_debug_server()


# ---------------------------------------------------------------------------
# disabled path stays the PR-2 no-op (acceptance)
# ---------------------------------------------------------------------------

def test_disabled_hot_path_is_noop_singleton(tiny_engine_params):
    """Tracer off, no debug server: a full serving run records nothing
    and stamps no queue-wait anchor, and the ring-only entry points
    (Tracer.span, request_scope: the per-token and per-request spans)
    return THE shared no-op singleton — nothing allocated per token."""
    from paddle_tpu.observability.tracer import _NULL_SPAN
    assert obs.get_debug_server() is None and obs.get_watchdog() is None
    tracer = obs.get_tracer()
    assert tracer.span("x") is _NULL_SPAN
    assert obs.request_scope("rid") is _NULL_SPAN

    eng = _make_engine(tiny_engine_params, slots=2)
    rng = np.random.RandomState(1)
    reqs = [eng.submit(rng.randint(0, 97, (4,)).astype(np.int32),
                       max_new_tokens=3) for _ in range(4)]
    eng.run_until_drained()
    assert all(r.finished for r in reqs)
    assert tracer.span_count == 0 and tracer.dropped == 0
    # request ids are still minted (cheap string), but the queue-wait
    # clock anchor is never stamped when tracing is off
    assert all(r.request_id is not None for r in reqs)
    assert all(r._submit_ns is None for r in reqs)
    eng.close()


if __name__ == "__main__":
    import sys
    sys.exit(pytest.main([__file__, "-x", "-q"]))
