"""Profiling/benchmark tooling: timeline exporter + op microbench
(reference: tools/timeline.py, operators/benchmark/op_tester.cc)."""

import json
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

import paddle_tpu as pt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _has_xprof() -> bool:
    try:
        import xprof  # noqa: F401
        return True
    except ImportError:
        return False


def test_timeline_export_chrome_trace():
    pytest.importorskip(
        "xprof",
        reason="xprof not installed — tools/timeline.py converts "
        "jax.profiler xplane captures with xprof's trace_viewer; "
        "without it the CLI exits 2 with a remediation hint "
        "(covered by test_timeline_cli_without_xprof)")
    prof_dir = tempfile.mkdtemp()
    main, startup = pt.Program(), pt.Program()
    with pt.unique_name_guard(), pt.program_guard(main, startup):
        x = pt.layers.data("x", [64])
        y = pt.layers.fc(x, 64, act="relu")
        loss = pt.layers.reduce_mean(y)
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        with pt.profiler.profiler(profile_path=prof_dir):
            for _ in range(2):
                exe.run(main,
                        feed={"x": np.random.rand(8, 64).astype("f")},
                        fetch_list=[loss])
    out = os.path.join(prof_dir, "timeline.json")
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import timeline
    timeline.convert(prof_dir, out)
    d = json.load(open(out))
    ev = d["traceEvents"] if isinstance(d, dict) else d
    assert len(ev) > 10


@pytest.mark.skipif(_has_xprof(), reason="xprof installed — the "
                    "ImportError degradation path cannot trigger")
def test_timeline_cli_without_xprof(tmp_path):
    """Satellite: tools/timeline.py and tools/profile_summary.py exit 2
    with a remediation hint when xprof is missing — never a raw
    ImportError traceback."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for cli in ("tools/timeline.py", "tools/profile_summary.py"):
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, cli),
             "--profile_path", str(tmp_path)],
            capture_output=True, text=True, timeout=120, env=env)
        assert r.returncode == 2, (cli, r.returncode, r.stderr)
        assert "xprof is not importable" in r.stderr, (cli, r.stderr)
        assert "pip install xprof" in r.stderr
        assert "Traceback" not in r.stderr, (cli, r.stderr)


@pytest.mark.parametrize("tool", ["bench_latent_decode",
                                  "bench_grouped_decode", "bench_ssd_step"])
def test_a_decode_kernels_bench_measures_on_a_chip_or_not_at_all(tool):
    """tools/bench_latent_decode.py, tools/bench_grouped_decode.py and
    tools/bench_ssd_step.py time a device kernel: on the CPU they exit 1
    and print no number, they do not fall back to the interpreter."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, f"tools/{tool}.py")],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 1, (r.returncode, r.stderr)
    assert "a chip is required" in r.stderr and not r.stdout, (r.stdout,
                                                              r.stderr)


def test_the_state_space_bench_rehearses_its_program_on_the_cpu():
    """`tools/bench_ssd_step.py --tiny`: the kernel interpreted and XLA's form,
    each `y` against the float64 contraction of the state it wrote, the
    kernel's three throw-away forms built, the chunked scan against the
    token-by-token one, and NO time."""
    import json
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/bench_ssd_step.py"),
         "--tiny"], capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert set(out["y_vs_float64"]) == {"xla", "kernel"}
    assert all(v < 1e-6 for v in out["y_vs_float64"].values())
    assert isinstance(out["state_equals_xla"], bool)
    assert out["hold"]["y"] < 1e-4 and out["hold"]["state"] < 1e-4
    assert all(v["layer_us"] is None for v in out["step"].values())
    assert all(out["step"]["kernel"][k] is None for k in (
        "as_served", "no_y", "no_broadcasts", "arithmetic_alone"))
    assert all(p["layer_us"] is None for p in out["prefill"])


def test_op_bench_single_op():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import op_bench
    ms, nbytes = op_bench.bench_op("relu", {"X": (64, 64)}, steps=3)
    assert ms > 0
    assert nbytes == 64 * 64 * 4


def test_op_bench_cli():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/op_bench.py"),
         "softmax", "X:32x64"],
        capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr
    assert "softmax" in r.stdout

def test_profile_summary_aggregation():
    """tools/profile_summary.summarize over a synthetic hlo_stats table
    (the xprof schema): time-weighted averages and bound-by grouping."""
    import tools.profile_summary as ps

    cols = ["Rank", "HLO op category", "Total self time (us)",
            "Model GFLOP/s", "Measured memory BW (GiB/s)", "Bound by"]
    def row(cat, t, gf, bw, bound):
        vals = [0, cat, t, gf, bw, bound]
        return {"c": [{"v": v} for v in vals]}
    stats = {"cols": [{"label": c} for c in cols],
             "rows": [row("convolution fusion", 3000, 100000, 400, "Compute"),
                      row("convolution fusion", 1000, 20000, 800, "HBM"),
                      row("loop fusion", 1000, 500, 750, "HBM"),
                      row("zero", 0, 0, 0, "HBM")]}
    out = ps.summarize(stats, steps=2, top=5)
    assert abs(out["total_ms_per_step"] - 2.5) < 1e-9
    rows = {(r["category"], r["bound_by"]): r for r in out["rows"]}
    conv = rows[("convolution fusion", "Compute")]
    assert abs(conv["ms_per_step"] - 1.5) < 1e-9
    assert abs(conv["pct"] - 60.0) < 1e-9
    assert abs(conv["avg_tflops"] - 100.0) < 1e-9
    hbm = rows[("convolution fusion", "HBM")]
    assert abs(hbm["avg_hbm_gibs"] - 800.0) < 1e-9
    assert ("zero", "HBM") not in rows  # zero-time rows dropped


def test_profiler_stop_without_start_is_noop():
    """stop_profiler with no trace active returns None instead of
    raising (serving PR satellite: safe teardown paths)."""
    assert pt.profiler.stop_profiler() is None
    assert pt.profiler.stop_profiler() is None        # idempotent


def test_profiler_context_double_stop_safe():
    """A body that already stopped the trace (or raised after a stop)
    must not blow up the profiler() exit path."""
    prof_dir = tempfile.mkdtemp()
    with pt.profiler.profiler(profile_path=prof_dir):
        assert pt.profiler.stop_profiler() == prof_dir
    # exception inside the body after a double-stop: the ORIGINAL error
    # propagates, not a RuntimeError from the exit path
    with pytest.raises(ValueError, match="boom"):
        with pt.profiler.profiler(profile_path=prof_dir):
            pt.profiler.stop_profiler()
            raise ValueError("boom")
    # the profiler still works after the aborted sessions
    with pt.profiler.profiler(profile_path=prof_dir):
        pass
    assert pt.profiler.stop_profiler() is None


def test_bench_serving_row_shape():
    """tools/bench_serving emits one JSON row per (model, concurrency,
    decode_chunk) with throughput/TTFT/TPOT + registry-sourced dispatch
    amortization (same style as bench_inference)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import bench_serving
    rows = bench_serving.run_model("tiny", concurrencies=[1, 2],
                                   requests_per_level=3, max_new=4,
                                   decode_chunks=(1, 4))
    assert len(rows) == 4                        # 2 cc x 2 chunk levels
    for row in rows:
        assert row["metric"].startswith("tiny_serving_c")
        assert row["value"] > 0                  # tokens/s
        assert row["unit"] == "tokens/s"
        for k in ("mean_ttft_ms", "mean_tpot_ms", "completed",
                  "compiled_executables"):
            assert k in row["extra"], row
        assert row["extra"]["completed"] == 3
        # registry-sourced percentiles ride along (observability PR)
        for k in ("p50_ttft_ms", "p99_ttft_ms", "p50_tpot_ms",
                  "p99_tpot_ms"):
            assert row["extra"][k] is not None and row["extra"][k] > 0, row
        # dispatch-amortization columns (decode fast path): registry-
        # sourced dispatch count, bounded by the chunk factor
        chunk = row["extra"]["decode_chunk"]
        assert row["metric"].endswith(f"_k{chunk}")
        assert row["extra"]["dispatches"] > 0
        assert row["extra"]["dispatches_per_token"] <= 1.0 / chunk + 1e-9
        assert row["extra"]["tokens_per_dispatch"] >= chunk - 1e-9
        # paged-pool columns (paged KV PR): registry-sourced block
        # occupancy under load + arena-normalized throughput
        assert row["extra"]["blocks_used"] > 0
        assert row["extra"]["blocks_total"] > 0
        assert row["extra"]["tokens_per_s_per_gb"] > 0
        assert "prefix_hit_rate" in row["extra"]
        # measured tracer overhead rides along (diagnostics PR): the
        # traced re-run really ran (throughput > 0) and the delta is a
        # finite percentage
        assert row["extra"]["tokens_per_s_traced"] > 0
        assert isinstance(row["extra"]["trace_overhead_pct"], float)
        # host/device dispatch split (SLO/lifecycle PR): registry-
        # sourced mean launch-side host ms per dispatch — the native-
        # core baseline column — plus the device wait next to it
        assert row["extra"]["host_overhead_ms"] is not None
        assert row["extra"]["host_overhead_ms"] > 0
        assert row["extra"]["device_ms_per_dispatch"] is not None
        # performance-attribution columns (tick-profiler PR): per-
        # phase engine-host ms from serving_tick_phase_seconds, and
        # the compile journal's FLOP-utilization proxy
        phases = row["extra"]["tick_phase_ms"]
        assert isinstance(phases, dict) and phases, row
        assert set(phases) <= {"admit", "prefill_chunk", "launch",
                               "collect", "stream", "bookkeeping"}
        assert all(v >= 0 for v in phases.values())
        assert phases["launch"] > 0          # dispatches really ticked
        # ... which exists only over a published or stated peak, and
        # the CPU has neither
        assert row["extra"]["mfu_proxy"] is None
    # the traced re-run restored the disabled production default
    import paddle_tpu.observability as obs
    assert not obs.tracing_enabled()


def test_bench_serving_shared_prefix_row():
    """tools/bench_serving --shared-prefix: one row comparing the
    prefix-cache-off cold baseline against the warm run over one long
    system prompt — hit rate > 0, shared blocks < cold blocks, and both
    TTFT cuts present (paged KV PR acceptance row)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import bench_serving
    rows = bench_serving.run_shared_prefix("tiny", requests=4, max_new=4,
                                           concurrency=4)
    assert len(rows) == 1
    row = rows[0]
    assert row["metric"] == "tiny_serving_shared_prefix_c4"
    assert row["value"] > 0 and row["unit"] == "tokens/s"
    e = row["extra"]
    # the warm run really shared: registry-sourced hit rate, and the
    # shared mapping held fewer arena blocks than the cold run
    assert e["prefix_hit_rate"] is not None and e["prefix_hit_rate"] > 0
    assert 0 < e["blocks_used"] < e["blocks_used_cold"]
    assert e["mean_ttft_ms_cold"] > 0 and e["mean_ttft_ms_warm"] > 0
    assert isinstance(e["ttft_speedup"], float)
    assert e["tokens_per_s_per_gb"] > 0 and e["tokens_per_s_cold"] > 0


def test_bench_serving_speculate_row_shape():
    """tools/bench_serving --speculate: one row per speculate_k over
    the repetitive-text workload with registry-sourced acceptance
    columns — the K=0 baseline prints None in the spec columns, the
    K>0 row shows >1 accepted token per verify pass (the raw
    tokens-per-model-pass win the speculative chunk loop exists for)
    while the dispatch-amortization bound holds."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import bench_serving
    rows = bench_serving.run_speculate("tiny", speculate_ks=(0, 4),
                                       requests=4, concurrency=2)
    assert len(rows) == 2
    for row, k in zip(rows, (0, 4)):
        assert row["metric"] == f"tiny_serving_spec_c2_s{k}"
        assert row["value"] > 0 and row["unit"] == "tokens/s"
        e = row["extra"]
        assert e["speculate_k"] == k
        assert e["completed"] == 4
        assert e["dispatches"] > 0
        assert e["dispatches_per_token"] <= 1.0 / 8 + 1e-9
        assert e["compiled_executables"] > 0
        assert e["mean_ttft_ms"] > 0 and e["mean_tpot_ms"] > 0
    base, spec = rows[0]["extra"], rows[1]["extra"]
    assert base["spec_proposed"] == 0 and base["spec_accepted"] == 0
    assert base["spec_accept_rate"] is None
    assert base["accepted_per_pass"] is None
    # the speculative row really drafted AND accepted: >1 token commits
    # per verify pass on repetitive text (the acceptance criterion)
    assert spec["spec_proposed"] > 0
    assert 0 < spec["spec_accepted"] <= spec["spec_proposed"]
    assert 0 < spec["spec_accept_rate"] <= 1
    assert spec["accepted_per_pass"] > 1.0, spec
    assert spec["dispatches"] <= base["dispatches"]


def test_bench_serving_oversubscribe_row_shape():
    """tools/bench_serving --oversubscribe: one row over the workload
    whose page demand exceeds the deliberately undersized arena, with
    registry-sourced fault-tolerance columns — preemptions really
    happened, every swap-out got a matching latency sample, every
    request still finished its full budget, and the arena drained to
    zero blocks (the no-leaked-pages acceptance pin, bench-visible)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import bench_serving
    rows = bench_serving.run_oversubscribe("tiny", requests=6,
                                           concurrency=4)
    assert len(rows) == 1
    row = rows[0]
    assert row["metric"] == "tiny_serving_oversub_c4"
    assert row["value"] > 0 and row["unit"] == "tokens/s"
    e = row["extra"]
    assert e["completed"] == 6
    assert e["oversubscription"] > 1.0          # demand really > arena
    assert e["worst_case_blocks"] > e["kv_blocks"]
    assert e["preemptions"] >= 1                # pressure really evicted
    assert e["swap_ins"] == e["preemptions"]    # every victim resumed
    assert e["swapped_now"] == 0
    assert e["swap_in_ms"] is not None and e["swap_in_ms"] > 0
    assert e["swap_out_ms"] is not None and e["swap_out_ms"] > 0
    assert e["blocks_used_after_drain"] == 0    # no leaked pages
    assert 0 < e["blocks_used_peak"] <= e["blocks_total"]


def test_bench_serving_mixed_row_shape():
    """tools/bench_serving --mixed: two rows (chunking off, then on)
    over the long-prompt + short-decode workload — the off row shows
    zero chunk dispatches, the on row shows the long prompt really
    split (registry-sourced prefill_chunks), both carry the
    p99_tpot_ms / long_ttft_ms columns, the on row carries the
    improvement ratios, and the streams were asserted bit-identical
    inside the workload itself (streams_identical pinned True)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import bench_serving
    rows = bench_serving.run_mixed("tiny", requests=2, short_max_new=8)
    assert len(rows) == 2                  # chunking off, then on
    off, on = rows
    assert off["metric"] == "tiny_serving_mixed_chunk0"
    assert on["metric"].startswith("tiny_serving_mixed_chunk")
    assert on["metric"] != off["metric"]
    for row in rows:
        assert row["value"] > 0 and row["unit"] == "tokens/s"
        e = row["extra"]
        assert e["p99_tpot_ms"] is not None and e["p99_tpot_ms"] > 0
        assert e["long_ttft_ms"] > 0
        assert e["streams_identical"] is True
        assert e["compiled_executables"] > 0
    # the off row ran monolithic (no chunk dispatches, no chunk
    # latency samples); the on row really split the long prompt
    assert off["extra"]["prefill_chunk"] is None
    assert off["extra"]["prefill_chunks"] == 0
    assert off["extra"]["prefill_chunk_ms"] is None
    assert on["extra"]["prefill_chunk"] >= 1
    assert on["extra"]["prefill_chunks"] >= 4   # the long prompt alone
    assert on["extra"]["prefill_chunk_ms"] > 0
    assert on["extra"]["p99_tpot_improvement"] is not None
    assert on["extra"]["long_ttft_ratio"] is not None


def test_bench_serving_debug_port_flag(capsys, monkeypatch):
    """--debug-port serves the diagnostics plane for the bench run and
    tears it down afterwards."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import bench_serving
    import paddle_tpu.observability as obs

    gpt_kwargs, _, prompt_lens, buckets = bench_serving.MODELS["tiny"]
    monkeypatch.setitem(bench_serving.MODELS, "tiny",
                        (gpt_kwargs, [1], prompt_lens, buckets))
    monkeypatch.setenv("BENCH_SERVING_REQUESTS", "2")
    bench_serving.main(["tiny", "--debug-port", "0"])
    out = capsys.readouterr()
    assert "debug server: http://127.0.0.1:" in out.err
    rows = [json.loads(line) for line in out.out.strip().splitlines()]
    assert rows and all("trace_overhead_pct" in r["extra"] for r in rows)
    assert obs.get_debug_server() is None    # stopped on exit


def test_bench_serving_http_row_shape():
    """tools/bench_serving --http: one wire-path row per concurrency
    with client-measured end-to-end TTFT/TPOT next to the same
    registry-sourced engine columns the library rows carry."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import bench_serving
    rows = bench_serving.run_http("tiny", concurrencies=[2],
                                  requests_per_level=3, max_new=4)
    assert len(rows) == 1
    row = rows[0]
    assert row["metric"] == "tiny_serving_http_c2"
    assert row["value"] > 0 and row["unit"] == "tokens/s"
    e = row["extra"]
    assert e["transport"] == "http"
    assert e["completed"] == 3
    # end-to-end wire cuts present and sane (wire TTFT includes the
    # engine-side TTFT plus HTTP/JSON/SSE overhead)
    assert e["e2e_mean_ttft_ms"] > 0
    assert e["e2e_p50_ttft_ms"] > 0
    assert e["e2e_mean_ttft_ms"] >= e["mean_ttft_ms"] * 0.5
    # registry-sourced engine columns preserved, same as library rows
    for k in ("mean_ttft_ms", "mean_tpot_ms", "p50_ttft_ms",
              "p99_ttft_ms", "dispatches", "blocks_total",
              "compiled_executables"):
        assert e[k] is not None, (k, e)
    assert e["server_requests_ok"] == 3
    # SLO/goodput plane (SLO/lifecycle PR): the bench runs under a
    # generous default SLO, so a healthy run attains 1.0 and every
    # delivered token is goodput
    assert e["slo_attainment"] == 1.0
    assert e["goodput_tokens_per_s"] is not None
    assert e["goodput_tokens_per_s"] > 0
    assert e["host_overhead_ms"] is not None and e["host_overhead_ms"] > 0
    # performance-attribution columns mirror the library rows
    phases = e["tick_phase_ms"]
    assert isinstance(phases, dict) and phases.get("launch", 0) > 0
    assert e["mfu_proxy"] is None   # no published peak for the CPU
    # the server was torn down: no leftover wire surface
    import paddle_tpu as pt
    snap = pt.observability.get_registry().snapshot()
    assert not snap.get("server_active_streams", {}).get("series")


def test_server_smoke_start_generate_drain():
    """Serving-service smoke on an ephemeral port: start -> one SSE
    generate -> graceful drain/shutdown, engine + router registry
    series retired afterwards."""
    import http.client
    import paddle_tpu as pt
    from paddle_tpu.models.gpt import GPTConfig, gpt_lm_program
    from paddle_tpu.models import gpt_decode as gd

    cfg = GPTConfig(vocab_size=97, hidden=32, layers=2, heads=4,
                    max_pos=64, dropout=0.0, attn_impl="xla")
    main_prog, startup, _ = gpt_lm_program(cfg, 8, is_test=True)
    exe = pt.Executor()
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup)
        params = gd.collect_gpt_params(scope, cfg)
    server = pt.server.serve(
        params, cfg,
        pt.server.ServerConfig(
            port=0, serving=pt.serving.ServingConfig(
                num_slots=2, prefill_buckets=(4, 8), max_len=32)))
    try:
        assert server.port > 0
        eng_label = server.router.replicas[0].engine.metrics.engine_label
        router_label = server.router.metrics.label
        conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                          timeout=60)
        conn.request("POST", "/v1/generate",
                     json.dumps({"prompt": [5, 7, 11],
                                 "max_new_tokens": 4}),
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        assert r.status == 200
        body = r.read().decode()
        conn.close()
        assert body.count("data: ") == 5       # 4 tokens + done frame
        assert "event: done" in body
        assert '"finish_reason": "length"' in body
        conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                          timeout=30)
        conn.request("GET", "/healthz")
        health = json.loads(conn.getresponse().read())
        conn.close()
        assert health["status"] == "ok"
        assert health["replicas"][0]["engine"] == eng_label
    finally:
        server.shutdown()                      # drain -> close engines
    snap = pt.observability.get_registry().snapshot()
    for family, label_key, label in (
            ("serving_submitted_total", "engine", eng_label),
            ("server_active_streams", "router", router_label),
            ("server_requests_total", "router", router_label)):
        rows = snap.get(family, {}).get("series", [])
        assert not any(s["labels"].get(label_key) == label
                       for s in rows), (family, rows)


def test_trace_summary_cli_smoke():
    """tools/trace_summary.py over a trace written by the observability
    exporter: top-N self-time table prints, JSON mode parses."""
    import paddle_tpu.observability as obs
    obs.enable_tracing()
    obs.get_tracer().clear()
    with obs.trace_span("alpha"):
        with obs.trace_span("beta"):
            pass
    obs.disable_tracing()
    path = os.path.join(tempfile.mkdtemp(), "trace.json")
    obs.export_chrome_trace(path)
    obs.get_tracer().clear()
    cli = os.path.join(REPO, "tools/trace_summary.py")
    r = subprocess.run([sys.executable, cli, path, "--top", "5"],
                       capture_output=True, text=True, timeout=120,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr
    assert "alpha" in r.stdout and "beta" in r.stdout
    assert "self_ms" in r.stdout
    r = subprocess.run([sys.executable, cli, path, "--json"],
                       capture_output=True, text=True, timeout=120,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr
    rows = json.loads(r.stdout)
    assert {row["name"] for row in rows} == {"alpha", "beta"}


def test_trace_summary_cli_absent_and_empty_files(tmp_path):
    """Satellite: a missing, empty, or non-JSON trace exits with a
    helpful message (status 2), never a traceback."""
    cli = os.path.join(REPO, "tools/trace_summary.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}

    def run(path):
        return subprocess.run([sys.executable, cli, path],
                              capture_output=True, text=True, timeout=120,
                              env=env)

    r = run(str(tmp_path / "nope.json"))
    assert r.returncode == 2
    assert "cannot read" in r.stderr and "Traceback" not in r.stderr

    empty = tmp_path / "empty.json"
    empty.write_text("")
    r = run(str(empty))
    assert r.returncode == 2
    assert "is empty" in r.stderr and "enable_tracing" in r.stderr
    assert "Traceback" not in r.stderr

    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    r = run(str(bad))
    assert r.returncode == 2
    assert "not chrome-trace JSON" in r.stderr
    assert "Traceback" not in r.stderr

    # a valid trace with zero complete events still exits 0
    ok = tmp_path / "ok.json"
    ok.write_text(json.dumps({"traceEvents": []}))
    r = run(str(ok))
    assert r.returncode == 0
    assert "no complete" in r.stdout
    # --json on the same file prints a parseable empty array
    r = subprocess.run([sys.executable, cli, str(ok), "--json"],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 0 and json.loads(r.stdout) == []


def test_train_summary_cli_smoke(tmp_path):
    """tools/train_summary.py over a StepLogger JSONL: annotated step
    table prints (SPIKE + RECOMPILE + NAN markers), JSON mode parses,
    and a missing/empty/garbage log exits 2 with a hint."""
    from paddle_tpu.observability.train_stats import StepLogger

    logger = StepLogger(log_dir=str(tmp_path), run_name="run")
    for i in range(4):
        logger.log_step(loss=1.0 - 0.1 * i, grad_norm=0.5, lr=0.01,
                        step_time_s=0.02, examples=8)
    logger.event("recompile", cause="feed_shape",
                 detail={"var": "x", "from": [8, 4], "to": [16, 4]})
    logger.log_step(loss=50.0, grad_norm=90.0, lr=0.01,
                    step_time_s=0.02, examples=8)      # spike
    with pytest.warns(RuntimeWarning, match="non-finite"):
        logger.log_step(loss=float("nan"), grad_norm=float("nan"),
                        lr=0.01, finite=False, step_time_s=0.02,
                        examples=8)
    # a recompile journaled after the last step (crash signature) must
    # still surface, not silently drop
    logger.event("recompile", cause="program_version", detail={})
    logger.close()
    path = os.path.join(str(tmp_path), "run.jsonl")
    cli = os.path.join(REPO, "tools/train_summary.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, cli, path], capture_output=True,
                       text=True, timeout=120, env=env)
    assert r.returncode == 0, r.stderr
    assert "SPIKE" in r.stdout
    assert "RECOMPILE(feed_shape)" in r.stdout
    assert "RECOMPILE(program_version)" in r.stdout
    assert "NAN" in r.stdout
    assert ("6 steps, 1 non-finite, 2 recompile(s) "
            "(1 after the last step)") in r.stdout
    r = subprocess.run([sys.executable, cli, path, "--json"],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 0, r.stderr
    rows = json.loads(r.stdout)
    assert len(rows) == 7  # 6 steps + trailing-recompile row
    assert rows[4]["annotations"] == ["SPIKE", "RECOMPILE(feed_shape)"]
    assert rows[5]["annotations"] == ["NAN"]
    assert rows[6]["kind"] == "trailing"
    assert rows[6]["annotations"] == ["RECOMPILE(program_version)"]

    # degradation: absent / empty / non-JSONL exit 2 with remediation
    r = subprocess.run([sys.executable, cli, str(tmp_path / "no.jsonl")],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 2 and "cannot read" in r.stderr
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    r = subprocess.run([sys.executable, cli, str(empty)],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 2 and "install_step_logger" in r.stderr
    bad = tmp_path / "bad.jsonl"
    bad.write_text("{nope\n")
    r = subprocess.run([sys.executable, cli, str(bad)],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 2 and "not JSONL" in r.stderr
    assert "Traceback" not in r.stderr


def test_serving_summary_reconstructs_preempt_and_failover(tmp_path):
    """Acceptance: a seeded run with the request log enabled — one
    workload preempted under an over-subscribed arena, one failed over
    after a replica death — reconstructs full phase timelines via
    tools/serving_summary.py: the summary table carries PREEMPT and
    FAILOVER annotations, --request-id prints the phase-by-phase
    timeline (queued -> admitted -> prefill -> preempted -> swapped_in
    -> decode -> finished), and failover chains merge into ONE
    request row."""
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu.models.gpt import GPTConfig, gpt_lm_program
    from paddle_tpu.models import gpt_decode as gd
    from paddle_tpu.observability.request_log import (
        RequestLog, install_request_log, uninstall_request_log)
    from paddle_tpu.server import Router, SLOConfig
    from paddle_tpu.serving import (FaultPlan, ServingConfig,
                                    ServingEngine)

    cfg = GPTConfig(vocab_size=97, hidden=32, layers=2, heads=4,
                    max_pos=64, dropout=0.0, attn_impl="xla")
    main_prog, startup, _ = gpt_lm_program(cfg, 8, is_test=True)
    exe = pt.Executor()
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup)
        params = gd.collect_gpt_params(scope, cfg)

    log = install_request_log(RequestLog(log_dir=str(tmp_path)))
    try:
        # part 1 (seeded): an over-subscribed arena forces preemption
        eng = ServingEngine(params, cfg, ServingConfig(
            num_slots=3, max_queue=16, prefill_buckets=(4, 8),
            max_len=24, block_size=4, kv_blocks=10, preempt=True))
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, cfg.vocab_size, (6,))
                   .astype(np.int32) for _ in range(3)]
        outs = eng.generate(prompts, max_new_tokens=12,
                            temperature=0.5, seed=7)
        assert eng.stats()["preemptions"] >= 1
        assert all(len(o) == 18 for o in outs)
        eng.close()
        # part 2: a replica that dies at step 0 fails its stream over
        faulty = ServingEngine(params, cfg, ServingConfig(
            num_slots=2, prefill_buckets=(4, 8), max_len=32,
            fault_plan=FaultPlan(step_exceptions={0})))
        healthy = ServingEngine(params, cfg, ServingConfig(
            num_slots=2, prefill_buckets=(4, 8), max_len=32))
        router = Router([faulty, healthy],
                        default_slo=SLOConfig(e2e_s=120.0))
        router.start()
        h = router.submit(np.asarray([3, 1, 4], np.int32), 6)
        tokens, reason = h.result(timeout=60)
        assert reason == "length" and h.retries == 1
        failover_root = None
        for e in log.recent():
            if e["kind"] == "failover":
                failover_root = e["request_id"]
        assert failover_root is not None
        router.close(drain=False)
    finally:
        uninstall_request_log()

    log_path = str(tmp_path / "serving.jsonl")
    cli = os.path.join(REPO, "tools/serving_summary.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, cli, log_path],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 0, r.stderr
    assert "PREEMPT" in r.stdout and "FAILOVER" in r.stdout
    assert "1 preempted" in r.stdout or "preempted" in r.stdout
    # JSON mode: the preempted request's row carries its phase cuts and
    # the failover chain merged into one row (original id as root)
    r = subprocess.run([sys.executable, cli, log_path, "--json"],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 0, r.stderr
    rows = {row["request_id"]: row for row in json.loads(r.stdout)}
    pre = next(row for row in rows.values()
               if "PREEMPT" in row["annotations"])
    assert pre["reason"] == "length" and pre["tokens"] == 12
    assert pre["queue_ms"] is not None and pre["total_ms"] > 0
    assert pre["dispatches"] >= 1 and pre["preemptions"] >= 1
    fo = rows[failover_root]
    assert "FAILOVER" in fo["annotations"]
    assert len(fo["chain"]) == 2               # stranded id + retried id
    assert fo["tokens"] == 6
    # --request-id: the full phase timeline, preemption inline
    r = subprocess.run([sys.executable, cli, log_path,
                        "--request-id", pre["request_id"]],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 0, r.stderr
    order = [line.split()[3] for line in r.stdout.splitlines()
             if line.strip().startswith("+")]
    for a, b in (("queued", "admitted"), ("admitted", "prefill"),
                 ("prefill", "preempted"), ("preempted", "swapped_in"),
                 ("swapped_in", "finished")):
        assert order.index(a) < order.index(b), (a, b, order)

    # degradation: absent / empty / non-JSONL exit 2 with remediation
    # (the shared summary_io convention)
    r = subprocess.run([sys.executable, cli,
                        str(tmp_path / "nope.jsonl")],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 2 and "cannot read" in r.stderr
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    r = subprocess.run([sys.executable, cli, str(empty)],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 2 and "install_request_log" in r.stderr
    bad = tmp_path / "bad.jsonl"
    bad.write_text("{nope\n")
    r = subprocess.run([sys.executable, cli, str(bad)],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 2 and "not JSONL" in r.stderr
    assert "Traceback" not in r.stderr


def _tiny_profiled_engine():
    """A tick_profile=True tiny engine that has served a small mix —
    the source for the perf-attribution CLI tests."""
    import paddle_tpu as pt
    from paddle_tpu.models.gpt import GPTConfig, gpt_lm_program
    from paddle_tpu.models import gpt_decode as gd

    cfg = GPTConfig(vocab_size=97, hidden=32, layers=2, heads=4,
                    max_pos=64, dropout=0.0, attn_impl="xla")
    main_prog, startup, _ = gpt_lm_program(cfg, 8, is_test=True)
    exe = pt.Executor()
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup)
        params = gd.collect_gpt_params(scope, cfg)
    eng = pt.serving.ServingEngine(
        params, cfg, pt.serving.ServingConfig(
            num_slots=2, max_queue=16, prefill_buckets=(4, 8),
            max_len=32, tick_profile=True))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (3 + i % 5,))
               .astype(np.int32) for i in range(6)]
    eng.generate(prompts, max_new_tokens=4)
    return eng


def test_perf_summary_and_check_metrics_clis(tmp_path):
    """tools/perf_summary renders the compile-journal attribution table
    (+ the --ticks phase table) from saved /compilez + /tickz payloads,
    and tools/check_metrics lints a live registry dump clean — both
    degrade to exit 2 on unreadable input, 1 on findings (the
    summary-CLI convention)."""
    import paddle_tpu as pt

    eng = _tiny_profiled_engine()
    label = eng.stats()["engine_label"]
    compilez = tmp_path / "compilez.json"
    compilez.write_text(json.dumps(
        {"engines": {label: eng._compile_snapshot()}}))
    tickz = tmp_path / "tickz.json"
    tickz.write_text(json.dumps(
        {"engines": {label: eng._tick_records()}}))
    regdump = tmp_path / "registry.json"
    regdump.write_text(pt.observability.get_registry().to_json())
    eng.close()

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    perf = os.path.join(REPO, "tools/perf_summary.py")
    r = subprocess.run([sys.executable, perf, str(compilez),
                        "--ticks", str(tickz)],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 0, r.stderr
    assert "decode_chunk" in r.stdout and "prefill:L" in r.stdout
    assert "mfu_proxy=" in r.stdout and "tick phases" in r.stdout
    assert "launch" in r.stdout
    r = subprocess.run([sys.executable, perf, str(compilez),
                        "--ticks", str(tickz), "--json"],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout)
    fams = out["engines"][label]["families"]
    assert fams["decode_chunk"]["calls"] >= 1
    phases = out["tick_phases"]
    assert phases["ticks"] >= 1
    assert sum(p["share"] for p in phases["phases"]) == \
        pytest.approx(1.0, abs=1e-6)
    # degradation: absent file exits 2 with a remediation hint
    r = subprocess.run([sys.executable, perf,
                        str(tmp_path / "nope.json")],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 2 and "cannot read" in r.stderr
    assert "Traceback" not in r.stderr

    check = os.path.join(REPO, "tools/check_metrics.py")
    r = subprocess.run([sys.executable, check, str(regdump)],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 0, r.stderr + r.stdout
    assert "clean" in r.stdout
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({
        "foo": {"type": "counter", "help": "no _total"},
        "bar_seconds": {"type": "histogram", "help": ""}}))
    r = subprocess.run([sys.executable, check, str(bad)],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 1
    assert "must end in _total" in r.stdout
    assert "help text is required" in r.stdout
    r = subprocess.run([sys.executable, check,
                        str(tmp_path / "nope.json")],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 2 and "cannot read" in r.stderr


def test_serving_summary_phases_footer(tmp_path):
    """tools/serving_summary --phases joins the tick flight ring
    against the request log via the monotonic stamps both sides carry:
    the footer splits per-phase time into serving (ticks inside a
    request window) vs other, and --json wraps rows + attribution."""
    import paddle_tpu as pt
    from paddle_tpu.models.gpt import GPTConfig, gpt_lm_program
    from paddle_tpu.models import gpt_decode as gd
    from paddle_tpu.observability.request_log import (
        RequestLog, install_request_log, uninstall_request_log)

    cfg = GPTConfig(vocab_size=97, hidden=32, layers=2, heads=4,
                    max_pos=64, dropout=0.0, attn_impl="xla")
    main_prog, startup, _ = gpt_lm_program(cfg, 8, is_test=True)
    exe = pt.Executor()
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup)
        params = gd.collect_gpt_params(scope, cfg)
    install_request_log(RequestLog(log_dir=str(tmp_path)))
    try:
        eng = pt.serving.ServingEngine(
            params, cfg, pt.serving.ServingConfig(
                num_slots=2, max_queue=16, prefill_buckets=(4, 8),
                max_len=32, tick_profile=True))
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, cfg.vocab_size, (4 + i,))
                   .astype(np.int32) for i in range(3)]
        eng.generate(prompts, max_new_tokens=4)
        label = eng.stats()["engine_label"]
        ticks = eng._tick_records()
        eng.close()
    finally:
        uninstall_request_log()
    log_path = str(tmp_path / "serving.jsonl")
    tickz = tmp_path / "tickz.json"
    tickz.write_text(json.dumps({"engines": {label: ticks}}))

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    cli = os.path.join(REPO, "tools/serving_summary.py")
    r = subprocess.run([sys.executable, cli, log_path,
                        "--phases", str(tickz)],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 0, r.stderr
    assert "-- tick phases" in r.stdout
    assert "launch" in r.stdout and "serving_ms" in r.stdout
    r = subprocess.run([sys.executable, cli, log_path,
                        "--phases", str(tickz), "--json"],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout)
    assert len(out["requests"]) == 3
    attr = out["tick_phases"]
    assert attr["ticks"] == len(ticks)
    # the serving engine really ticked inside request windows
    assert attr["in_request_windows"] >= 1
    assert attr["serving"].get("launch", 0) > 0
    # without --phases the bare-array row shape is preserved
    r = subprocess.run([sys.executable, cli, log_path, "--json"],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 0 and isinstance(json.loads(r.stdout), list)
    # a phases file with no usable records exits 2 with remediation
    empty = tmp_path / "empty_ticks.json"
    empty.write_text("[]")
    r = subprocess.run([sys.executable, cli, log_path,
                        "--phases", str(empty)],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 2 and "tick_profile" in r.stderr


def test_api_freeze_spec_is_current():
    """Satellite: the API-freeze check runs inside the suite — the live
    public surface (including this PR's observability additions) must
    match tools/API.spec signature for signature. In-process (no
    subprocess) so the diff shows up directly in the failure."""
    import importlib
    import tools.print_signatures as ps
    importlib.reload(ps)      # sys.path games by other tests: stay fresh

    current = sorted(ps.iter_api())
    spec = os.path.join(REPO, "tools", "API.spec")
    with open(spec) as f:
        frozen = sorted(line.rstrip("\n") for line in f if line.strip())
    added = sorted(set(current) - set(frozen))
    removed = sorted(set(frozen) - set(current))
    assert current == frozen, (
        "public API drifted from tools/API.spec — regenerate deliberately "
        "with `python tools/print_signatures.py > tools/API.spec`.\n"
        f"added: {added[:20]}\nremoved: {removed[:20]}")
    # the diagnostics surface is part of the frozen API
    assert any("start_debug_server" in line for line in frozen)
    assert any("dump_flight_record" in line for line in frozen)


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-x", "-q"]))


def test_bench_serving_rebalance_row_shape():
    """tools/bench_serving --rebalance: one row over the skewed-
    admission workload with registry-sourced migration columns — the
    rebalancer-on run really migrated (and the off run registered
    ZERO migrations), every migration got a latency sample, the hot
    replica's tail columns are present both ways, and the streams were
    asserted bit-identical inside the workload itself."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import bench_serving
    rows = bench_serving.run_rebalance("tiny", requests=6)
    assert len(rows) == 1
    row = rows[0]
    assert row["metric"] == "tiny_serving_rebalance_r2"
    assert row["value"] > 0 and row["unit"] == "tokens/s"
    e = row["extra"]
    assert e["requests"] == 6 and e["replicas"] == 2
    assert e["migrations"] >= 1                 # the rebalancer fired
    assert e["migrations_off"] == 0             # baseline stayed put
    assert e["migration_ms"] is not None and e["migration_ms"] > 0
    assert e["migration_failures"] == 0
    assert e["p99_tpot_ms_on"] is not None
    assert e["p99_tpot_ms_off"] is not None
    assert e["p99_ttft_ms_on"] is not None
    assert e["p99_ttft_ms_off"] is not None
    assert e["tokens_per_s_off"] > 0
    # both routers were torn down: no leftover migration series
    snap = pt.observability.get_registry().snapshot()
    assert not snap.get("server_migrations_total", {}).get("series")


def test_bench_serving_mesh_row_shape():
    """tools/bench_serving --mesh: one row per tensor-parallel mesh
    size with the mesh_shape / hbm_per_chip_gb columns — per-chip KV
    bytes must drop by exactly 1/tp against the mesh-1 row (the
    serve-a-bigger-model win as a printed number), streams asserted
    identical inside the workload itself (streams_identical pinned
    True on every row)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import bench_serving
    rows = bench_serving.run_mesh("tiny", meshes=(1, 2), requests=3,
                                  max_new=4)
    assert len(rows) == 2                        # one row per mesh size
    by_tp = {}
    for row in rows:
        e = row["extra"]
        tp = e["mesh_shape"][0]
        assert row["metric"] == f"tiny_serving_mesh{tp}"
        assert row["value"] > 0 and row["unit"] == "tokens/s"
        assert e["completed"] == 3
        assert e["hbm_per_chip_gb"] > 0
        assert e["pool_bytes"] > 0
        assert e["streams_identical"] is True
        assert e["compiled_executables"] > 0
        assert e["dispatches"] > 0
        by_tp[tp] = e
    # the capacity win, measured: per-chip bytes halve EXACTLY at tp=2
    # while the logical arena (pool_bytes, blocks) stays identical —
    # pinned on the raw bytes column (the GB column is display-rounded)
    assert by_tp[1]["pool_bytes"] == by_tp[2]["pool_bytes"]
    assert by_tp[1]["hbm_per_chip_bytes"] == by_tp[1]["pool_bytes"]
    assert by_tp[2]["hbm_per_chip_bytes"] * 2 == by_tp[2]["pool_bytes"]


def test_serving_summary_stitches_migration_hops(tmp_path):
    """tools/serving_summary renders a migrated request as ONE
    timeline: the migrate_in's rerouted_from link joins the source and
    target engine ids through the same union-find failover chains use,
    the row carries a MIGRATE annotation + migration count, and the
    footer counts migrated requests."""
    import paddle_tpu as pt
    from paddle_tpu.models.gpt import GPTConfig, gpt_lm_program
    from paddle_tpu.models import gpt_decode as gd
    from paddle_tpu.observability.request_log import (
        RequestLog, install_request_log, uninstall_request_log)
    from paddle_tpu.serving import ServingConfig, ServingEngine

    cfg = GPTConfig(vocab_size=97, hidden=32, layers=2, heads=4,
                    max_pos=64, dropout=0.0, attn_impl="xla")
    main_prog, startup, _ = gpt_lm_program(cfg, 8, is_test=True)
    exe = pt.Executor()
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup)
        params = gd.collect_gpt_params(scope, cfg)

    def make():
        return ServingEngine(params, cfg, ServingConfig(
            num_slots=2, prefill_buckets=(4, 8), max_len=48,
            decode_chunk=4))

    log = install_request_log(RequestLog(log_dir=str(tmp_path)))
    try:
        src, dst = make(), make()
        req = src.submit(np.asarray([3, 1, 4], np.int32), 30)
        while len(req.tokens) < 2:
            src.step()
        ticket = src.migrate_out(req)
        req2 = dst.migrate_in(ticket)
        src.run_until_drained()
        dst.run_until_drained()
        assert req2.state == "finished"
        src.close()
        dst.close()
        source_rid, target_rid = req.request_id, req2.request_id
    finally:
        uninstall_request_log()

    log_path = str(tmp_path / "serving.jsonl")
    cli = os.path.join(REPO, "tools/serving_summary.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, cli, log_path, "--json"],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 0, r.stderr
    rows = json.loads(r.stdout)
    row = next(rw for rw in rows if rw["request_id"] == source_rid)
    assert row["chain"] == [source_rid, target_rid]   # one timeline
    assert "MIGRATE" in row["annotations"]
    assert "FAILOVER" not in row["annotations"]       # hop, not failure
    assert "PREEMPT" not in row["annotations"]        # handoff, not
    assert row["preemptions"] == 0                    # page pressure
    assert row["migrations"] == 1
    assert row["tokens"] == 30
    # table mode: annotation inline + migrated count in the footer
    r = subprocess.run([sys.executable, cli, log_path],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 0, r.stderr
    assert "MIGRATE" in r.stdout
    assert "1 migrated" in r.stdout
    # --request-id on EITHER id prints the stitched event timeline
    r = subprocess.run([sys.executable, cli, log_path,
                        "--request-id", target_rid],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 0, r.stderr
    order = [line.split()[3] for line in r.stdout.splitlines()
             if line.strip().startswith("+")]
    assert order.index("migrate_out") < order.index("migrate_in") \
        < order.index("finished")


def test_bench_serving_quantize_row_shape():
    """tools/bench_serving --quantize: one row per quantization mode
    (fp32 / int8-w / int8-w+int8-kv) with the kv_dtype/weight_dtype,
    tokens_per_s_per_gb, greedy_token_agreement, and max_logit_delta
    columns — keys, shapes and counts only: greedy agreement >=0.99,
    the logit-delta budget met, streams asserted deterministic per row
    inside the workload itself, compile count still
    O(buckets)+admit+1 chunk loop on every mode, and the capacity win
    on the deterministic BYTES columns. No timing: the tokens/s-per-GB
    ratio it once pinned was a CPU wall-clock ratio over about 10 ms,
    unsound under six workers (PERF.md, section 7)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import bench_serving
    rows = bench_serving.run_quantize("tiny", requests=6, max_new=16)
    assert len(rows) == 3                        # one row per mode
    by_mode = {}
    for row in rows:
        e = row["extra"]
        mode = row["metric"].split("_quant_")[1]
        assert mode in ("fp32", "int8w", "int8w_int8kv")
        assert row["value"] > 0 and row["unit"] == "tokens/s"
        assert e["completed"] == 6
        assert e["tokens_per_s_per_gb"] > 0
        assert e["streams_deterministic"] is True
        # the pinned budget: TEACHER-FORCED per-token argmax agreement
        # along the fp32 trajectory (kernel fidelity, not free-running
        # trajectory sensitivity — that lands in stream_agreement)
        assert e["greedy_token_agreement"] >= 0.99
        assert 0 < e["stream_agreement"] <= 1.0
        # per-token logit-delta budget along the fp32 trajectory: the
        # tiny model's measured delta is ~2.6e-3; 0.05 is the pinned
        # ceiling with an order of magnitude of headroom before a
        # numerics regression would go unnoticed
        assert e["max_logit_delta"] <= 0.05
        # compile discipline unchanged by quantization: 2 buckets +
        # chunk loop + admit sampler
        assert e["compiled_executables"] <= 2 + 2
        by_mode[mode] = e
    assert by_mode["fp32"]["kv_dtype"] == "float32"
    assert by_mode["fp32"]["weight_dtype"] == "float32"
    assert by_mode["fp32"]["greedy_token_agreement"] == 1.0
    assert by_mode["fp32"]["max_logit_delta"] == 0.0
    assert by_mode["int8w"]["weight_dtype"] == "int8"
    assert by_mode["int8w"]["kv_dtype"] == "float32"
    assert by_mode["int8w_int8kv"]["kv_dtype"] == "int8"
    # the capacity win, measured on the deterministic BYTES columns:
    # int8 weights shrink >=2x, the int8 arena (data + f32 scale
    # plane) shrinks >=2.5x vs the fp32 pool
    assert by_mode["int8w"]["weight_bytes"] * 2 \
        <= by_mode["fp32"]["weight_bytes"]
    assert by_mode["int8w"]["pool_bytes"] == by_mode["fp32"]["pool_bytes"]
    assert by_mode["int8w_int8kv"]["pool_bytes"] * 2.5 \
        <= by_mode["fp32"]["pool_bytes"]
    # every mode served the same work: the same tokens out of the same
    # six requests (what the tokens/s columns divide by)
    assert len({e["completed"] for e in by_mode.values()}) == 1
    assert set(by_mode) == {"fp32", "int8w", "int8w_int8kv"}


def test_bench_serving_adapters_row_shape():
    """tools/bench_serving --adapters: one row per pool population
    (1 vs N adapters co-batched) with the registry-sourced pool
    columns. Determinism (fresh-engine re-run) and isolation (each
    co-batched request vs a dedicated single-adapter engine) are
    asserted INSIDE the workload, so this pin runs it small and checks
    the row shape: n_adapters / adapters_resident / adapter_uploads /
    adapter_evictions / adapter_pool_bytes, the constant-pool-bytes
    invariant (uploads are value updates at fixed shape), and compile
    count still O(buckets)+admit+1 with adapters in the batch."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import bench_serving
    rows = bench_serving.run_adapters("tiny", n_adapters=3, requests=6,
                                      max_new=16)
    assert len(rows) == 2                 # 1-adapter vs N-adapter rows
    by_pop = {}
    for row in rows:
        e = row["extra"]
        n = int(row["metric"].rsplit("_", 1)[1])
        assert row["value"] > 0 and row["unit"] == "tokens/s"
        assert e["completed"] == 6
        assert e["n_adapters"] == n
        assert e["adapters_resident"] == n
        assert e["adapter_uploads"] == n
        assert e["adapter_evictions"] == 0
        assert e["adapter_pool_bytes"] > 0
        assert e["streams_deterministic"] is True
        # compile discipline unchanged by the adapter pool: 2 buckets
        # + chunk loop + admit sampler
        assert e["compiled_executables"] <= 2 + 2
        by_pop[n] = e
    assert set(by_pop) == {1, 3}
    # the pool is fixed-shape: residency varies, bytes do not
    assert by_pop[1]["adapter_pool_bytes"] \
        == by_pop[3]["adapter_pool_bytes"]
    # isolation was really asserted on the co-batched row
    assert by_pop[3]["streams_isolated"] is True


# ---------------------------------------------------------------------------
# bench regression gate (tools/bench_gate.py) + bench_serving --json
# ---------------------------------------------------------------------------

def _gate_artifact(tmp_path, name, rows):
    p = tmp_path / name
    p.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    return str(p)


def test_bench_gate_pass_and_regression_paths(tmp_path, capsys):
    """tools/bench_gate compares bench artifacts: exit 0 when every
    gated metric is within threshold, 1 on a regression (direction
    inferred from the metric name: throughput regresses down, latency
    up), explicit --metric thresholds override, and multiple baselines
    average."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import bench_gate
    base = _gate_artifact(tmp_path, "base.json", [
        {"metric": "tiny_serving_c4_k8", "value": 100.0,
         "unit": "tokens/s"},
        {"metric": "mean_ttft_ms", "value": 50.0}])
    good = _gate_artifact(tmp_path, "good.json", [
        {"metric": "tiny_serving_c4_k8", "value": 97.0},
        {"metric": "mean_ttft_ms", "value": 52.0}])
    bad = _gate_artifact(tmp_path, "bad.json", [
        {"metric": "tiny_serving_c4_k8", "value": 70.0},
        {"metric": "mean_ttft_ms", "value": 49.0}])

    assert bench_gate.main([base, good]) == 0
    out = capsys.readouterr().out
    assert "within threshold" in out

    # 30% throughput drop breaches the default -10% gate; the ttft
    # IMPROVEMENT is not flagged (direction heuristic)
    assert bench_gate.main([base, bad]) == 1
    cap = capsys.readouterr()
    assert "REGRESSION" in cap.out and "tiny_serving_c4_k8" in cap.out
    assert cap.out.count("REGRESSION") == 1
    assert "1 regression(s)" in cap.err

    # explicit threshold: a 3% drop breaches -1%
    assert bench_gate.main(
        [base, good, "--metric", "tiny_serving_c4_k8:-1%"]) == 1
    capsys.readouterr()
    # a named metric absent from the artifacts is itself a finding
    assert bench_gate.main([base, good, "--metric", "nope"]) == 1
    assert "nope: - -> - [-10%] missing" in capsys.readouterr().out
    # multiple baselines average: mean(100, 70) = 85 vs 97 passes
    assert bench_gate.main([base, bad, good]) == 0
    capsys.readouterr()
    # disjoint metric sets never pass by vacuity
    other = _gate_artifact(tmp_path, "other.json",
                           [{"metric": "zzz", "value": 1.0}])
    assert bench_gate.main([base, other]) == 1
    assert "no shared metrics" in capsys.readouterr().err


def test_bench_gate_wrapper_shape_and_exit_2(tmp_path):
    """The BENCH_* runner wrapper compares by exit code (run_rc), and
    unreadable/one-artifact inputs exit 2 with a remediation hint, no
    traceback (the summary_io convention) — pinned over the wire like
    the other summary CLIs."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    gate = os.path.join(REPO, "tools/bench_gate.py")
    ok_run = tmp_path / "BENCH_r01.json"
    ok_run.write_text(json.dumps(
        {"n": 1, "cmd": ["pytest"], "rc": 0, "tail": "all passed"},
        indent=2))
    bad_run = tmp_path / "BENCH_r02.json"
    bad_run.write_text(json.dumps(
        {"n": 2, "cmd": ["pytest"], "rc": 1, "tail": "1 failed"},
        indent=2))
    r = subprocess.run([sys.executable, gate, str(ok_run),
                        str(ok_run)], capture_output=True, text=True,
                       timeout=120, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "run_rc" in r.stdout
    r = subprocess.run([sys.executable, gate, str(ok_run),
                        str(bad_run)], capture_output=True, text=True,
                       timeout=120, env=env)
    assert r.returncode == 1
    assert "run_rc" in r.stdout and "REGRESSION" in r.stdout
    # unreadable candidate: exit 2 + hint
    r = subprocess.run([sys.executable, gate, str(ok_run),
                        str(tmp_path / "nope.json")],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 2
    assert "cannot read" in r.stderr and "Traceback" not in r.stderr
    # a single artifact cannot gate anything
    r = subprocess.run([sys.executable, gate, str(ok_run)],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 2 and "at least two" in r.stderr
    # malformed threshold spec
    r = subprocess.run([sys.executable, gate, str(ok_run),
                        str(bad_run), "--metric", "run_rc:5%"],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 2 and "bad threshold" in r.stderr


def test_bench_serving_json_artifact_feeds_bench_gate(
        tmp_path, capsys, monkeypatch):
    """--json OUT writes the stdout rows as a JSONL artifact whose
    shape bench_gate loads directly — the perf-CI loop (bench twice,
    gate the second run against the first) closes in-process."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import bench_gate
    import bench_serving
    gpt_kwargs, _, prompt_lens, buckets = bench_serving.MODELS["tiny"]
    monkeypatch.setitem(bench_serving.MODELS, "tiny",
                        (gpt_kwargs, [1], prompt_lens, buckets))
    monkeypatch.setenv("BENCH_SERVING_REQUESTS", "2")
    out = tmp_path / "PERF_run.json"
    bench_serving.main(["tiny", "--decode-chunk", "8",
                        "--json", str(out)])
    cap = capsys.readouterr()
    assert f"wrote 1 row(s) to {out}" in cap.err
    stdout_rows = [json.loads(ln)
                   for ln in cap.out.strip().splitlines()]
    artifact_rows = [json.loads(ln)
                     for ln in out.read_text().strip().splitlines()]
    assert artifact_rows == stdout_rows          # stdout-identical
    assert artifact_rows[0]["unit"] == "tokens/s"
    # the artifact gates against itself clean (zero drift)
    assert bench_gate.main([str(out), str(out)]) == 0
    assert "within threshold" in capsys.readouterr().out
