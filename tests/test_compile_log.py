"""The compile log (observability/compile_log.py): one record an executable
from jax's own events, the layers' tags on the right records, probes outside
every sum, `compile/*` spans nested where the thread was, and the pin: a warm
step and a warm tick never reach the log."""

import json
import os
import subprocess
import sys
import threading
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import observability as obs
from paddle_tpu.observability.compile_log import PHASES, CompileLog
from paddle_tpu.utils.compile_cache import ensure_compile_cache

LOG = obs.compile_log()


@pytest.fixture(autouse=True)
def fresh_log():
    LOG.install()       # a test before this one may have cleared jax's lists
    LOG.clear()
    yield
    obs.disable_tracing()
    obs.get_tracer().clear()


def counted():
    return LOG.records(include_probes=False)


def by_name(name):
    return [r for r in LOG.records() if r["fun_name"] == name]


# -- one record an executable -------------------------------------------------

def test_jitted_function_leaves_one_record_and_a_second_call_none():
    @jax.jit
    def one_record(x):
        return jnp.tanh(x) * 3.0

    one_record(jnp.ones((4, 4)))
    mine = by_name("one_record")
    assert len(mine) == 1
    r = mine[0]
    assert r["trace_s"] > 0 and r["lower_s"] > 0 and r["backend_compile_s"] > 0
    assert r["cache_load_s"] == 0.0 and r["cache"] in ("off", "miss")
    assert r["seconds"] == pytest.approx(sum(r[p + "_s"] for p in PHASES))
    assert [s[0] for s in r["spans"]] == ["compile/trace", "compile/lower",
                                          "compile/backend"]
    assert all(b <= e for _, b, e in r["spans"])
    assert r["thread"] == threading.current_thread().name
    assert not r["probe"] and not r["nested"] and r["tag"] is None
    other = jnp.ones((4, 4)) + 1.0              # (an executable of its own)
    n, calls = len(LOG.records()), LOG.calls
    one_record(jnp.ones((4, 4)))
    one_record(other)
    assert len(by_name("one_record")) == 1 and LOG.calls == calls
    assert len(LOG.records()) == n


def test_a_new_shape_is_a_new_executable():
    @jax.jit
    def two_shapes(x):
        return x + 1

    two_shapes(jnp.ones(3))
    two_shapes(jnp.ones(5))
    assert len(by_name("two_shapes")) == 2


def test_a_jit_inside_a_jit_is_the_outers_inner_trace():
    @jax.jit
    def inner_fn(x):
        return jnp.sin(x) * 2.0

    @jax.jit
    def outer_fn(x):
        return inner_fn(x) + inner_fn(x + 1.0).sum()

    before = len(counted())
    outer_fn(jnp.ones((8,)))
    assert not by_name("inner_fn")              # never a second executable
    outer, = by_name("outer_fn")
    assert outer["inner_by_name"]["inner_fn"][0] >= 1
    assert outer["inner_traces"] >= outer["inner_by_name"]["inner_fn"][0]
    # counted once: the inner seconds lie inside the outer trace's own
    assert 0 < outer["inner_trace_s"] <= outer["trace_s"]
    new = counted()[before:]
    assert sum(r["trace_s"] for r in new) == pytest.approx(
        LOG.snapshot()["totals"]["trace_s"]
        - sum(r["trace_s"] for r in counted()[:before]))


def test_an_executable_compiled_inside_a_trace_is_taken_out_of_it():
    @jax.jit
    def eager_inside(x):
        with jax.ensure_compile_time_eval():
            table = jnp.cumsum(jnp.ones((7,)))      # compiles, right here
        return x + table.sum()

    eager_inside(jnp.ones(()))
    outer, = by_name("eager_inside")
    nested = [r for r in LOG.records() if r["nested"]]
    assert nested and all(r["trace_s"] == 0.0 for r in nested)
    assert all(r["backend_compile_s"] > 0 for r in nested)
    begin, end = outer["spans"][0][1:]
    inside = sum(e - b for r in nested for _, b, e in r["spans"]
                 if begin <= b and e <= end) * 1e-9
    assert inside > 0
    assert outer["trace_s"] == pytest.approx((end - begin) * 1e-9 - inside,
                                             abs=1e-6)


def test_persistent_cache_hit_is_booked_as_load(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    keep = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()

        def build():
            @jax.jit
            def comes_back(x):
                return jnp.cos(x) @ x.T
            return comes_back

        build()(jnp.ones((16, 16)))
        first, = by_name("comes_back")
        if first["cache"] != "miss" or not any(tmp_path.iterdir()):
            pytest.skip("the CPU backend did not persist the executable: "
                        f"cache={first['cache']!r}")
        assert first["backend_compile_s"] > 0 and first["cache_load_s"] == 0
        jax.clear_caches()
        build()(jnp.ones((16, 16)))
        second = by_name("comes_back")[-1]
        assert second["cache"] == "hit"
        assert second["cache_load_s"] > 0 and second["backend_compile_s"] == 0
        # one name for one thing: the hit's whole backend phase
        name, begin, end = second["spans"][-1]
        assert name == "compile/cache_load"
        assert (end - begin) * 1e-9 == pytest.approx(second["cache_load_s"])
        totals = LOG.snapshot()["totals"]["cache"]
        assert totals["hit"] >= 1 and totals["miss"] >= 1
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_registry_counters_follow_the_records():
    reg = obs.get_registry()

    def series(family, label):
        fam = reg.snapshot().get(family, {"series": []})
        return {s["labels"][label]: s["value"] for s in fam["series"]}

    before_s = series("compile_phase_seconds_total", "phase")
    before_n = series("compile_cache_total", "result")

    @jax.jit
    def counted_fn(x):
        return x * x + 2

    counted_fn(jnp.ones(6))
    r, = by_name("counted_fn")
    after_s = series("compile_phase_seconds_total", "phase")
    after_n = series("compile_cache_total", "result")
    totals = LOG.snapshot()["totals"]
    for phase in ("trace", "lower", "backend_compile"):
        assert after_s[phase] - before_s.get(phase, 0.0) == pytest.approx(
            totals[phase + "_s"])
    assert after_n[r["cache"]] - before_n.get(r["cache"], 0) == \
        totals["executables"]


# -- installation ---------------------------------------------------------------

def test_installation_survives_cleared_listeners():
    assert LOG.installed()
    jax.monitoring.clear_event_listeners()
    assert not LOG.installed()

    @jax.jit
    def unheard(x):
        return x - 1

    unheard(jnp.ones(2))
    assert not by_name("unheard")
    LOG.install()
    LOG.install()                               # idempotent, by looking
    assert LOG.installed()
    from jax._src import monitoring
    assert monitoring.get_scalar_listeners().count(LOG._on_scalar) == 1

    @jax.jit
    def heard(x):
        return x - 2

    heard(jnp.ones(2))
    assert len(by_name("heard")) == 1


def test_the_packages_import_is_the_one_installation(monkeypatch, tmp_path):
    assert LOG.installed()                      # `import paddle_tpu` did it
    jax.monitoring.clear_event_listeners()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert ensure_compile_cache() == str(tmp_path)
    assert not LOG.installed()      # where the cache lives, and no more
    import paddle_tpu.utils.compile_cache as where
    assert "observability" not in open(where.__file__).read()


# The suite's own process has the persistent cache disabled (conftest.py), so
# what `import paddle_tpu` does to it is looked at in processes of their own:
# one that starts as a user's does, with the package in a checkout of its own
# (a link to this one under tmp_path), so that `<checkout>/.jax_cache` is
# nobody else's.

_AFTER_THE_IMPORT = """
import json, os
{before}
import paddle_tpu as pt
import jax
from jax._src import xla_bridge
print(json.dumps({{"package": os.path.dirname(pt.__file__),
                  "dir": jax.config.jax_compilation_cache_dir,
                  "backends": sorted(xla_bridge._backends)}}))
"""

_A_MAKER_BEFORE_ANY_ENGINE = """
import json, sys
import paddle_tpu as pt
import jax, jax.numpy as jnp
# as benchmarks/run.py does after its import: the quick programs persist too
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

@jax.jit
def make_weights(key):
    return jax.random.normal(key, (32, 32)) @ jnp.ones((32, 32))

# the key is an argument: one cache entry serves every seed
make_weights(jax.random.PRNGKey(int(sys.argv[1]))).block_until_ready()
log = pt.observability.compile_log()
print(json.dumps({"maker": [r["cache"] for r in log.records()
                            if r["fun_name"] == "make_weights"],
                  "cache": log.snapshot()["totals"]["cache"],
                  "dir": jax.config.jax_compilation_cache_dir}))
"""


def _in_a_process_of_its_own(tmp_path, code, *argv, env=()):
    checkout = tmp_path / "checkout"
    if not checkout.exists():
        checkout.mkdir()
        (checkout / "paddle_tpu").symlink_to(os.path.dirname(pt.__file__),
                                             target_is_directory=True)
    environ = {k: v for k, v in os.environ.items() if k not in (
        "JAX_ENABLE_COMPILATION_CACHE", "JAX_COMPILATION_CACHE_DIR")}
    environ.update(env, PYTHONPATH=str(checkout))
    done = subprocess.run([sys.executable, "-c", code, *argv], env=environ,
                          cwd=checkout, capture_output=True, text=True,
                          timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    return checkout, json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("whose", ["nobody", "caller", "environment"])
def test_the_import_configures_the_cache_directory(tmp_path, whose):
    named = str(tmp_path / "named")
    before = ('import jax; jax.config.update("jax_compilation_cache_dir", '
              f'{named!r})' if whose == "caller" else "")
    env = {"JAX_COMPILATION_CACHE_DIR": named} if whose == "environment" else {}
    checkout, said = _in_a_process_of_its_own(
        tmp_path, _AFTER_THE_IMPORT.format(before=before), env=env)
    assert said["package"] == str(checkout / "paddle_tpu")
    # a directory somebody named is kept; else where the package sits
    assert said["dir"] == (str(checkout / ".jax_cache") if whose == "nobody"
                           else named)
    # setting it touched no device and made nothing on disk
    assert said["backends"] == []
    assert not os.path.exists(named) and not (checkout / ".jax_cache").exists()


@pytest.mark.parametrize("whose", ["checkout", "environment"])
def test_a_maker_jitted_before_any_engine_goes_through_the_cache(tmp_path,
                                                                 whose):
    """What PR 58 moved: a function jitted right after the import, with no
    Executor and no engine yet, is a `miss` in a first process and a `hit`
    in a second, never `off` (with the directory set by the first engine it
    was compiled on every start and written nowhere: a `miss` every time)."""
    named = str(tmp_path / "named")
    env = {"JAX_COMPILATION_CACHE_DIR": named} if whose == "environment" else {}
    checkout, first = _in_a_process_of_its_own(
        tmp_path, _A_MAKER_BEFORE_ANY_ENGINE, "7", env=env)
    where = named if whose == "environment" else str(checkout / ".jax_cache")
    assert first["dir"] == where
    assert first["maker"] == ["miss"] and first["cache"]["off"] == 0
    assert any(name.startswith("jit_make_weights") for name in os.listdir(where))
    _, second = _in_a_process_of_its_own(
        tmp_path, _A_MAKER_BEFORE_ANY_ENGINE, "11", env=env)
    assert second["maker"] == ["hit"] and second["cache"]["off"] == 0
    assert second["cache"]["miss"] == 0


def test_import_is_a_phase_and_observability_stays_stdlib_only():
    phase, = [p for p in LOG.phases() if p["phase"] == "setup/import"]
    assert phase["seconds"] > 0
    assert phase["begin_ns"] == pt._IMPORT_BEGIN_NS
    # the module itself imports nothing but the standard library and its
    # two neighbours (jax is imported by install(), not before)
    import ast
    import sys
    mod = sys.modules["paddle_tpu.observability.compile_log"]
    tree = ast.parse(open(mod.__file__).read())
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    names = {a.name.split(".")[0] for n in top if isinstance(n, ast.Import)
             for a in n.names} | {n.module for n in top
                                  if isinstance(n, ast.ImportFrom)
                                  and n.level == 0}
    assert names <= {"__future__", "contextlib", "sys", "threading", "time",
                     "collections", "typing"}


def test_a_log_of_its_own_is_bounded():
    log = CompileLog(capacity=2).install()
    try:
        for n in (3, 4, 5):
            jax.jit(lambda x: x * 2)(jnp.ones(n))
        snap = log.snapshot()
        assert len(snap["executables"]) == 2 and snap["dropped"] >= 1
        assert log.snapshot(limit=1)["executables"] == snap["executables"][-1:]
        assert log.snapshot(limit=0)["executables"] == []
    finally:
        jax.monitoring.clear_event_listeners()
        LOG.install()


# -- the executor ---------------------------------------------------------------

def _tiny_program(width=4):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = pt.layers.data("x", [width])
        y = pt.layers.data("y", [1])
        h = pt.layers.fc(x, size=8, act="relu")
        loss = pt.layers.mean(pt.layers.square_error_cost(
            pt.layers.fc(h, size=1), y))
        pt.optimizer.SGD(1e-2).minimize(loss)
    return main, startup, loss


def _feed(batch=4, width=4):
    return {"x": np.ones((batch, width), np.float32),
            "y": np.zeros((batch, 1), np.float32)}


def test_executor_tags_the_program_and_the_cause():
    main, startup, loss = _tiny_program()
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        exe.run(main, feed=_feed(4), fetch_list=[loss])
        exe.run(main, feed=_feed(6), fetch_list=[loss])
    tag = f"program:{str(main._uid)[:8]}"
    mine = [r for r in counted() if r["tag"] == tag]
    assert [r["cause"] for r in mine] == ["first_compile", "feed_shape"]
    assert all(r["backend_compile_s"] > 0 for r in mine)
    start = [r for r in counted()
             if r["tag"] == f"program:{str(startup._uid)[:8]}"]
    assert len(start) == 1 and start[0]["cause"] == "first_compile"


@pytest.mark.parametrize("how", ["capture_hlo", "analyze_compile"])
def test_a_look_before_the_first_dispatch_is_the_programs_compile(
        how, tmp_path):
    # jax's own account of the backend, taken beside the log's
    heard = []

    def listen(event, seconds, **_):
        if event.endswith("backend_compile_duration"):
            heard.append(seconds)

    jax.monitoring.register_event_duration_secs_listener(listen)
    exe = pt.Executor()
    try:
        with pt.scope_guard(pt.Scope()):
            if how == "capture_hlo":
                main, startup, loss = _tiny_program()
                exe.run(startup)
                LOG.clear()
                del heard[:]
                exe.capture_hlo = True
                exe.run(main, feed=_feed(), fetch_list=[loss])
            else:
                with obs.step_logging(log_dir=str(tmp_path)):
                    main, startup, loss = _tiny_program()
                    exe.run(startup)
                    LOG.clear()
                    del heard[:]
                    exe.run(main, feed=_feed(), fetch_list=[loss])
    finally:
        jax.monitoring.clear_event_listeners()
        LOG.install()
    tag = f"program:{str(main._uid)[:8]}"
    mine = [r for r in LOG.records() if r["tag"] == tag]
    # the look (AOT lower + compile, before the call) made the executable
    # and the dispatch behind it found jax's trace and executable: the
    # program's one compile is counted, under its tag and cause
    made, = [r for r in mine if r["cache"] is not None]
    assert not made["probe"] and made["cause"] == "first_compile"
    assert made["backend_compile_s"] > 0
    assert [r["probe"] for r in mine if r is not made] == [False]   # a trace
    totals = LOG.totals()
    assert totals["executables"] >= 1 and totals["traces_alone"] >= 1
    probes = [r for r in LOG.records() if r["probe"]]
    assert totals["probes"] == len(probes)
    # the log's backend seconds are jax's, less what a probe repeated
    assert totals["backend_compile_s"] + totals["cache_load_s"] == \
        pytest.approx(sum(heard) - sum(r["backend_compile_s"]
                                       + r["cache_load_s"] for r in probes),
                      rel=0.05, abs=2e-3)
    counted_rows = LOG.records(include_probes=False)
    for phase in PHASES:
        assert totals[phase + "_s"] == pytest.approx(
            sum(r[phase + "_s"] for r in counted_rows))


def test_a_look_jax_compiles_again_behind_is_the_probe():
    @jax.jit
    def looked_at(x):
        return jnp.sin(x) + 3

    x = jnp.ones(5)
    with LOG.probing():
        looked_at.lower(x).compile()
    first, = by_name("looked_at")
    assert not first["probe"] and first["backend_compile_s"] > 0
    jax.clear_caches()          # jax forgets: the dispatch compiles again
    looked_at(x)
    first, again = by_name("looked_at")
    assert first["probe"] and not again["probe"]
    assert again["backend_compile_s"] > 0
    assert LOG.totals()["probes"] == 1
    # a second look at what the thread just looked at stays a probe
    with LOG.probing():
        looked_at.lower(x).compile()
    assert [r["probe"] for r in by_name("looked_at")][2:] in ([], [True])


def test_a_lowering_alone_behind_the_dispatch_stays_a_probe():
    @jax.jit
    def costed(x):
        return x * 3 - 1

    costed(jnp.ones(7))
    with LOG.probing():
        costed.lower(jax.ShapeDtypeStruct((7,), jnp.float32)).cost_analysis()
    real, probe = by_name("costed")
    assert not real["probe"] and probe["probe"] and probe["cache"] is None
    totals = LOG.totals()
    assert totals["probes"] == 1
    assert totals["lower_s"] == pytest.approx(
        sum(r["lower_s"] for r in counted()))      # the probe's is not in


def test_compile_spans_nest_inside_executor_dispatch():
    main, startup, loss = _tiny_program()
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        obs.get_tracer().clear()
        obs.enable_tracing()
        exe.run(main, feed=_feed(), fetch_list=[loss])
        obs.disable_tracing()
    spans = obs.get_tracer().snapshot()
    tid = threading.get_ident()
    dispatch, = [s for s in spans if s.name == "executor/dispatch"]
    compile_span, = [s for s in spans if s.name == "executor/compile"]
    tag = f"program:{str(main._uid)[:8]}"
    for name in ("compile/trace", "compile/lower", "compile/backend"):
        mine = [s for s in spans if s.name == name and s.args.get("tag") == tag]
        assert len(mine) == 1, name
        s = mine[0]
        assert s.tid == dispatch.tid == tid and s.depth > dispatch.depth
        assert dispatch.ts_us <= s.ts_us
        assert s.ts_us + s.dur_us <= dispatch.ts_us + dispatch.dur_us + 1
        # executor/compile is the Program lowered to a callable: over
        # before jax traced anything
        assert compile_span.ts_us + compile_span.dur_us <= s.ts_us
    assert [s for s in spans if s.name == "compile/backend"
            and s.args.get("tag") == tag][0].args["cache"] in ("off", "miss")


def test_ten_warm_executor_steps_never_reach_the_log():
    main, startup, loss = _tiny_program()
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        for _ in range(2):
            exe.run(main, feed=_feed(), fetch_list=[loss])
        obs.get_tracer().clear()
        obs.enable_tracing()
        calls, records = LOG.calls, len(LOG.records())
        for _ in range(10):
            exe.run(main, feed=_feed(), fetch_list=[loss])
        assert LOG.calls == calls and len(LOG.records()) == records
    spans = obs.get_tracer().snapshot()
    assert sum(s.name == "executor/dispatch" for s in spans) == 10
    assert not [s for s in spans if s.name.startswith("compile/")]


# -- the serving engine -------------------------------------------------------------

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


def _engine(tiny, **kw):
    cfg, params = tiny
    return pt.serving.ServingEngine(params, cfg, pt.serving.ServingConfig(
        num_slots=2, max_queue=16, prefill_buckets=(4, 8), max_len=32, **kw))


def _prompts(cfg, n=4):
    rng = np.random.RandomState(0)
    return [rng.randint(0, cfg.vocab_size, (3 + (i % 2) * 4,)).astype(np.int32)
            for i in range(n)]


def test_scheduler_tags_land_on_the_right_records(tiny_engine_params):
    eng = _engine(tiny_engine_params)
    try:
        eng.generate(_prompts(tiny_engine_params[0]), max_new_tokens=4)
        events = list(eng.scheduler.compile_events)
    finally:
        eng.close()
    tags = [r["tag"] for r in counted() if r["tag"]]
    assert sorted(tags) == sorted(events)       # an executable a tag
    assert {"prefill:L4", "prefill:L8", "decode_chunk",
            "admit_sample"} <= set(tags)
    for r in counted():
        if r["tag"] in ("prefill:L4", "prefill:L8"):
            assert r["fun_name"] == "prefill_impl"
        if r["tag"] == "decode_chunk":
            assert r["fun_name"] == "chunk_impl"
            assert r["trace_s"] > 0 and r["backend_compile_s"] > 0
    chunk, = [r for r in counted() if r["tag"] == "decode_chunk"]
    assert LOG.seconds_of("decode_chunk") == pytest.approx(chunk["seconds"])
    assert LOG.seconds_of("no such tag") == 0.0


def test_engine_build_is_a_phase_and_the_jits_another(tiny_engine_params):
    obs.enable_tracing()
    eng = _engine(tiny_engine_params)
    try:
        eng.generate(_prompts(tiny_engine_params[0], 1), max_new_tokens=2)
    finally:
        eng.close()
    obs.disable_tracing()
    phases = {p["phase"]: p for p in LOG.phases()}
    assert set(phases) == {"setup/import", "serving/engine_build",
                           "serving/engine_build/jits"}
    build = phases["serving/engine_build"]
    # the jits are made at the first request, outside the constructor and
    # on the drive thread
    assert phases["serving/engine_build/jits"]["begin_ns"] >= \
        build["begin_ns"] + int(build["seconds"] * 1e9)
    names = {s.name for s in obs.get_tracer().snapshot()}
    assert {"serving/engine_build", "serving/engine_build/jits"} <= names


def test_first_request_compiles_inside_the_ticks_admit(tiny_engine_params):
    eng = _engine(tiny_engine_params)
    try:
        obs.get_tracer().clear()
        obs.enable_tracing()
        eng.generate(_prompts(tiny_engine_params[0], 1), max_new_tokens=2)
        obs.disable_tracing()
    finally:
        eng.close()
    spans = obs.get_tracer().snapshot()
    admits = [s for s in spans if s.name == "serving/tick/admit"]
    assert admits
    for name in ("compile/trace", "compile/lower", "compile/backend"):
        mine = [s for s in spans if s.name == name
                and (s.args or {}).get("tag") == "prefill:L4"]
        assert len(mine) == 1, name
        s = mine[0]
        holders = [a for a in admits if a.tid == s.tid and a.ts_us <= s.ts_us
                   and s.ts_us + s.dur_us <= a.ts_us + a.dur_us + 1]
        assert len(holders) == 1 and s.depth > holders[0].depth, name


def test_ten_warm_engine_ticks_never_reach_the_log(tiny_engine_params):
    cfg = tiny_engine_params[0]
    eng = _engine(tiny_engine_params)
    try:
        eng.generate(_prompts(cfg), max_new_tokens=4)       # every shape
        for p in _prompts(cfg, 2):
            eng.submit(p, max_new_tokens=24)
        eng.step()
        obs.get_tracer().clear()
        obs.enable_tracing()
        calls, records = LOG.calls, len(LOG.records())
        for _ in range(10):
            eng.step()
        assert LOG.calls == calls and len(LOG.records()) == records
        obs.disable_tracing()
        eng.run_until_drained()
        assert LOG.calls == calls
    finally:
        eng.close()
    spans = obs.get_tracer().snapshot()
    assert sum(s.name == "serving/engine_step" for s in spans) == 10
    assert not [s for s in spans if s.name.startswith("compile/")]


def test_journal_reads_the_logs_stopwatch_and_probes_stay_out(
        tiny_engine_params):
    eng = _engine(tiny_engine_params, tick_profile=True)
    try:
        eng.generate(_prompts(tiny_engine_params[0]), max_new_tokens=4)
        journal = eng.compile_journal
        fams = journal.snapshot()["families"]
        stats = eng.stats()["compile"]
    finally:
        eng.close()
    real = [r for r in LOG.records() if r["tag"] and not r["probe"]]
    probes = [r for r in LOG.records() if r["probe"]]
    # one stopwatch: a family's seconds are its executables' in the log
    for name, fam in fams.items():
        mine = [r for r in real if r["tag"] == name]
        assert len(mine) == fam["compiles"], name
        assert fam["compile_s"] == pytest.approx(
            sum(r["seconds"] for r in mine)), name
    for rec in journal.records:
        assert rec["compile_s"] in [r["seconds"] for r in real
                                    if r["tag"] == rec["family"]]
    # _cost_probe lowered each a second time: marked, in no sum (tagged
    # where jax ran the body again and did not find the trace it had)
    assert len(probes) == len(real)
    assert {r["tag"] for r in probes} <= {r["tag"] for r in real} | {None}
    assert all(r["backend_compile_s"] == 0 and r["cache"] is None
               for r in probes)
    assert stats["probes"] == len(probes)
    assert stats["executables"] == len(LOG.records()) - len(probes)
    assert stats["lower_s"] == pytest.approx(
        sum(r["lower_s"] for r in LOG.records() if not r["probe"]))


def test_compilez_serves_the_table_without_tick_profile(tiny_engine_params):
    server = obs.DebugServer(port=0)
    eng = _engine(tiny_engine_params)
    try:
        eng.generate(_prompts(tiny_engine_params[0], 2), max_new_tokens=2)

        def get(path):
            with urllib.request.urlopen(f"{server.url}{path}", timeout=10) as r:
                return json.loads(r.read())

        page = get("/compilez")
        assert page["enabled"] is False and page["engines"] == {}
        table = page["compile"]
        snap = LOG.snapshot()
        assert table["executables"] == json.loads(
            json.dumps(snap["executables"]))
        # the engine's stats carry the sums, and point here for the rows
        assert table["totals"] == eng.stats()["compile"] == snap["totals"]
        assert {"prefill:L4", "decode_chunk"} <= {
            r["tag"] for r in table["executables"]}
        assert [p["phase"] for p in table["phases"]].count(
            "serving/engine_build") == 1
        one = get("/compilez?limit=1")["compile"]
        assert one["executables"] == table["executables"][-1:]
        assert one["totals"] == table["totals"]
    finally:
        server.stop()
        eng.close()
