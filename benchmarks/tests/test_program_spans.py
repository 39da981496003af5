"""The program's spans read back from a trace: on two hand-made traces whose
answers can be counted on paper, and on the traces recorded on the chip
(`data/train_phases.textproto.gz`, `data/engine_tick.textproto.gz`)."""

import gzip
import os
import subprocess
import sys

import pytest

from lib import program_spans as ps
from lib import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
US = 1e-6


def plane(pid, name, lines):
    """A plane from {line name: "name start end" rows, microseconds}."""
    ids, out = {}, [f'planes {{ id: {pid} name: "{name}"']
    for lid, (line, rows) in enumerate(lines.items(), 1):
        out.append(f' lines {{ id: {lid} name: "{line}" timestamp_ns: 0')
        for row in rows.strip().splitlines():
            event, start, end = row.split()
            mid = ids.setdefault(event, len(ids) + 1)
            out.append(f"  events {{ metadata_id: {mid} offset_ps: {int(start) * 10 ** 6} "
                       f"duration_ps: {(int(end) - int(start)) * 10 ** 6} }}")
        out.append(" }")
    out.extend(f' event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
               for n, i in ids.items())
    return "\n".join(out + ["}"])


def trace(device_ops, threads):
    return (plane(1, "/device:TPU:0", {"XLA Ops": device_ops}) + "\n"
            + plane(2, "/host:CPU", threads) + "\n")


# One chip, microseconds, window 0..1000. The device runs step 1 at 100..400
# and step 2 at 500..800: idle 0..100, 400..500, 800..1000.
#   run 1 40..450:  prepare 40..70, place 70..90, dispatch 90..110, writeback
#                   110..120, fetch 120..440; its own time 10
#   run 2 460..850: prepare 460..480, place 480..510, dispatch 510..520,
#                   writeback 520..530, fetch 530..840; its own time 10
#   run 3 880..1100 is cut by the window's end: prepare 880..890, place 890..1100
#   PjitFunction is the runtime's own event and is no program span
TRAIN = trace("""
%fusion.1 100 400
%fusion.1 500 800
""", {"python3": """
bench_window 0 1000
step 30 455
executor/run 40 450
executor/prepare 40 70
executor/place 70 90
executor/dispatch 90 110
PjitFunction(step) 92 108
executor/writeback 110 120
executor/fetch 120 440
executor/run 460 850
executor/prepare 460 480
executor/place 480 510
executor/dispatch 510 520
executor/writeback 520 530
executor/fetch 530 840
executor/run 880 1100
executor/prepare 880 890
executor/place 890 1100
"""})

# One chip, window 0..1000. Busy 150..590 and 640..900: idle 0..150, 590..640,
# 900..1000. The driver thread: waits for work 20..100; tick A 100..300 admits
# (inside: a fence's wait 102..106, a prefill, the wait for its first token
# 140..148), launches (the dispatch inside), collects 180..280 and streams;
# tick B 300..320 only admits; tick C 600..700 collects 610..690;
# tick D 940..1010 is cut by the window's end. A client thread holds nothing
# of the program's.
SERVE = trace("""
%fusion.2 150 590
%fusion.2 640 900
""", {"python3": """
bench_window 0 1000
""", "pt-serve-drive-r0": """
serving/idle_wait 20 100
serving/engine_step 100 300
serving/tick/admit 100 150
serving/wait/fence 102 106
serving/prefill 110 140
serving/wait/first_token 140 148
serving/tick/launch 150 180
serving/decode_dispatch 155 175
serving/tick/collect 180 280
serving/tick/stream 280 295
serving/engine_step 300 320
serving/tick/admit 300 310
serving/engine_step 600 700
serving/tick/collect 610 690
serving/engine_step 940 1010
serving/tick/admit 960 1005
""", "bench-client-7": """
socket_read 0 900
"""})


@pytest.fixture(scope="module")
def hand(tmp_path_factory):
    out = {}
    for name, text in (("train", TRAIN), ("serve", SERVE)):
        path = tmp_path_factory.mktemp("spans") / f"{name}.textproto"
        path.write_text(text)
        out[name] = ps.summarize(tr.load(str(path)))
    return out


def approx_dict(got, want):
    return set(got) == set(want) and all(got[k] == pytest.approx(want[k]) for k in want)


def test_tree_gives_parents_and_self_time():
    nodes = ps.tree([("a", 0, 10), ("b", 1, 3), ("c", 2, 1), ("d", 5, 2), ("e", 12, 1)])
    assert [(n[0], n[3], n[4]) for n in nodes] == [
        ("a", None, 5), ("b", 0, 2), ("c", 1, 1), ("d", 0, 2), ("e", None, 1)]
    assert ps.under(nodes, 2, "a") and not ps.under(nodes, 4, "a")


def test_train_steps_by_hand(hand):
    s = hand["train"]
    assert s["window_s"] == pytest.approx(1000 * US)
    assert set(s["spans"]) == {"executor/run", "executor/prepare", "executor/place",
                               "executor/dispatch", "executor/writeback", "executor/fetch"}
    run = s["spans"]["executor/run"]
    assert run["runs"] == 2                                   # the third is cut by the edge
    assert run["total_s"] == pytest.approx(800 * US) and run["self_s"] == pytest.approx(20 * US)
    assert s["spans"]["executor/fetch"]["total_s"] == pytest.approx(630 * US)
    assert s["spans"]["executor/prepare"]["runs"] == 3        # 880..890 lies whole inside
    assert s["steps"] == 2 and s["ticks"] == 0 and s["tick_host_ms"] is None
    assert s["step_host_ms"] == pytest.approx((90 + 80) / 2 * 1e-3)
    assert s["step_place_ms"] == pytest.approx((20 + 30) / 2 * 1e-3)
    # each gap is cut at the spans' edges: 0..100 is 40 before any span, then
    # prepare 30, place 20, dispatch 10; 400..500 is fetch 40, run 1's own 10,
    # 10 between the runs, prepare 20, place 20; 800..1000 is fetch 40, run 2's
    # own 10, 30 between, prepare 10, place 110 of the cut run
    assert s["idle_s"] == pytest.approx(400 * US)
    assert approx_dict(s["idle_by_span"], {
        "none": 80 * US, "executor/prepare": 60 * US, "executor/place": 150 * US,
        "executor/dispatch": 10 * US, "executor/fetch": 80 * US, "executor/run": 20 * US})
    # a step's own time is no phase, and neither is the time between steps
    assert s["idle_phased_s"] == pytest.approx(300 * US)


def test_engine_ticks_by_hand(hand):
    s = hand["serve"]
    step = s["spans"]["serving/engine_step"]
    assert step["runs"] == 3 and step["total_s"] == pytest.approx(320 * US)
    # tick A keeps 5 of its 200, tick B 10 of 20, tick C 20 of 100
    assert step["self_s"] == pytest.approx(35 * US)
    assert s["spans"]["serving/tick/launch"]["self_s"] == pytest.approx(10 * US)
    assert s["spans"]["serving/tick/admit"]["runs"] == 2      # the cut tick's is not whole
    # ticks A and C launched or collected; less their waits for the device:
    # (200 - 100 - 4 - 8) and (100 - 80)
    assert s["ticks"] == 2 and s["steps"] == 0 and s["step_host_ms"] is None
    assert s["tick_host_ms"] == pytest.approx((88 + 20) / 2 * 1e-3)
    # 0..150: 20 before any span, the wait for work 80, tick A's admit 2, the
    # fence 4, admit 4, its prefill 30, the first token 8, admit 2; 590..640:
    # 10 between ticks, tick C's own 10, its collect 30; 900..1000: 40 between
    # ticks, tick D's own 20, its admit 40
    assert approx_dict(s["idle_by_span"], {
        "none": 70 * US, "serving/idle_wait": 80 * US, "serving/tick/admit": 48 * US,
        "serving/wait/fence": 4 * US, "serving/wait/first_token": 8 * US,
        "serving/prefill": 30 * US, "serving/engine_step": 30 * US,
        "serving/tick/collect": 30 * US})
    # a tick's own time is no phase; a prefill and a wait lie inside one
    assert s["idle_s"] == pytest.approx(300 * US) and s["idle_phased_s"] == pytest.approx(200 * US)


def test_a_trace_without_program_spans_gives_nothing(tmp_path):
    """What the parent of the PR that added the spans leaves behind: the
    readers return None and the line leaves their metrics out."""
    path = tmp_path / "old.textproto"
    path.write_text(trace("%fusion.1 100 400", {"python3": "bench_window 0 1000\nstep 30 455"}))
    assert ps.summary_at(str(path)) is None
    assert ps.summary_at(str(tmp_path / "no_such.trace")) is None
    assert ps.of_run({"trace": None}) is None


def test_readers_find_the_run_by_the_command_line(tmp_path, monkeypatch):
    """A reader is handed the run and no cell name: the trace is found where
    run.py's Context.out_path put it, by the command line's --workload."""
    import importlib.util

    cell = tmp_path / "tiny-cell.trace" / "plugins" / "profile" / "1"
    cell.mkdir(parents=True)
    # a reader never sees a text trace; the loader tells the two apart by name
    (cell / "x.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(ps, "OUT", str(tmp_path))
    monkeypatch.setattr(tr, "find_xplane", lambda d: str(tmp_path / "train.textproto"))
    (tmp_path / "train.textproto").write_text(TRAIN)
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", "tiny-cell", "--seed", "1"])
    ps.summary_at.cache_clear()

    def reader(name):
        path = os.path.join(os.path.dirname(DATA), "..", "layer_metrics", name + ".py")
        spec = importlib.util.spec_from_file_location("r", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read

    run = {"trace": {"busy_s": 1.0}}
    assert reader("exec_host_ms")(run) == pytest.approx(0.085)
    assert reader("exec_place_ms")(run) == pytest.approx(0.025)
    assert reader("idle_named_share.train")(run) == pytest.approx(75.0)
    assert reader("tick_host_ms.chat")(run) is None           # no tick in a train trace
    assert reader("exec_host_ms")({"trace": None}) is None    # an untraced run
    # a program that writes its dispatches and no step or tick around them
    (tmp_path / "train.textproto").write_text(trace(
        "%fusion.1 100 400", {"python3": "bench_window 0 1000\nserving/prefill 30 90"}))
    ps.summary_at.cache_clear()
    assert reader("idle_named_share.chat")(run) is None
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload=other", "--seed", "1"])
    assert reader("exec_host_ms")(run) is None                # no trace of that cell
    ps.summary_at.cache_clear()


def test_table_as_a_script(tmp_path):
    path = tmp_path / "serve.textproto"
    path.write_text(SERVE)
    done = subprocess.run([sys.executable, os.path.join(os.path.dirname(DATA), "..", "lib",
                                                        "program_spans.py"), str(path)],
                          capture_output=True, text=True, timeout=120,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr
    rows = {line.split()[0]: line.split() for line in done.stdout.splitlines()[2:]}
    assert rows["serving/tick/collect"][1] == "2"
    assert float(rows["serving/tick/collect"][2]) == pytest.approx(0.090)
    assert float(rows["serving/idle_wait"][4]) == pytest.approx(80 * US, abs=1e-6)
    assert rows["tick_host_ms"][1] == "0.054"


def recorded(name, tmp_path):
    path = tmp_path / f"{name}.textproto"
    with gzip.open(os.path.join(DATA, f"{name}.textproto.gz"), "rt") as f:
        path.write_text(f.read())
    return ps.summarize(tr.load(str(path)))


def test_recorded_train_step(tmp_path):
    """106 ms of `small-train-s1024` on a v5e (PR 24, seed 1000003): one whole
    `Executor.run` with the end of the step before and the start of the step
    after, cut with `trace_reduce.clip_text_proto(planes, lo, hi, keep_host=
    <bench_window + the phase names>)`, the lines `Async XLA Ops` and `Steps`
    left out, gzipped. The step's own 1.6 ms after the fetch is `_run_impl`
    returning, when the last references to its donated inputs die."""
    s = recorded("train_phases", tmp_path)
    assert s["window_s"] == pytest.approx(0.105933943)
    assert s["steps"] == 1 and s["ticks"] == 0
    run = s["spans"]["executor/run"]
    assert run["runs"] == 1 and run["total_s"] == pytest.approx(0.078469588)
    assert run["self_s"] == pytest.approx(0.00163334, rel=1e-5)
    assert s["spans"]["executor/fetch"]["total_s"] == pytest.approx(0.070650988)
    assert s["spans"]["executor/dispatch"]["runs"] == 2       # the next step's lies whole inside
    assert s["step_host_ms"] == pytest.approx(78.469588 - 70.650988)
    assert s["step_place_ms"] == pytest.approx(0.53889)
    # two gaps of 6.6 and 5.5 ms between steps, and the pauses between
    # operations inside a step, which lie under the fetch that waits for them
    assert s["idle_s"] == pytest.approx(0.012827214)
    idle = s["idle_by_span"]
    assert idle["executor/fetch"] == pytest.approx(0.00571428, rel=1e-5)      # loss on its way to the host
    assert idle["executor/run"] == pytest.approx(0.00331823, rel=1e-5)        # the steps' own tails
    assert idle["executor/dispatch"] == pytest.approx(0.001794521, rel=1e-5)  # until the launch
    assert idle["none"] == pytest.approx(9.123e-05, rel=1e-4)                 # the benchmark's loop
    assert s["idle_phased_s"] == pytest.approx(0.009417754, rel=1e-5)
    assert sum(idle.values()) == pytest.approx(s["idle_s"])


def test_recorded_engine_tick(tmp_path):
    """407 ms of `small-chat-steady` on a v5e (PR 24, seed 2100001011), cut the
    same way: one whole tick that admitted a request (its prefill, then the
    wait for its first token behind the chunk in flight), launched a decode
    dispatch, collected the one before and streamed its tokens, with parts of
    the ticks around it."""
    s = recorded("engine_tick", tmp_path)
    assert s["ticks"] == 1 and s["steps"] == 0
    tick = s["spans"]["serving/engine_step"]
    assert tick["runs"] == 1 and tick["total_s"] == pytest.approx(0.30154645)
    assert tick["self_s"] == pytest.approx(0.00036222, rel=1e-5)
    admit = s["spans"]["serving/tick/admit"]
    assert admit["total_s"] == pytest.approx(0.299291231) and admit["self_s"] == pytest.approx(0.002161519)
    # 285 of the admission's 299 ms are the wait for the device, and the block
    # the tick then collects is ready when it asks
    assert s["spans"]["serving/wait/first_token"]["total_s"] == pytest.approx(0.285336703)
    assert s["spans"]["serving/tick/collect"]["total_s"] == pytest.approx(0.000549)
    assert s["spans"]["serving/prefill"]["runs"] == 2         # the next tick's lies whole inside
    assert s["spans"]["serving/tick/launch"]["self_s"] == pytest.approx(2.222e-05, rel=1e-3)
    assert s["spans"]["serving/decode_dispatch"]["total_s"] == pytest.approx(0.00067284)
    assert s["tick_host_ms"] == pytest.approx(301.54645 - 285.336703 - 0.549)
    # the chip is busy nearly all through; most of what idles lies under the
    # wait for the first token, while the prefill queued behind the chunk starts
    assert s["idle_s"] == pytest.approx(0.002246926, rel=1e-5)
    assert s["idle_phased_s"] == pytest.approx(0.002233976, rel=1e-5)
    assert max(s["idle_by_span"], key=s["idle_by_span"].get) == "serving/wait/first_token"
