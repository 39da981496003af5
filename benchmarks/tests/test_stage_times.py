"""lib/stage_times.py: on hand-made traces whose answers can be counted on
paper (the innermost-stage rule, the kernel-name fall-back, the split by
program, a trace without scopes) and on the traces recorded on the chip
(`data/stage_chunk.textproto.gz`: one decode chunk of `small-chat-steady` with
the admission before it; `data/stage_train.textproto.gz`: one step of
`small-train-s1024`; both cut by `stage_times.clip_text_proto`)."""

import gzip
import importlib.util
import os
import subprocess
import sys

import pytest

from lib import stage_times as st

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH, "tests", "data")
NEW = ("stage_named_share.chat", "stage_named_share.offline", "stage_named_share.train",
       "head_time_share.chat", "head_time_share.offline", "norm_time_share.offline",
       "loop_us_per_step.chat", "loop_us_per_step.offline", "loss_time_share",
       "optimizer_time_share", "exec_release_ms")


def xplane(tmp_path, text, name="t.xplane.pb"):
    from jax.profiler import ProfileData

    path = tmp_path / name
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def trace(ops, modules, host="", window=(0, 1000)):
    """One chip. `ops`: "name start end tf_op" rows in microseconds (tf_op `-`
    for none); `modules` and `host`: "name start end" rows."""
    ids, tf, rows = {}, {}, []
    for row in ops.strip().splitlines():
        name, start, end, tf_op = row.split()
        ids.setdefault(name, len(ids) + 1)
        tf[name] = tf_op
        rows.append((ids[name], int(start), int(end)))
    events = lambda rows: "".join(
        f"  events {{ metadata_id: {i} offset_ps: {s * 10 ** 6} duration_ps: {(e - s) * 10 ** 6} }}\n"
        for i, s, e in rows)
    mods, mod_rows = {}, []
    for row in modules.strip().splitlines():
        name, start, end = row.split()
        mods.setdefault(name, len(ids) + len(mods) + 1)
        mod_rows.append((mods[name], int(start), int(end)))
    meta = "".join(
        f' event_metadata {{ key: {i} value {{ id: {i} name: "%{n} = {n.split(".")[0]}()"'
        + (f' stats {{ metadata_id: 1 str_value: "{tf[n]}" }}' if tf[n] != "-" else "") + " } }\n"
        for n, i in ids.items())
    meta += "".join(f' event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
                    for n, i in mods.items())
    device = ('planes { id: 1 name: "/device:TPU:0"\n'
              ' stat_metadata { key: 1 value { id: 1 name: "tf_op" } }\n'
              f' lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0\n{events(rows)} }}\n'
              f' lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0\n{events(mod_rows)} }}\n'
              f"{meta}}}\n")
    spans, span_rows = {"bench_window": 1}, [(1, *window)]
    for row in host.strip().splitlines():
        name, start, end = row.split()
        spans.setdefault(name, len(spans) + 1)
        span_rows.append((spans[name], int(start), int(end)))
    host_plane = ('planes { id: 2 name: "/host:CPU"\n'
                  f' lines {{ id: 1 name: "python3" timestamp_ns: 0\n{events(span_rows)} }}\n'
                  + "".join(f' event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
                            for n, i in spans.items()) + "}\n")
    return device + host_plane


# Window 0..1000 us. A prefill 0..200, an admission 200..220, a chunk 300..900
# whose `while` 300..900 holds its body's operations; 900..1000 idle.
SERVE = trace("""
fusion.1 0 50 jit(prefill_impl)/embed/add:
fusion.2 50 150 jit(prefill_impl)/attn/project/dot_general:
custom-call.3 150 190 -
fusion.4 190 200 jit(prefill_impl)/head/dot_general:
fusion.5 200 220 jit(admit_impl)/loop/sample/argmax:
while.6 300 900 jit(chunk_impl)/while:
fusion.7 300 400 jit(chunk_impl)/while/body/closed_call/norm/mul:
fusion.8 400 600 jit(chunk_impl)/while/body/closed_call/attn/attend/head/reduce:
fusion.9 600 700 jit(chunk_impl)/while/body/closed_call/loop/sample/jit(argmax)/reduce:
fusion.10 700 760 jit(chunk_impl)/while/body/closed_call/loop/finish/select_n:
copy.11 760 800 jit(chunk_impl)/while/body/closed_call/overhead/copy:
copy.12 800 850 -
""", """
jit_prefill_impl(1) 0 200
jit_admit_impl(2) 200 220
jit_chunk_impl(3) 300 900
""").replace('"%custom-call.3 = custom-call()"', '"%paged_attention.3 = custom-call()"')


def test_stages_by_program_innermost_wins_and_kernels_fall_back(tmp_path):
    tables = st.reduce_path(xplane(tmp_path, SERVE))
    us = lambda d: {k: round(v * 1e6, 3) for k, v in d.items()}
    assert set(tables["modules"]) == {"jit_prefill_impl", "jit_admit_impl", "jit_chunk_impl"}
    prefill, chunk = tables["modules"]["jit_prefill_impl"], tables["modules"]["jit_chunk_impl"]
    # a Mosaic call without a tf_op is put down by its kernel's name
    assert us(prefill["stages"]) == {"embed": 50, "attn/project": 100, "attn/attend": 40, "head": 10}
    assert us(tables["modules"]["jit_admit_impl"]["stages"]) == {"loop/sample": 20}
    # `attn/attend/head/reduce`: the innermost stage is `head`; `jit(argmax)` is
    # no stage; the `while` owns the 50 us none of its body ran in, under no stage
    assert us(chunk["stages"]) == {"norm": 100, "head": 200, "loop/sample": 100, "loop/finish": 60}
    assert us(chunk["unnamed"]) == {"copy.11": 40, "copy.12": 50, "while.6": 50}
    assert tables["busy_s"] == pytest.approx(820e-6)
    assert tables["named_s"] == pytest.approx(680e-6)
    assert tables["scoped_s"] == pytest.approx(640e-6)     # less the kernel's 40
    assert (prefill["runs"], chunk["runs"]) == (1.0, 1.0)
    assert st.stage_seconds(tables, ("loop",)) == pytest.approx(180e-6)
    assert st.stage_seconds(tables, ("loop",), "jit_chunk_impl") == pytest.approx(160e-6)
    assert st.stage_seconds(tables, ("head", "norm")) == pytest.approx(310e-6)
    assert st.stage_seconds(tables, ("loss",)) is None
    # `head` is no prefix of another word, `attn` takes its stages
    assert st.stage_seconds(tables, ("hea",)) is None
    assert st.stage_seconds(tables, ("attn",)) == pytest.approx(140e-6)


def test_stage_of_and_the_set_of_stages_is_data():
    assert st.stage_of("jit(chunk_impl)/while/body/closed_call/moe/router/reduce_sum:") == "moe/router"
    assert st.stage_of("jit(prefill_impl)/mla/attend/cond/branch_1_fun/pallas_call:") == "mla/attend"
    assert st.stage_of("jit(step)/jit(main)/head/matmul_grad/transpose:") == "head"
    assert st.stage_of("jit(step)/attn/fused_attention_grad/pallas_call:") == "attn/fused_attention_grad"
    assert st.stage_of("jit(step)/optimizer/adam/mul:") == "optimizer"
    assert st.stage_of("jit(step)/sum/add:") is None and st.stage_of(None) is None
    assert st.stage_of("jit(chunk_impl)/overhead/add:") is None
    assert st.stage_of("jit(f)/hc/coeff/jit(norm)/reduce:") == "hc/coeff"
    # the next model's family is one more entry
    assert st.stage_of("jit(f)/ssm/scan/mul:") is None
    assert st.stage_of("jit(f)/ssm/scan/mul:", st.STAGES + ("ssm/*",)) == "ssm/scan"
    assert st.kernel_stage("%_causal_rows_call.4 = custom-call()") == "attn/attend"
    assert st.kernel_stage("%fused_attention_bwd_dq.1 = custom-call()") == "attn"
    assert st.kernel_stage("%ragged-dot-none.2 = custom-call()") == "moe/experts"
    assert st.kernel_stage("%paged_attention.4 = fusion()") is None


# Two steps of training, 100..400 and 500..800, and the host's runs around them
TRAIN = trace("""
fusion.1 100 130 jit(step)/embed/lookup_table/gather:
fusion.2 130 200 jit(step)/head/matmul/dot_general:
fusion.3 200 260 jit(step)/loss/softmax_with_cross_entropy/reduce:
fusion.4 260 300 jit(step)/head/matmul_grad/dot_general:
fusion.5 300 340 jit(step)/sum/add:
fusion.6 340 400 jit(step)/optimizer/adam/mul:
fusion.1 500 530 jit(step)/embed/lookup_table/gather:
fusion.2 530 600 jit(step)/head/matmul/dot_general:
fusion.3 600 660 jit(step)/loss/softmax_with_cross_entropy/reduce:
fusion.4 660 700 jit(step)/head/matmul_grad/dot_general:
fusion.5 700 740 jit(step)/sum/add:
fusion.6 740 800 jit(step)/optimizer/adam/mul:
""", """
jit_step(7) 100 400
jit_step(7) 500 800
""", """
executor/run 40 450
executor/fetch 120 430
executor/release 430 448
executor/run 460 850
executor/fetch 530 830
executor/release 830 842
executor/run 880 1100
executor/fetch 890 1000
executor/release 1000 1090
""")


def readers():
    out = {}
    for name in NEW:
        spec = importlib.util.spec_from_file_location("m", os.path.join(BENCH, "layer_metrics", name + ".py"))
        out[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(out[name])
    return out


def as_run(monkeypatch, tmp_path, text, cell):
    """A traced run's trace where a reader looks for it."""
    out = tmp_path / "out"
    folder = out / f"{cell}.trace" / "plugins" / "profile" / "x"
    folder.mkdir(parents=True)
    xplane(folder, text)
    monkeypatch.setattr(st.program_spans, "OUT", str(out))
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", cell, "--seed", "1"])
    st.tables_at.cache_clear()
    return {"trace": {"busy_s": 1.0}, "decode_chunk": 4}


def test_the_readers_on_a_training_trace(monkeypatch, tmp_path):
    run = as_run(monkeypatch, tmp_path, TRAIN, "small-train-s1024")
    read = {name: mod.read(run) for name, mod in readers().items()}
    assert read["stage_named_share.train"] == pytest.approx(100 * 520 / 600)
    assert read["loss_time_share"] == pytest.approx(100 * 340 / 600)      # head + loss, _grad too
    assert read["optimizer_time_share"] == pytest.approx(100 * 120 / 600)
    # the two runs whole inside the window: 18 and 12 us; the third is cut
    assert read["exec_release_ms"] == pytest.approx(0.015)
    assert read["loop_us_per_step.chat"] is None and read["norm_time_share.offline"] is None
    assert st.traced({"trace": None}) is None


def test_the_readers_on_a_serving_trace_and_once_a_run(monkeypatch, tmp_path):
    run = as_run(monkeypatch, tmp_path, SERVE, "small-chat-steady")
    loads = []
    real = st.tr.load
    monkeypatch.setattr(st.tr, "load", lambda path: loads.append(path) or real(path))
    read = {name: mod.read(run) for name, mod in readers().items()}
    assert len(loads) == 1                                   # eleven readers, one parse
    assert read["stage_named_share.chat"] == pytest.approx(100 * 680 / 820)
    assert read["head_time_share.chat"] == pytest.approx(100 * 210 / 820)
    assert read["norm_time_share.offline"] == pytest.approx(100 * 100 / 820)
    assert read["loop_us_per_step.chat"] == pytest.approx(160 / 4)      # one run of 4 steps
    assert read["optimizer_time_share"] is None and read["exec_release_ms"] is None


def test_a_trace_without_scopes_gives_none_from_every_new_reader(monkeypatch, tmp_path):
    """The parent's trace: the same operations with the program's op types
    and kernels but no stage; the Mosaic call's name alone is no scope."""
    bare = SERVE
    for stage in ("embed/", "attn/project/", "attn/attend/head/", "head/", "loop/sample/",
                  "loop/finish/", "norm/"):
        bare = bare.replace(stage, "")
    run = as_run(monkeypatch, tmp_path, bare, "xl-docs-offline")
    tables = st.traced(run)
    assert tables["scoped_s"] == 0 and tables["named_s"] == pytest.approx(40e-6)
    assert {name: mod.read(run) for name, mod in readers().items()} == dict.fromkeys(NEW)
    parent = TRAIN.replace("head/", "").replace("loss/", "").replace("optimizer/", "") \
        .replace("embed/", "").replace("executor/release", "host/other")
    run = as_run(monkeypatch, tmp_path / "p", parent, "small-train-dp4")
    assert {name: mod.read(run) for name, mod in readers().items()} == dict.fromkeys(NEW)


def test_the_cutter_keeps_tf_ops_and_the_script_prints_the_table(tmp_path):
    path = xplane(tmp_path, SERVE)
    cut = st.clip_text_proto(path, 250e3, 950e3)             # the chunk alone
    tables = st.reduce_path(xplane(tmp_path, cut, "cut.xplane.pb"))
    assert set(tables["modules"]) == {"jit_chunk_impl"}
    whole = st.reduce_path(path)["modules"]["jit_chunk_impl"]
    assert tables["modules"]["jit_chunk_impl"]["stages"] == pytest.approx(whole["stages"])
    out = subprocess.run([sys.executable, os.path.join(BENCH, "lib", "stage_times.py"), path],
                         capture_output=True, text=True, check=True,
                         env=dict(os.environ, JAX_PLATFORMS="cpu")).stdout
    assert "jit_chunk_impl: 0.0006 s in 1.0 runs" in out
    assert "loop (layer)" in out and "the 10 largest operations under no stage" in out
    assert "jit_chunk_impl: copy.12" in out
    assert subprocess.run([sys.executable, os.path.join(BENCH, "lib", "stage_times.py")],
                          capture_output=True).returncode == 1


def recorded(tmp_path, name):
    with gzip.open(os.path.join(DATA, name), "rt") as f:
        return st.reduce_path(xplane(tmp_path, f.read(), name + ".xplane.pb"))


def test_the_recorded_decode_chunk(tmp_path):
    """`small-chat-steady`, my chip run of PR 35: a prefill, its admission
    and the chunk of 8 steps after it."""
    tables = recorded(tmp_path, "stage_chunk.textproto.gz")
    assert set(tables["modules"]) == {"jit_prefill_impl", "jit_admit_impl", "jit_chunk_impl"}
    chunk = tables["modules"]["jit_chunk_impl"]
    assert set(chunk["stages"]) == {"embed", "norm", "attn/project", "attn/attend", "ffn/dense",
                                    "head", "loop/sample", "loop/finish"}
    # the paged kernel writes the row, so a decode step has no `attn/write`; a prefill has
    assert "attn/write" in tables["modules"]["jit_prefill_impl"]["stages"]
    assert set(tables["modules"]["jit_admit_impl"]["stages"]) == {"loop/sample", "loop/finish"}
    assert chunk["runs"] == pytest.approx(1.0)
    assert 1e6 * st.stage_seconds(tables, ("loop",), "jit_chunk_impl") / 8 == pytest.approx(47.1, rel=0.01)
    assert 1e6 * chunk["stages"]["head"] == pytest.approx(217.9, rel=0.01)
    # the arg-max over 32 x 50,257 logits is the sampler's
    assert max(chunk["kinds"]["loop/sample"], key=chunk["kinds"]["loop/sample"].get) \
        == "iota_reduce_fusion"
    assert chunk["kinds"]["attn/attend"]["paged_attention"] == pytest.approx(312e-6, rel=0.02)
    # what no stage holds: the compiler's own prefetches, which carry no tf_op
    assert 100 * tables["named_s"] / tables["busy_s"] == pytest.approx(60.3, abs=0.2)
    assert tables["scoped_s"] == pytest.approx(tables["named_s"])
    loose = {}
    for name, s in chunk["unnamed"].items():
        loose[st.tr.family(name)] = loose.get(st.tr.family(name), 0.0) + s
    assert sorted(loose, key=loose.get)[-2:] == ["slice-done", "copy-done"]
    assert loose["copy-done"] + loose["slice-done"] > 0.9 * sum(loose.values()) - 20e-6


def test_the_recorded_training_step(tmp_path):
    """`small-train-s1024`, my chip run of PR 35: two steps and the host's
    runs around them."""
    tables = recorded(tmp_path, "stage_train.textproto.gz")
    (step,) = tables["modules"].values()
    assert step["runs"] == pytest.approx(2.0, abs=0.01)
    share = lambda *prefixes: 100 * st.stage_seconds(tables, prefixes) / tables["busy_s"]
    assert 100 * tables["named_s"] / tables["busy_s"] == pytest.approx(95.9, abs=0.1)
    assert share("head", "loss") == pytest.approx(24.1, abs=0.1)
    assert share("head") == pytest.approx(15.3, abs=0.1)
    assert share("optimizer") == pytest.approx(2.35, abs=0.05)
    assert share("attn") == pytest.approx(44.0, abs=0.1) and share("ffn") == pytest.approx(23.0, abs=0.1)
    # XLA books Adam's update of a matrix on its gradient's product: the
    # fusion is named for the update and carries the product's scope
    assert step["kinds"]["ffn/mul_grad"]["divide_subtract_fusion"] \
        > 3 * step["kinds"]["optimizer"]["divide_subtract_fusion"]
    assert step["kinds"]["attn/fused_attention_grad"]["fused_attention_grad"] \
        == pytest.approx(28.3e-3, rel=0.01)
    steps, seconds = tables["release"]
    assert steps == 2 and 1e3 * seconds / steps == pytest.approx(1.515, abs=0.01)
    assert "executor/release: 1.515 ms a step over 2 steps" in st.table(tables)
