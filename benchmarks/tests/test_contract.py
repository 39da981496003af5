"""BENCHMARK.json against the contract's own rules, and against the files the
harness finds by name."""

import importlib.util
import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def module(folder, name):
    path = os.path.join(BENCH, folder, name + ".py")
    assert os.path.isfile(path), path
    spec = importlib.util.spec_from_file_location("m", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cells_of(metric, bench):
    return metric.get("workloads") or [w["name"] for w in bench["workloads"]]


def test_keys_names_and_units(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks"] and 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
    assert len(names) == len(set(names))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(1, len(bench["workloads"]) // 4)


def test_every_cell_finds_its_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    for c in configs.values():
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["source"] == c["source"] and body["reduced"] == c["reduced"] == []
    for w in bench["workloads"]:
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            mix = json.load(f)
        assert os.path.isfile(os.path.join(BENCH, "modes", mix["mode"] + ".py"))
        assert mix["who"]


def test_every_metric_has_a_reader_and_moves_a_metric_of_its_cells(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in bench["end_to_end"]:
        mod = module("end_to_end", m["name"])
        assert (mod.UNIT, mod.BETTER) == (m["unit"], m["better"])
    layers = {}
    for m in bench["per_layer"]:
        mod = module("layer_metrics", m["name"])
        assert (mod.LAYER, mod.UNIT, mod.MOVES) == (m["layer"], m["unit"], m["moves"]), m["name"]
        assert m["moves"] in e2e
        assert set(cells_of(m, bench)) <= set(cells_of(e2e[m["moves"]], bench)), m["name"]
        layers.setdefault(m["layer"], []).append(m["name"])
    for w in bench["workloads"]:
        mine = lambda group: [m for m in bench[group] if w["name"] in cells_of(m, bench)]
        assert len(mine("end_to_end")) >= 2 and mine("per_layer")


def test_peaks_name_their_source():
    from lib import peaks

    assert peaks.PEAKS["TPU v5 lite"]["bf16_flops"] == 197e12
    assert peaks.PEAKS["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_costs_match_the_hand_count():
    from lib import costs

    with open(os.path.join(BENCH, "configs", "gpt2-small.json")) as f:
        small = json.load(f)
    # 12 layers of 4*768^2 + 2*768*3072 and the tied head 768*50257
    assert costs.matmul_params(small) == 12 * (4 * 768 ** 2 + 2 * 768 * 3072) + 768 * 50257
    assert costs.train_flops_per_token(small, 1024) == pytest.approx(0.798e9, rel=2e-3)
    assert costs.kv_bytes_per_token(small) == 36864
    with open(os.path.join(BENCH, "configs", "gpt2-xl.json")) as f:
        xl = json.load(f)
    assert costs.kv_bytes_per_token(xl) == 307200
    assert costs.weight_bytes(xl) == pytest.approx(3.1e9, rel=0.02)
    # a prompt of one token: every product once, the head once, one score
    assert costs.prefill_flops(small, 1) == 2 * (costs.matmul_params(small)) + 12 * 2 * 768
