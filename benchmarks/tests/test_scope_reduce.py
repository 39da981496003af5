"""lib/scope_reduce.py on a recorded trace (tests/data/moonlight_scopes.json.gz:
cut from a chip run of `moonlight-longctx-offline`, see its `source`) and its
reader of the xplane file's wire format on a file made here by hand."""

import gzip
import json
import os

import pytest

from lib import scope_reduce as sr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "moonlight_scopes.json.gz")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA) as f:
        rec = json.load(f)
    return ([tuple(e) for e in rec["ops"]], [tuple(m) for m in rec["modules"]], rec["tf_op"])


def test_self_time_by_scope_and_by_kernel(recorded):
    ops, modules, tf_op = recorded
    tables = sr.by_scope(ops, modules, 0.0, 1e18, tf_op)
    assert set(tables) == {"jit_chunk_impl", "jit_prefill_impl"}
    chunk, prefill = tables["jit_chunk_impl"], tables["jit_prefill_impl"]
    # the cut holds no container, so an operation's self time is its own: the
    # scopes' seconds are the durations summed by the scope in its tf_op
    by_hand = {}
    lo, hi = modules[0][1], modules[0][1] + modules[0][2]
    for name, start, dur in ops:
        if lo <= start < hi:
            scope = sr.KERNEL_SCOPES.get(name[1:].split(" ")[0].rsplit(".", 1)[0]) \
                or sr.scope_of(tf_op[name])
            if scope:
                by_hand[scope] = by_hand.get(scope, 0.0) + dur * 1e-9
    assert chunk["scopes"] == pytest.approx(by_hand)
    assert set(chunk["scopes"]) >= {"mla/project", "mla/absorb", "mla/attend", "moe/router",
                                    "moe/dispatch", "moe/experts", "moe/shared",
                                    "moe/combine", "head"}
    # a decode step: the grouped expert products take most of it, then the
    # latent kernel, which runs under mla/attend and is counted once
    assert max(chunk["scopes"], key=chunk["scopes"].get) == "moe/experts"
    assert chunk["kernels"]["ragged-dot-none"] == pytest.approx(0.02044, rel=0.01)
    assert chunk["kernels"]["latent_paged_attention"] == pytest.approx(0.00283, rel=0.01)
    assert chunk["attend_s"] == pytest.approx(chunk["scopes"]["mla/attend"])
    assert chunk["attend_s"] >= chunk["kernels"]["latent_paged_attention"]
    # the prefill's attention is the flash kernel's forward under mla/attend
    assert prefill["scopes"]["mla/attend"] > 0 and "latent_paged_attention" not in prefill["kernels"]


def test_a_window_cuts_and_an_unscoped_program_gives_nothing(recorded):
    ops, modules, tf_op = recorded
    assert sr.by_scope(ops, modules, 0.0, 0.0, tf_op) == {}
    bare = sr.by_scope(ops, modules, 0.0, 1e18, {})
    # without tf_ops only XLA's own grouped-product kernels keep a scope
    assert all(set(t["scopes"]) <= {"moe/experts"} for t in bare.values())
    run = {"scopes": sr.by_scope(ops, modules, 0.0, 1e18, tf_op)}
    assert sr.scope_seconds(run, "jit_chunk_impl", "moe/") > sr.scope_seconds(run, "jit_chunk_impl", "mla/")
    assert sr.scope_seconds({"scopes": None}, None, "moe/") is None
    assert sr.scope_seconds(run, "jit_admit_impl", "moe/") is None


def test_scope_of_takes_the_innermost_scope():
    assert sr.scope_of("jit(chunk_impl)/while/body/closed_call/moe/router/reduce_sum:") == "moe/router"
    assert sr.scope_of("jit(prefill_impl)/mla/attend/cond/branch_1_fun/pallas_call:") == "mla/attend"
    assert sr.scope_of("jit(chunk_impl)/while/body/closed_call/head/dot_general:") == "head"
    assert sr.scope_of("jit(chunk_impl)/while/body/closed_call/overhead/add:") is None
    assert sr.scope_of("ragged-dot-none:") is None and sr.scope_of(None) is None


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, payload):
    if isinstance(payload, int):
        return _varint(number << 3) + _varint(payload)
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def test_metadata_ops_reads_tf_op_from_the_wire_format(tmp_path):
    """One device plane with a stat `tf_op` (id 7) and another stat, two event
    metadata of which one has a tf_op; a host plane that is not read."""
    stat_meta = lambda sid, name: _field(5, _field(1, sid) + _field(2, _field(1, sid) + _field(2, name)))
    event_meta = lambda mid, name, stats: _field(
        4, _field(1, mid) + _field(2, _field(1, mid) + _field(2, name) + b"".join(stats)))
    tf = _field(5, _field(1, 7) + _field(5, b"jit(chunk_impl)/moe/experts/ragged_dot:"))
    other = _field(5, _field(1, 3) + _field(3, 1504))
    device = (_field(2, b"/device:TPU:0") + stat_meta(3, b"flops") + stat_meta(7, b"tf_op")
              + event_meta(1, b"%fusion.1 = fusion()", [other, tf])
              + event_meta(2, b"%copy.2 = copy()", [other]))
    host = _field(2, b"/host:CPU") + stat_meta(7, b"tf_op") + event_meta(1, b"span", [tf])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, device) + _field(1, host))
    assert sr.metadata_ops(str(path)) == {
        "%fusion.1 = fusion()": "jit(chunk_impl)/moe/experts/ragged_dot:"}
