"""The two readers of the paged decode kernel's share of the device's busy time,
on the traces recorded on the chip: an engine tick of a program without the
kernel reads 0.0001% (the reading of a mechanism that is not there), a training step
reads the share of its Mosaic calls."""

import gzip
import importlib.util
import os

import pytest

from lib import trace_reduce as tr

TESTS = os.path.dirname(os.path.abspath(__file__))
READERS = ("paged_attn_time_share.chat", "paged_attn_time_share.offline")


def reader(name):
    path = os.path.join(TESTS, "..", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("r", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("name", READERS)
def test_share_on_recorded_traces(name, tmp_path):
    read = reader(name).read
    unpacked = tmp_path / "engine_tick.textproto"
    with gzip.open(os.path.join(TESTS, "data", "engine_tick.textproto.gz"), "rt") as f:
        unpacked.write_text(f.read())
    tick = tr.reduce(tr.load(str(unpacked)))
    # no Mosaic call; XLA's own `ConcatBitcast` custom calls take 0.5 us of 0.40 s
    assert tick["busy_s"] > 0.4 and tick["kernel_s"] < 1e-6
    assert 0.0 <= read({"trace": tick}) < 1e-3
    step = tr.reduce(tr.load(os.path.join(TESTS, "data", "train_step.textproto")))
    assert read({"trace": step}) == pytest.approx(
        100.0 * step["kernel_s"] / step["busy_s"])
    assert 50.0 < read({"trace": step}) < 100.0    # 7.07 of 11.97 ms (PR 23's record)
    assert read({"trace": None}) is None           # an untraced run leaves the metric out


def test_the_two_readers_differ_only_in_what_they_move():
    chat, offline = (reader(name) for name in READERS)
    assert (chat.LAYER, chat.UNIT) == (offline.LAYER, offline.UNIT) == ("decode/prefill math", "%")
    assert (chat.MOVES, offline.MOVES) == ("tpot_mean_ms", "serve_tok_s")
