"""The reduction from a profiler trace to numbers: on a hand-made trace whose
answers can be counted on paper, and on the trace recorded on the chip."""

import os

import pytest

from lib import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# One chip, times in microseconds. Window 0..1000 (the host's bench_window).
#   program A (jit_chunk_impl) 100..500: a `while` 100..500 holding fusion.1
#     100..300 and custom-call 300..450 (50 us of the while are its own)
#   idle 500..600, inside the host's `feed` span 480..620
#   program B (jit_prefill_impl) 600..900: all-reduce 600..700 alone, then
#     all-reduce 700..800 under a fusion 700..900 on the same line
#   program C (jit_chunk_impl) 950..1100 is cut by the window's end
HAND = """
planes { id: 1 name: "/device:TPU:0"
 lines { id: 1 name: "XLA Ops" timestamp_ns: 0
  events { metadata_id: 1 offset_ps: 100000000 duration_ps: 400000000 }
  events { metadata_id: 2 offset_ps: 100000000 duration_ps: 200000000 }
  events { metadata_id: 3 offset_ps: 300000000 duration_ps: 150000000 }
  events { metadata_id: 4 offset_ps: 600000000 duration_ps: 100000000 }
  events { metadata_id: 4 offset_ps: 700000000 duration_ps: 100000000 }
  events { metadata_id: 5 offset_ps: 700000000 duration_ps: 200000000 }
  events { metadata_id: 2 offset_ps: 950000000 duration_ps: 150000000 }
 }
 lines { id: 2 name: "XLA Modules" timestamp_ns: 0
  events { metadata_id: 6 offset_ps: 100000000 duration_ps: 400000000 }
  events { metadata_id: 7 offset_ps: 600000000 duration_ps: 300000000 }
  events { metadata_id: 6 offset_ps: 950000000 duration_ps: 150000000 }
 }
 lines { id: 3 name: "Async XLA Ops" timestamp_ns: 0
  events { metadata_id: 2 offset_ps: 0 duration_ps: 1000000000 }
 }
 event_metadata { key: 1 value { id: 1 name: "%while.3 = (s32[]{:T(128)}, bf16[8,64]{1,0:T(8,128)(2,1)}) while((s32[], bf16[8,64]) %tuple.1), condition=%cond, body=%body" } }
 event_metadata { key: 2 value { id: 2 name: "%fusion.1 = bf16[8,64]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[8,64]{1,0:T(8,128)(2,1)} %custom-call.9), kind=kLoop, calls=%fused_computation" } }
 event_metadata { key: 3 value { id: 3 name: "%fused_attention.2 = bf16[96,1024,64]{2,1,0:T(8,128)(2,1)} custom-call(bf16[96,1024,64]{2,1,0} %bitcast.1), custom_call_target=\\"tpu_custom_call\\"" } }
 event_metadata { key: 4 value { id: 4 name: "%all-reduce.5 = f32[768]{0:T(1024)} all-reduce(f32[768]{0:T(1024)} %fusion.1), replica_groups={{0,1,2,3}}" } }
 event_metadata { key: 5 value { id: 5 name: "%copy_bitcast_fusion.28 = bf16[8,64]{1,0} fusion(bf16[8,64]{1,0} %p), kind=kLoop" } }
 event_metadata { key: 6 value { id: 6 name: "jit_chunk_impl(1234)" } }
 event_metadata { key: 7 value { id: 7 name: "jit_prefill_impl(99)" } }
}
planes { id: 2 name: "/host:CPU"
 lines { id: 1 name: "python3" timestamp_ns: 0
  events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000000 }
  events { metadata_id: 2 offset_ps: 480000000 duration_ps: 140000000 }
 }
 event_metadata { key: 1 value { id: 1 name: "bench_window" } }
 event_metadata { key: 2 value { id: 2 name: "feed" } }
}
"""


@pytest.fixture(scope="module")
def hand(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "hand.textproto"
    path.write_text(HAND)
    return tr.reduce(tr.load(str(path)), ("feed", "step"))


def test_names_and_opcodes():
    text = ("%fusion.16 = f32[50257,768]{1,0:T(8,128)} fusion(bf16[8,1024,768]{2,1,0:T(8,128)(2,1)S(1)} "
            "%custom-call.204, bf16[8,1023,50257]{1,2,0} %get-tuple-element.724), kind=kOutput")
    assert (tr.op_name(text), tr.opcode(text), tr.family(text)) == ("fusion.16", "fusion", "fusion")
    assert not tr.is_kernel(text)         # it only reads a custom call's result
    assert tr.is_kernel("%fused_attention_grad.44 = (bf16[96,1024,64]{2,1,0:T(8,128)(2,1)S(1)}, "
                        "bf16[96,1024,64]{2,1,0}) custom-call(bf16[96,1024,64]{2,1,0} %bitcast.2454)")
    assert tr.is_collective("%all-reduce-start.1 = f32[8]{0} all-reduce-start(f32[8]{0} %x)")
    assert tr.is_container("%while.3 = (s32[]) while((s32[]) %t), condition=%c, body=%b")
    assert tr.family("copy_bitcast_fusion.28") == "copy_bitcast_fusion"
    assert tr.module_name("jit_chunk_impl(1234)") == "jit_chunk_impl"


def test_interval_arithmetic():
    assert tr.union([(5, 7), (1, 3), (2, 4)]) == [[1, 4], [5, 7]]
    assert tr.subtract([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]
    own = dict(tr.self_times([("outer", 0, 10), ("a", 1, 3), ("b", 5, 2)]))
    assert own == {"outer": 5, "a": 3, "b": 2}


def test_hand_made_trace(hand):
    us = 1e-6
    assert hand["chips"] == 1
    assert hand["window_s"] == pytest.approx(1000 * us)
    # busy: 100..500, 600..900, 950..1000; the async line does not count
    assert hand["busy_s"] == pytest.approx(750 * us)
    assert hand["kernel_s"] == pytest.approx(150 * us)
    # 600..700 has no compute beside it; 700..800 lies under the fusion
    assert hand["collective_exposed_s"] == pytest.approx(100 * us)
    assert hand["module_s"]["jit_chunk_impl"] == pytest.approx(450 * us)    # 400 + the 50 before the edge
    assert hand["module_whole_s"] == pytest.approx({"jit_chunk_impl": 400 * us,
                                                    "jit_prefill_impl": 300 * us})
    assert hand["module_runs"] == {"jit_chunk_impl": 1, "jit_prefill_impl": 1}
    ops = dict(hand["device_ops"])
    assert ops["while.3"] == pytest.approx(50 * us)            # its own time, not its body's
    assert ops["all_fusion"] == pytest.approx(250 * us)        # fusion.1 twice: 200 and the cut 50
    assert ops["fused_attention.2"] == pytest.approx(150 * us)
    gaps = dict(hand["idle_gaps"])
    assert gaps["feed"] == pytest.approx(100 * us)             # 500..600, under the host's span
    assert gaps["after_window_start_before_jit_chunk_impl"] == pytest.approx(100 * us)
    assert gaps["after_jit_prefill_impl_before_jit_chunk_impl"] == pytest.approx(50 * us)
    assert len(hand["device_ops"]) <= 10 and len(hand["idle_gaps"]) <= 10


def test_no_device_operation_gives_nothing(tmp_path):
    path = tmp_path / "host_only.textproto"
    path.write_text(HAND[HAND.index('planes { id: 2'):])
    assert tr.reduce(tr.load(str(path))) is None


def test_clip_round_trips(tmp_path, hand):
    path = tmp_path / "hand.textproto"
    path.write_text(HAND)
    clipped = tmp_path / "clipped.textproto"
    clipped.write_text(tr.clip_text_proto(tr.load(str(path)), 0, 1e6, ("bench_window", "feed")))
    again = tr.reduce(tr.load(str(clipped)), ("feed", "step"))
    assert again["busy_s"] == pytest.approx(hand["busy_s"])
    assert again["kernel_s"] == pytest.approx(hand["kernel_s"])
    assert dict(again["idle_gaps"]) == pytest.approx(dict(hand["idle_gaps"]))


def test_recorded_trace():
    """12 ms from the backward pass of one GPT-2-small training step on a v5e
    (PR 23; names cut to `%name = opcode()` by clip_text_proto). busy_s and
    kernel_s were also counted by a plain sweep over the events and a sum
    over the custom calls; the figures pin the reduction."""
    path = os.path.join(DATA, "train_step.textproto")
    summary = tr.reduce(tr.load(path), ("feed", "step"))
    expected = RECORDED
    assert summary["chips"] == 1
    for key in ("window_s", "busy_s", "kernel_s"):
        assert summary[key] == pytest.approx(expected[key], rel=1e-6), key
    assert 0 < summary["kernel_s"] < summary["busy_s"] <= summary["window_s"]
    assert summary["device_ops"][0][0] == expected["top_op"]
    assert summary["collective_exposed_s"] == 0.0


RECORDED = {"window_s": 0.012, "busy_s": 0.011966289, "kernel_s": 0.007068447,
            "top_op": "all_fused_attention_grad"}
