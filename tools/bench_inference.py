"""Inference perf rows (VERDICT r4 item 3): batch-1 latency + batched
throughput for BERT-base / GPT-2-small / ResNet-50 through the Python
Predictor (inference.create_predictor), in this one process. Usage:

    python tools/bench_inference.py [bert gpt2 resnet50]

Prints one JSON line per (model, batch), naming the device it ran on.
The native C++ runner is not timed here: it is its own process and
would need the chip this one holds (tests/test_native_capi.py drives
it alone).
"""

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def build_model(name):
    import paddle_tpu as pt
    main, startup = pt.Program(), pt.Program()
    with pt.unique_name_guard(), pt.program_guard(main, startup):
        if name == "bert":
            from paddle_tpu.models.bert import BertConfig, bert_encoder
            cfg = BertConfig()
            seq = 128
            src = pt.layers.data("src_ids", [seq], dtype="int64")
            sent = pt.layers.data("sent_ids", [seq], dtype="int64")
            mask = pt.layers.data("input_mask", [seq], dtype="float32")
            out = bert_encoder(src, sent, mask, cfg, is_test=True)
            feeds = ["src_ids", "sent_ids", "input_mask"]

            def feed_for(b, rng):
                return {
                    "src_ids": rng.randint(0, cfg.vocab_size,
                                           (b, seq)).astype(np.int64),
                    "sent_ids": rng.randint(0, 2, (b, seq)).astype(
                        np.int64),
                    "input_mask": np.ones((b, seq), np.float32),
                }
        elif name == "gpt2":
            from paddle_tpu.models.gpt import GPTConfig, gpt_decoder
            cfg = GPTConfig(dropout=0.0)
            seq = 128
            tokens = pt.layers.data("tokens", [seq], dtype="int64")
            out = gpt_decoder(tokens, cfg, is_test=True)
            feeds = ["tokens"]

            def feed_for(b, rng):
                return {"tokens": rng.randint(
                    0, cfg.vocab_size, (b, seq)).astype(np.int64)}
        else:
            from paddle_tpu.models.resnet import resnet
            img = pt.layers.data("img", [3, 224, 224], dtype="float32")
            out = resnet(img, depth=50, class_num=1000)
            feeds = ["img"]

            def feed_for(b, rng):
                return {"img": rng.rand(b, 3, 224, 224).astype(
                    np.float32)}
    return main, startup, out, feeds, feed_for


def bench_python(name, batches):
    import paddle_tpu as pt
    main, startup, out, feeds, feed_for = build_model(name)
    work = tempfile.mkdtemp()
    exe = pt.Executor()
    rows = []
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        pt.io.save_inference_model(work, feeds, [out], exe,
                                   main_program=main)
    pred = pt.inference.create_predictor(pt.inference.Config(work))
    rng = np.random.RandomState(0)
    for b in batches:
        feed = feed_for(b, rng)
        pred.run(feed)                      # compile + warm
        reps = 20 if b == 1 else 10
        t0 = time.perf_counter()
        for _ in range(reps):
            r = pred.run(feed)
        np.asarray(r[0])                    # wait for the last result
        dt = (time.perf_counter() - t0) / reps
        rows.append((b, dt))
    return rows


def main():
    from paddle_tpu.observability.device_peaks import device_report
    models = sys.argv[1:] or ["bert", "gpt2", "resnet50"]
    batches = {"bert": [1, 32], "gpt2": [1, 16], "resnet50": [1, 32]}
    device = device_report()
    for name in models:
        for b, dt in bench_python(name, batches[name]):
            print(json.dumps({
                "metric": f"{name}_infer_python_b{b}",
                "value": round(dt * 1e3, 2),
                "unit": "ms/batch (%.1f samples/s)" % (b / dt),
                "vs_baseline": None,
                "device": device,
            }), flush=True)


if __name__ == "__main__":
    main()
