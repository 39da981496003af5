"""C++ PJRT standalone runner round trip (native/pjrt_runner).

Reference: paddle/fluid/train/demo + inference/api — serving without
Python. Here: export_native() writes StableHLO + CompileOptions +
manifest; the C++ runner dlopens a PJRT C-API plugin, compiles, and
executes. The test round-trips a trained model through libtpu on the
chip (the `tpu_plugin` fixture; this process stays on the CPU) and
requires numerical equality with the Python predictor.
"""

import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

import paddle_tpu as pt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_native_runner_matches_python(tpu_plugin):
    rng = np.random.RandomState(0)
    main, startup = pt.Program(), pt.Program()
    with pt.unique_name_guard(), pt.program_guard(main, startup):
        img = pt.layers.data("img", [1, 8, 8])
        label = pt.layers.data("label", [1], dtype="int64")
        h = pt.layers.conv2d(img, 4, 3, padding=1, act="relu")
        h = pt.layers.pool2d(h, 2, "max", 2)
        logits = pt.layers.fc(h, size=3)
        loss = pt.layers.mean(
            pt.layers.softmax_with_cross_entropy(logits, label))
        pt.optimizer.Adam(5e-3).minimize(loss)

    work = tempfile.mkdtemp()
    model_dir = os.path.join(work, "model")
    art_dir = os.path.join(work, "artifact")
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        for _ in range(10):
            exe.run(main,
                    feed={"img": rng.rand(8, 1, 8, 8).astype("f"),
                          "label": rng.randint(0, 3, (8, 1)).astype("i8")},
                    fetch_list=[loss])
        os.makedirs(model_dir, exist_ok=True)
        pt.io.save_inference_model(model_dir, ["img"], [logits], exe,
                                   main_program=main)

    pt.inference.export_native(model_dir, art_dir, batch_size=2)
    x = rng.rand(2, 1, 8, 8).astype("f")
    x.tofile(os.path.join(art_dir, "in0.bin"))

    cfg = pt.inference.Config(model_dir)
    expected = np.asarray(
        pt.inference.create_predictor(cfg).run({"img": x})[0])

    # build + run the C++ loop (no Python in the serving path)
    runner = os.path.join(work, "pjrt_runner")
    subprocess.run(["sh", os.path.join(REPO, "native/pjrt_runner/build.sh"),
                    work], check=True, capture_output=True)
    r = subprocess.run(
        [runner, tpu_plugin, art_dir, os.path.join(art_dir, "in0.bin")],
        capture_output=True, text=True, timeout=280)
    assert r.returncode == 0, f"runner failed: {r.stderr}\n{r.stdout}"
    assert "OK" in r.stdout, r.stdout

    got = np.fromfile(os.path.join(art_dir, "out0.bin"),
                      np.float32).reshape(expected.shape)
    np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-6)


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-x", "-q"]))
