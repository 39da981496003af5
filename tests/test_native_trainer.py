"""C++ PJRT standalone TRAINING loop (native/pjrt_runner/pjrt_trainer.cc).

Reference: paddle/fluid/train/demo/demo_trainer.cc — train without
Python. Here: inference.export_train_step() writes the whole train step
(fwd+bwd+Adam, params donated) as StableHLO; the C++ trainer loops it
with the carry kept on device. The loss curve must follow the Python
Executor's: the same computation, compiled here for the CPU and there
for the chip (one process per chip, so the two sides cannot share a
backend and the bits may differ in the last places)."""

import json
import os
import subprocess
import tempfile

import numpy as np

import paddle_tpu as pt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEPS = 5


def _build():
    main, startup = pt.Program(), pt.Program()
    with pt.unique_name_guard(), pt.program_guard(main, startup):
        x = pt.layers.data("x", [8])
        label = pt.layers.data("label", [1], dtype="int64")
        h = pt.layers.fc(x, 16, act="relu")
        logits = pt.layers.fc(h, 4)
        loss = pt.layers.mean(
            pt.layers.softmax_with_cross_entropy(logits, label))
        pt.optimizer.Adam(1e-2).minimize(loss)
    main.random_seed = startup.random_seed = 5
    return main, startup, loss


def _feed():
    rng = np.random.RandomState(0)
    return {"x": rng.randn(8, 8).astype(np.float32),
            "label": rng.randint(0, 4, (8, 1)).astype(np.int64)}


def test_native_trainer_matches_python(tpu_plugin):
    main, startup, loss = _build()
    feed = _feed()

    work = tempfile.mkdtemp()
    art = os.path.join(work, "train_artifact")
    pt.inference.export_train_step(art, main, startup, feed, [loss])

    # Python trajectory through the normal Executor path
    exe = pt.Executor()
    py_losses = []
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        for _ in range(STEPS):
            lv, = exe.run(main, feed=feed, fetch_list=[loss])
            py_losses.append(float(np.ravel(lv)[0]))

    # C++ trajectory on the chip
    trainer = os.path.join(work, "pjrt_trainer")
    subprocess.run(["sh", os.path.join(REPO, "native/pjrt_runner/build.sh"),
                    work], check=True, capture_output=True)
    proc = subprocess.run(
        [trainer, tpu_plugin, art, str(STEPS)],
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    cpp_losses = json.load(open(os.path.join(art, "losses.json")))

    assert len(cpp_losses) == STEPS
    # float32 at highest matmul precision on both sides (conftest): the
    # backends differ in summation order and transcendentals only
    np.testing.assert_allclose(
        np.asarray(cpp_losses, np.float32),
        np.asarray(py_losses, np.float32), rtol=1e-4,
        err_msg="C++ train loop diverged from the Python executor")


def test_export_train_step_artifact_shape():
    """Backend-independent artifact check: manifest lists the donated
    carry (params + opt state + rng), the loss output, and input bins of
    the right size."""
    main, startup, loss = _build()
    feed = _feed()
    work = tempfile.mkdtemp()
    art = os.path.join(work, "a")
    pt.inference.export_train_step(art, main, startup, feed, [loss])
    m = json.load(open(os.path.join(art, "manifest.json")))
    names = [i["name"] for i in m["inputs"]]
    assert "rng" in names
    n_state = sum(1 for n in names if n.startswith("state:"))
    # 2 fc layers: w+b each, Adam: 2 moments + 2 beta-pows each => 4 params
    # + 16 opt-state tensors + lr var maybe; at minimum params+moments
    assert n_state >= 12, names
    assert any(n.startswith("feed:x") for n in names)
    assert len(m["carry"]) == n_state + 1          # states + rng
    assert len(m["loss_outputs"]) == 1
    for i, meta in enumerate(m["inputs"]):
        path = os.path.join(art, f"in{i}.bin")
        want = np.dtype(meta["dtype"]).itemsize * int(
            np.prod(meta["shape"] or [1]))
        assert os.path.getsize(path) == want, (i, meta)
    # the exported module carries the donation aliases
    mlir = open(os.path.join(art, "model.mlir")).read()
    assert "tf.aliasing_output" in mlir or "jax.buffer_donor" in mlir, \
        "no donation aliases in exported module"


if __name__ == "__main__":
    test_export_train_step_artifact_shape()
    test_native_trainer_matches_python()
    print("PASS")
