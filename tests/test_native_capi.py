"""Linkable C inference API (VERDICT r4 item 3): a plain-C process links
libpaddle_tpu_infer.so (native/pjrt_runner/paddle_tpu_infer.h) and runs
an exported artifact — the reference's paddle_inference_api.h / capi
surface, TPU-form. Needs a TPU: the C process opens it through libtpu
(the `tpu_plugin` fixture), this process stays on the CPU."""

import os
import subprocess
import tempfile

import numpy as np

import paddle_tpu as pt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_capi_smoke(plugin, work, art_dir, in_bins):
    """Build the native stack in `work` and run capi_smoke on `art_dir`
    against the PJRT plug-in; returns the CompletedProcess."""
    subprocess.run(["sh", os.path.join(REPO, "native/pjrt_runner/build.sh"),
                    work], check=True, capture_output=True)
    return subprocess.run(
        [os.path.join(work, "capi_smoke"), plugin, art_dir, *in_bins],
        capture_output=True, text=True, timeout=300)


def test_c_smoke_links_and_matches_python(tpu_plugin):
    rng = np.random.RandomState(0)
    main, startup = pt.Program(), pt.Program()
    with pt.unique_name_guard(), pt.program_guard(main, startup):
        x = pt.layers.data("x", [12])
        h = pt.layers.fc(x, 16, act="relu")
        out = pt.layers.fc(h, 5, act="softmax")

    work = tempfile.mkdtemp()
    model_dir = os.path.join(work, "model")
    art_dir = os.path.join(work, "artifact")
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        os.makedirs(model_dir, exist_ok=True)
        pt.io.save_inference_model(model_dir, ["x"], [out], exe,
                                   main_program=main)
        xv = rng.rand(4, 12).astype("f")
        expected, = exe.run(main.clone(for_test=True), feed={"x": xv},
                            fetch_list=[out])

    pt.inference.export_native(model_dir, art_dir, batch_size=4)
    xv.tofile(os.path.join(art_dir, "in0.bin"))

    r = _run_capi_smoke(tpu_plugin, work, art_dir,
                        [os.path.join(art_dir, "in0.bin")])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "CAPI-OK" in r.stdout
    # the C consumer saw the right surface
    assert "inputs=1 outputs=1" in r.stdout
    first = float(np.asarray(expected).reshape(-1)[0])
    got = float(r.stdout.split("out0 first=")[1].split()[0])
    assert abs(got - first) < 1e-4, (got, first)


def test_external_params_artifact_matches_python(tpu_plugin):
    """export_native(external_params=True): weight-free module +
    param<i>.bin files staged once at PTI_Create — the big-model serving
    format. Output must equal the Python predictor."""
    rng = np.random.RandomState(1)
    main, startup = pt.Program(), pt.Program()
    with pt.unique_name_guard(), pt.program_guard(main, startup):
        x = pt.layers.data("x", [10])
        h = pt.layers.fc(x, 24, act="relu")
        out = pt.layers.fc(h, 6)

    work = tempfile.mkdtemp()
    model_dir = os.path.join(work, "model")
    art_dir = os.path.join(work, "artifact")
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        os.makedirs(model_dir, exist_ok=True)
        pt.io.save_inference_model(model_dir, ["x"], [out], exe,
                                   main_program=main)
        xv = rng.rand(3, 10).astype("f")
        expected, = exe.run(main.clone(for_test=True), feed={"x": xv},
                            fetch_list=[out])

    pt.inference.export_native(model_dir, art_dir, batch_size=3,
                               external_params=True)
    import json
    man = json.load(open(os.path.join(art_dir, "manifest.json")))
    assert len(man["params"]) == 4  # 2 weights + 2 biases
    xv.tofile(os.path.join(art_dir, "in0.bin"))

    r = _run_capi_smoke(tpu_plugin, work, art_dir,
                        [os.path.join(art_dir, "in0.bin")])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "CAPI-OK" in r.stdout
    first = float(np.asarray(expected).reshape(-1)[0])
    got = float(r.stdout.split("out0 first=")[1].split()[0])
    assert abs(got - first) < 1e-4, (got, first)
