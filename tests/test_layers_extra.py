"""Layer wrappers for the long-tail ops (layers/nn_extra.py) exercised
through full programs (build -> infer shapes -> jit -> run)."""

import numpy as np
import pytest

import paddle_tpu as pt


def _run(build, feed):
    main, startup = pt.Program(), pt.Program()
    with pt.unique_name_guard(), pt.program_guard(main, startup):
        fetches = build()
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        return [np.asarray(v) for v in
                exe.run(main, feed=feed, fetch_list=list(fetches))]


def test_activations_and_shuffles():
    x = np.random.RandomState(0).randn(2, 4, 4, 4).astype("f")

    def build():
        xv = pt.layers.data("x", [4, 4, 4])
        outs = [pt.layers.relu6(xv), pt.layers.brelu(xv),
                pt.layers.hard_swish(xv), pt.layers.stanh(xv),
                pt.layers.selu(xv),
                pt.layers.shuffle_channel(xv, group=2),
                pt.layers.space_to_depth(xv, 2)]
        return outs

    o = _run(build, {"x": x})
    np.testing.assert_allclose(o[0], np.clip(x, 0, 6), rtol=1e-6)
    assert o[5].shape == x.shape
    assert o[6].shape == (2, 16, 2, 2)


def test_l2_normalize_and_maxout():
    x = np.random.RandomState(1).randn(3, 8).astype("f")

    def build():
        xv = pt.layers.data("x", [8])
        return [pt.layers.l2_normalize(xv, axis=1),
                pt.layers.maxout(pt.layers.reshape(xv, [-1, 8, 1, 1]), 2)]

    n, mo = _run(build, {"x": x})
    np.testing.assert_allclose(
        n, x / np.linalg.norm(x, axis=1, keepdims=True), rtol=1e-4)
    assert mo.shape == (3, 4, 1, 1)


def test_rank_losses():
    rng = np.random.RandomState(2)
    lab = (rng.rand(4, 1) > 0.5).astype("f")
    left = rng.rand(4, 1).astype("f")
    right = rng.rand(4, 1).astype("f")

    def build():
        lv = pt.layers.data("l", [1])
        a = pt.layers.data("a", [1])
        b = pt.layers.data("b", [1])
        return [pt.layers.rank_loss(lv, a, b),
                pt.layers.margin_rank_loss(lv, a, b, margin=0.1)]

    r, m = _run(build, {"l": lab, "a": left, "b": right})
    assert np.isfinite(r).all() and np.isfinite(m).all()


def test_center_loss_trains():
    rng = np.random.RandomState(3)

    def build():
        x = pt.layers.data("x", [8])
        y = pt.layers.data("y", [1], dtype="int64")
        feat = pt.layers.fc(x, 4)
        loss = pt.layers.mean(
            pt.layers.center_loss(feat, y, num_classes=3, alpha=0.1))
        pt.optimizer.SGD(0.1).minimize(loss)
        return [loss]

    main, startup = pt.Program(), pt.Program()
    with pt.unique_name_guard(), pt.program_guard(main, startup):
        fetches = None
        x = pt.layers.data("x", [8])
        y = pt.layers.data("y", [1], dtype="int64")
        feat = pt.layers.fc(x, 4)
        loss = pt.layers.mean(
            pt.layers.center_loss(feat, y, num_classes=3, alpha=0.1))
        pt.optimizer.SGD(0.1).minimize(loss)
    exe = pt.Executor()
    feed = {"x": rng.randn(6, 8).astype("f"),
            "y": rng.randint(0, 3, (6, 1)).astype("i8")}
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        ls = [float(np.ravel(exe.run(main, feed=feed,
                                     fetch_list=[loss])[0])[0])
              for _ in range(10)]
    assert ls[-1] < ls[0]


def test_sampled_softmax_trains():
    rng = np.random.RandomState(4)
    V = 50

    def build_and_train():
        main, startup = pt.Program(), pt.Program()
        with pt.unique_name_guard(), pt.program_guard(main, startup):
            x = pt.layers.data("x", [16])
            y = pt.layers.data("y", [1], dtype="int64")
            logits = pt.layers.fc(x, V)
            loss = pt.layers.mean(
                pt.layers.sampled_softmax_with_cross_entropy(
                    logits, y, num_samples=8))
            pt.optimizer.Adam(5e-3).minimize(loss)
        exe = pt.Executor()
        feed = {"x": rng.randn(8, 16).astype("f"),
                "y": rng.randint(0, V, (8, 1)).astype("i8")}
        with pt.scope_guard(pt.Scope()):
            exe.run(startup)
            return [float(np.ravel(exe.run(main, feed=feed,
                                           fetch_list=[loss])[0])[0])
                    for _ in range(15)]

    ls = build_and_train()
    assert np.isfinite(ls).all()
    assert ls[-1] < ls[0]


def test_dice_loss_builds_and_computes():
    def build():
        x = pt.layers.data("x", [3, 4], append_batch_size=False)
        l = pt.layers.data("l", [3, 1], dtype="int64",
                           append_batch_size=False)
        sm = pt.layers.softmax(x)
        return [pt.layers.dice_loss(sm, l)]

    rng = np.random.RandomState(7)
    out, = _run(build, {"x": rng.randn(3, 4).astype("f"),
                        "l": rng.randint(0, 4, (3, 1)).astype("i8")})
    assert out.shape[0] == 3
    assert np.isfinite(out).all()
    assert ((out >= 0) & (out <= 1)).all()


def test_autoincreased_step_counter():
    main, startup = pt.Program(), pt.Program()
    with pt.unique_name_guard(), pt.program_guard(main, startup):
        counter = pt.layers.autoincreased_step_counter(begin=1, step=1)
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        vals = [int(np.ravel(exe.run(main, feed={},
                                     fetch_list=[counter])[0])[0])
                for _ in range(3)]
    # increments IN PLACE across runs; first read returns `begin`
    assert vals == [1, 2, 3], vals


def test_image_resize_and_grid():
    x = np.random.RandomState(5).rand(1, 2, 4, 4).astype("f")

    def build():
        xv = pt.layers.data("x", [2, 4, 4])
        up = pt.layers.resize_bilinear(xv, out_shape=[8, 8])
        theta = pt.layers.fill_constant([1, 2, 3], "float32", 0.0)
        # identity affine via assign_value-free route: use eye rows
        return [up]

    up, = _run(build, {"x": x})
    assert up.shape == (1, 2, 8, 8)


def test_edit_distance_layer():
    def build():
        h = pt.layers.data("h", [4], dtype="int64",
                           append_batch_size=True)
        r = pt.layers.data("r", [4], dtype="int64",
                           append_batch_size=True)
        d, cnt = pt.layers.edit_distance(h, r, normalized=False)
        return [d, cnt]

    d, cnt = _run(build, {"h": np.array([[1, 2, 3, 4]], "i8"),
                          "r": np.array([[1, 3, 3, 4]], "i8")})
    assert float(d[0, 0]) == 1.0


def test_unique_with_counts_layer():
    def build():
        x = pt.layers.data("x", [6], dtype="int32",
                           append_batch_size=False)
        out, idx, cnt = pt.layers.unique_with_counts(x)
        return [out, idx, cnt]

    out, idx, cnt = _run(build, {"x": np.array([5, 2, 5, 1, 2, 5], "i4")})
    uniq = out[:3]
    np.testing.assert_array_equal(sorted(uniq.tolist()), [1, 2, 5])
    np.testing.assert_array_equal(out[idx],
                                  np.array([5, 2, 5, 1, 2, 5]))


def test_mean_iou_layer():
    def build():
        p = pt.layers.data("p", [4], dtype="int32",
                           append_batch_size=False)
        l = pt.layers.data("l", [4], dtype="int32",
                           append_batch_size=False)
        miou, wrong, correct = pt.layers.mean_iou(p, l, 3)
        return [miou]

    miou, = _run(build, {"p": np.array([0, 1, 1, 2], "i4"),
                         "l": np.array([0, 1, 2, 2], "i4")})
    assert np.isclose(float(miou[0]), 2 / 3, atol=1e-6)


def test_dynamic_lstmp_layer():
    rng = np.random.RandomState(6)

    def build():
        x = pt.layers.data("x", [5, 16], append_batch_size=True)
        proj, cell = pt.layers.dynamic_lstmp(x, size=16, proj_size=3)
        return [proj, cell]

    proj, cell = _run(build, {"x": rng.randn(2, 5, 16).astype("f")})
    assert proj.shape == (2, 5, 3)
    assert cell.shape == (2, 5, 4)


def test_ctc_greedy_decoder_layer():
    probs = np.zeros((1, 6, 4), "f")
    # argmax path: 1 1 0 2 2 3 -> decoded 1 2 3
    path = [1, 1, 0, 2, 2, 3]
    for t, c in enumerate(path):
        probs[0, t, c] = 1.0

    def build():
        x = pt.layers.data("x", [6, 4])
        out, ln = pt.layers.ctc_greedy_decoder(x, blank=0)
        return [out, ln]

    out, ln = _run(build, {"x": probs})
    np.testing.assert_array_equal(out[0, :3], [1, 2, 3])
    assert int(ln[0, 0]) == 3


if __name__ == "__main__":
    import sys
    sys.exit(pytest.main([__file__, "-x", "-q"]))


def test_beam_search_on_device_matches_host_loop():
    """The single-jit on-device beam decode (lax.fori_loop + gather_tree)
    must reproduce the host-loop reference (weak-spot fix: each host-loop
    step pays a host round trip; on-device pays one dispatch)."""
    import jax.numpy as jnp
    from paddle_tpu.layers import decode

    V, B, K, L = 7, 2, 3, 5
    rng = np.random.RandomState(0)
    table = rng.randn(V, V).astype("f") * 2  # markov next-token logits

    def host_step(tokens):
        last = np.asarray(tokens)[:, -1]
        return table[last]

    def dev_step(tokens, t):
        last = jnp.take_along_axis(
            tokens, jnp.full((tokens.shape[0], 1), t), axis=1)[:, 0]
        return jnp.asarray(table)[last]

    for lp in (0.0, 0.6):
        hs, hsc = decode.beam_search_decode(
            host_step, B, K, bos_id=1, eos_id=0, max_len=L,
            length_penalty=lp)
        ds, dsc = decode.beam_search_decode_on_device(
            dev_step, B, K, bos_id=1, eos_id=0, max_len=L,
            length_penalty=lp)
        np.testing.assert_array_equal(hs, ds)
        # scores: f32 on-device log_softmax vs the host loop's f64 numpy
        np.testing.assert_allclose(hsc, dsc, rtol=1e-4, atol=1e-4)
