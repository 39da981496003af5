"""softmax / cross-entropy family op tests
(reference: test_softmax_op.py, test_softmax_with_cross_entropy_op.py)."""

import numpy as np

from op_test import OpTest


def _rand(*shape, seed=41):
    return np.random.RandomState(seed).uniform(-1, 1, shape).astype("f")


def softmax_np(x, axis=-1):
    e = np.exp(x - x.max(axis=axis, keepdims=True))
    return e / e.sum(axis=axis, keepdims=True)


class TestSoftmax(OpTest):
    op_type = "softmax"

    def setUp(self):
        x = _rand(4, 7)
        self.inputs = {"X": x}
        self.outputs = {"Out": softmax_np(x)}
        self.attrs = {"axis": -1}

    def test_output(self):
        self.check_output(atol=1e-5)

    def test_grad(self):
        self.check_grad(["X_in"], "Out_out", max_relative_error=0.02)


class TestSoftmaxAxis(OpTest):
    op_type = "softmax"

    def setUp(self):
        x = _rand(3, 5, 4, seed=42)
        self.inputs = {"X": x}
        self.outputs = {"Out": softmax_np(x, axis=1)}
        self.attrs = {"axis": 1}

    def test_output(self):
        self.check_output(atol=1e-5)


class TestSoftmaxWithCrossEntropy(OpTest):
    op_type = "softmax_with_cross_entropy"

    def setUp(self):
        logits = _rand(5, 7, seed=43)
        label = np.random.RandomState(44).randint(0, 7, (5, 1)).astype(
            np.int64)
        sm = softmax_np(logits)
        loss = -np.log(sm[np.arange(5), label[:, 0]])[:, None]
        self.inputs = {"Logits": logits, "Label": label}
        self.outputs = {"Softmax": sm, "Loss": loss}
        self.attrs = {"soft_label": False}

    def test_output(self):
        self.check_output(atol=1e-5)

    def test_grad(self):
        self.check_grad(["Logits_in"], "Loss_out",
                        max_relative_error=0.02)


class TestSoftmaxWithCrossEntropySoftLabel(OpTest):
    op_type = "softmax_with_cross_entropy"

    def setUp(self):
        logits = _rand(5, 7, seed=45)
        label = softmax_np(_rand(5, 7, seed=46))
        sm = softmax_np(logits)
        loss = -(label * np.log(sm)).sum(axis=1, keepdims=True)
        self.inputs = {"Logits": logits, "Label": label}
        self.outputs = {"Softmax": sm, "Loss": loss}
        self.attrs = {"soft_label": True}

    def test_output(self):
        self.check_output(atol=1e-5)

    def test_grad(self):
        self.check_grad(["Logits_in"], "Loss_out",
                        max_relative_error=0.02)


class TestCrossEntropy(OpTest):
    op_type = "cross_entropy"

    def setUp(self):
        x = softmax_np(_rand(5, 6, seed=47))
        label = np.random.RandomState(48).randint(0, 6, (5, 1)).astype(
            np.int64)
        loss = -np.log(x[np.arange(5), label[:, 0]])[:, None]
        self.inputs = {"X": x, "Label": label}
        self.outputs = {"Y": loss}
        self.attrs = {"soft_label": False}

    def test_output(self):
        self.check_output(atol=1e-5)

    def test_grad(self):
        self.check_grad(["X_in"], "Y_out", max_relative_error=0.02)


class TestSigmoidCrossEntropyWithLogits(OpTest):
    op_type = "sigmoid_cross_entropy_with_logits"

    def setUp(self):
        x = _rand(4, 5, seed=49)
        label = np.random.RandomState(50).randint(0, 2, (4, 5)).astype("f")
        loss = np.maximum(x, 0) - x * label + np.log1p(np.exp(-np.abs(x)))
        self.inputs = {"X": x, "Label": label}
        self.outputs = {"Out": loss}

    def test_output(self):
        self.check_output(atol=1e-5)

    def test_grad(self):
        self.check_grad(["X_in"], "Out_out", max_relative_error=0.02)


class TestSquareErrorCost(OpTest):
    op_type = "square_error_cost"

    def setUp(self):
        x = _rand(4, 3, seed=51)
        y = _rand(4, 3, seed=52)
        self.inputs = {"X": x, "Label": y}
        self.outputs = {"Out": (x - y) ** 2}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["X_in"], "Out_out")


class TestAccuracy(OpTest):
    op_type = "accuracy"

    def setUp(self):
        rng = np.random.RandomState(53)
        vals = rng.uniform(0, 1, (6, 3)).astype("f")
        idx = rng.randint(0, 10, (6, 3)).astype(np.int64)
        label = idx[:, 1:2].copy()
        label[0] = (idx[0, 0] + idx[0, 1] + idx[0, 2] + 1) % 10  # miss
        correct = sum(1 for i in range(6) if label[i, 0] in idx[i])
        self.inputs = {"Out": vals, "Indices": idx, "Label": label}
        self.outputs = {"Accuracy": np.array([correct / 6.0], "f"),
                        "Correct": np.array([correct], np.int32),
                        "Total": np.array([6], np.int32)}

    def test_output(self):
        self.check_output()


# ---------------------------------------------------------------------------
# PR 51: the loss op saves a row's log-sum-exp, and its gradient rebuilds
# the probabilities from the logits
# ---------------------------------------------------------------------------

import pytest

import paddle_tpu as pt
from paddle_tpu.framework.core import NAMESCOPE_ATTR, grad_var_name
from paddle_tpu.observability.metrics import get_registry

LSE_COUNTER = "loss_lse_lowerings_total"
KEPT_COUNTER = "loss_softmax_kept_lowerings_total"


def _counters():
    reg = get_registry()
    return (int(reg.counter(LSE_COUNTER).value),
            int(reg.counter(KEPT_COUNTER).value))


def _lowered_since(before):
    """(log-sum-exp lowerings, kept-softmax lowerings) since `before`."""
    after = _counters()
    return after[0] - before[0], after[1] - before[1]


def _xent_case(case):
    """(logits, label, attrs, aux weight) of one gradient case; a row's
    loss is weighted so that no symmetry hides a wrong gradient."""
    rng = np.random.RandomState(510 + len(case))
    shape, axis = ((3, 7, 5), 1) if case == "axis" else ((6, 11), -1)
    logits = (3.0 * rng.randn(*shape)).astype("f")
    classes = shape[axis]
    lab_shape = list(shape)
    lab_shape[axis] = 1
    label = rng.randint(0, classes, lab_shape).astype(np.int64)
    attrs = {"axis": axis}
    if case == "ignore_index":
        attrs["ignore_index"] = 3
        label[0, 0], label[4, 0] = 3, 3
    if case == "soft_label":
        attrs["soft_label"] = True
        label = softmax_np(rng.randn(*shape).astype("f"), axis=axis)
    return logits, label, attrs, 0.3 if case == "aux" else 0.0


def _xent_reference(logits, label, attrs, weight, aux):
    """The float32 loss jax differentiates: log_softmax, the label's
    entry (0 where ignored), a weighted sum, and the aux term through
    the probabilities."""
    import jax
    import jax.numpy as jnp
    axis = attrs["axis"]

    def total(x):
        logp = jax.nn.log_softmax(x, axis=axis)
        if attrs.get("soft_label"):
            loss = -jnp.sum(label * logp, axis=axis, keepdims=True)
        else:
            loss = -jnp.take_along_axis(logp, jnp.asarray(label, jnp.int32),
                                        axis=axis)
            loss = jnp.where(label == attrs.get("ignore_index", -100),
                             0.0, loss)
        sm = jnp.exp(logp)
        return jnp.sum(loss * weight) + aux * jnp.sum(sm * sm)

    return total(jnp.asarray(logits)), jax.grad(total)(jnp.asarray(logits))


@pytest.mark.parametrize("case", ["hard", "ignore_index", "soft_label",
                                  "axis", "aux"])
def test_gradient_from_saved_lse_matches_float32_reference(case):
    logits, label, attrs, aux = _xent_case(case)
    weight = np.random.RandomState(7).uniform(
        0.5, 1.5, [1 if i == attrs["axis"] % logits.ndim else d
                   for i, d in enumerate(logits.shape)]).astype("f")
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = pt.layers.data("x", list(logits.shape), append_batch_size=False,
                           stop_gradient=False)
        y = pt.layers.data("y", list(label.shape), dtype=str(label.dtype),
                           append_batch_size=False)
        w = pt.layers.assign(weight)
        loss, sm = pt.layers.softmax_with_cross_entropy(
            x, y, return_softmax=True, **attrs)
        total = pt.layers.reduce_sum(loss * w)
        if aux:
            total = total + pt.layers.reduce_sum(sm * sm) * aux
        gx, = pt.gradients([total], [x])
    grad_op, = [op for op in main.global_block.ops
                if op.type == "softmax_with_cross_entropy_grad"]
    assert grad_op.input("Lse") and grad_op.input("Logits")
    assert not grad_op.input("Softmax")
    before = _counters()
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        got_total, got = exe.run(main, feed={"x": logits, "y": label},
                                 fetch_list=[total, gx])
    # the aux loss reads Softmax and sends Softmax@GRAD: that program
    # keeps the vocabulary-wide tensor, the others do not
    assert _lowered_since(before) == ((0, 1) if aux else (1, 0))
    want_total, want = _xent_reference(logits, label, attrs, weight, aux)
    np.testing.assert_allclose(np.asarray(got_total).reshape(()),
                               np.asarray(want_total), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_softmax_read_forward_only_counts_as_kept():
    """Another op that READS Softmax (a metric: no gradient through it)
    keeps its write; the gradient still rebuilds from the saved Lse."""
    logits, label, attrs, _ = _xent_case("hard")
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = pt.layers.data("x", list(logits.shape), append_batch_size=False,
                           stop_gradient=False)
        y = pt.layers.data("y", list(label.shape), dtype="int64",
                           append_batch_size=False)
        loss, sm = pt.layers.softmax_with_cross_entropy(
            x, y, return_softmax=True)
        sm.stop_gradient = True
        top = pt.layers.reduce_max(sm)
        gx, = pt.gradients([pt.layers.mean(loss)], [x])
    grad_op, = [op for op in main.global_block.ops
                if op.type == "softmax_with_cross_entropy_grad"]
    assert grad_op.input("Lse") and not grad_op.input("Softmax")
    before = _counters()
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        got, peak = exe.run(main, feed={"x": logits, "y": label},
                            fetch_list=[gx, top])
    assert _lowered_since(before) == (0, 1)
    sm_np = softmax_np(logits)
    onehot = np.eye(logits.shape[1], dtype="f")[label[:, 0]]
    np.testing.assert_allclose(np.asarray(got),
                               (sm_np - onehot) / logits.shape[0],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(peak).reshape(()), sm_np.max(),
                               rtol=1e-5)


@pytest.mark.parametrize("scale", [1.0, 8.0])
def test_bf16_gradient_no_further_from_reference_than_saved_softmax(scale):
    """Under AMP the logits are bfloat16. The gradient rebuilt from them
    and the float32 Lse is rounded ONCE; the older formula rounded the
    saved softmax to bfloat16 and then the difference again. Against the
    float32 gradient at the same (bfloat16-valued) logits the new one is
    at least as close, in the worst element and in the mean."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.framework.registry import LowerContext, get_op_def

    rng = np.random.RandomState(int(scale))
    rows, classes = 64, 1000
    logits = jnp.asarray(scale * rng.randn(rows, classes), jnp.bfloat16)
    label = jnp.asarray(rng.randint(0, classes, (rows, 1)), jnp.int32)
    g = jnp.asarray(rng.uniform(0.5, 1.5, (rows, 1)), jnp.float32)
    opdef = get_op_def("softmax_with_cross_entropy")
    ctx = LowerContext(abstract=True)      # abstract: counts nothing
    fwd = opdef.lower(ctx, {"Logits": [logits], "Label": [label]}, {})
    assert fwd["Lse"][0].dtype == jnp.float32
    assert fwd["Lse"][0].shape == fwd["Loss"][0].shape == (rows, 1)
    assert fwd["Softmax"][0].dtype == jnp.bfloat16
    new = opdef.grad_lower(
        ctx, {"Logits": [logits], "Lse": fwd["Lse"], "Label": [label],
              "Loss@GRAD": [g]}, {})["Logits@GRAD"][0]
    old = opdef.grad_lower(
        ctx, {"Softmax": fwd["Softmax"], "Label": [label],
              "Loss@GRAD": [g]}, {})["Logits@GRAD"][0]
    assert new.dtype == old.dtype == jnp.bfloat16
    x32 = logits.astype(jnp.float32)
    want = jax.grad(lambda x: jnp.sum(g * -jnp.take_along_axis(
        jax.nn.log_softmax(x), label, axis=1)))(x32)
    err_new = np.abs(np.asarray(new.astype(jnp.float32) - want))
    err_old = np.abs(np.asarray(old.astype(jnp.float32) - want))
    assert err_new.max() <= err_old.max()
    assert err_new.mean() <= err_old.mean()
    # one rounding of a float32 value: half a bfloat16 ulp, 2**-9 relative
    assert np.all(err_new <= np.abs(np.asarray(want)) * 2.0 ** -8 + 1e-30)


def _tiny_gpt(amp, seq=8, **kw):
    from paddle_tpu.models.gpt import GPTConfig, gpt_lm_program
    cfg = GPTConfig(vocab_size=61, hidden=32, layers=2, heads=2,
                    max_pos=seq, dropout=0.0)
    return cfg, gpt_lm_program(cfg, seq, amp=amp, **kw)


def test_gpt_program_saves_lse_and_moves_no_vocabulary_wide_tensor():
    """Structure of the cells' program: the loss op reads the head's
    (b, s, V) logits as they lie and saves a float32 Lse in the shape of
    Loss; the grad op reads those logits, that Lse and no Softmax;
    nothing under `loss` makes a (b, s - 1, V) tensor, forward or
    backward; the float32 per-position loss reaches the float32 mean
    through the slice with no cast; one lowering a compile, counted as
    the log-sum-exp kind."""
    seq = 8
    cfg, (main, startup, fetches) = _tiny_gpt(amp=True, seq=seq)
    blk = main.global_block
    xent, = [op for op in blk.ops if op.type == "softmax_with_cross_entropy"]
    grad, = [op for op in blk.ops
             if op.type == "softmax_with_cross_entropy_grad"]
    assert xent.input("Logits") == [fetches["logits"].name]
    assert blk.var(xent.input("Logits")[0]).dtype == "bfloat16"
    assert blk.var(xent.output("Lse")[0]).dtype == "float32"
    assert blk.var(xent.output("Loss")[0]).dtype == "float32"
    assert blk.var(xent.output("Lse")[0]).shape == (-1, seq, 1)
    assert grad.input("Lse") == xent.output("Lse")
    assert grad.input("Logits") == xent.input("Logits")
    assert not grad.input("Softmax")
    assert grad.attrs["softmax_read"] is False
    under_loss = [op for op in blk.ops
                  if op.attrs.get(NAMESCOPE_ATTR, "").startswith("loss")]
    assert {"softmax_with_cross_entropy", "softmax_with_cross_entropy_grad",
            "slice", "slice_grad", "mean"} <= {op.type for op in under_loss}
    for op in under_loss:
        for name in op.output_names():
            if name and blk.has_var(name):
                shape = tuple(blk.var(name).shape or ())
                assert shape[-2:] != (seq - 1, cfg.vocab_size), (op, name)
    sliced, = [op for op in under_loss if op.type == "slice"]
    mean, = [op for op in under_loss if op.type == "mean"]
    assert sliced.input("Input") == xent.output("Loss")
    assert mean.input("X") == sliced.output("Out")
    assert blk.var(sliced.output("Out")[0]).dtype == "float32"
    before = _counters()
    exe = pt.Executor()
    tokens = np.random.RandomState(3).randint(
        0, cfg.vocab_size, (4, seq)).astype(np.int64)
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        first, = exe.run(main, feed={"tokens": tokens},
                         fetch_list=[fetches["loss"]])
        second, = exe.run(main, feed={"tokens": tokens},
                          fetch_list=[fetches["loss"]])
    assert _lowered_since(before) == (1, 0)
    assert np.isfinite(first).all() and second[0] < first[0]


def test_gpt_shifted_loss_equals_sliced_logits_formulation():
    """float32: the loss over the whole (b, s, V) logits with the LOSS cut
    to s - 1 equals log_softmax over logits[:, :-1] against tokens[:, 1:]
    (the program before PR 51) to 1e-6 relative, so does its gradient on
    those rows, and the last position's logits get a gradient of exactly
    zero."""
    import jax
    import jax.numpy as jnp
    seq = 8
    cfg, (main, startup, fetches) = _tiny_gpt(amp=False, seq=seq)
    d_logits = main.global_block.var(grad_var_name(fetches["logits"].name))
    tokens = np.random.RandomState(5).randint(
        0, cfg.vocab_size, (3, seq)).astype(np.int64)
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        loss, logits, got = exe.run(
            main, feed={"tokens": tokens},
            fetch_list=[fetches["loss"], fetches["logits"], d_logits])

    def sliced(x):
        logp = jax.nn.log_softmax(x[:, :-1])
        picked = jnp.take_along_axis(
            logp, jnp.asarray(tokens[:, 1:, None], jnp.int32), axis=-1)
        return -jnp.mean(picked)

    want_loss, want = jax.value_and_grad(sliced)(jnp.asarray(logits))
    np.testing.assert_allclose(np.asarray(loss).reshape(()),
                               np.asarray(want_loss), rtol=1e-6)
    got = np.asarray(got)
    assert got.shape == (3, seq, cfg.vocab_size)
    assert np.all(got[:, -1] == 0.0)
    assert np.abs(got[:, :-1]).max() > 0.0
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5, atol=1e-8)


def test_forward_op_without_lse_output_keeps_the_saved_softmax_desc():
    """An op desc from before the saved output (built by hand, or loaded)
    still differentiates: its grad op reads the saved Softmax, counted as
    a kept softmax."""
    logits, label, _, _ = _xent_case("hard")
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        blk = main.global_block
        x = pt.layers.data("x", list(logits.shape), append_batch_size=False,
                           stop_gradient=False)
        y = pt.layers.data("y", list(label.shape), dtype="int64",
                           append_batch_size=False)
        blk.create_var(name="sm"), blk.create_var(name="ce")
        blk.append_op("softmax_with_cross_entropy",
                      {"Logits": [x.name], "Label": [y.name]},
                      {"Softmax": ["sm"], "Loss": ["ce"]}, {})
        gx, = pt.gradients([pt.layers.mean(blk.var("ce"))], [x])
    grad_op, = [op for op in blk.ops
                if op.type == "softmax_with_cross_entropy_grad"]
    assert grad_op.input("Softmax") == ["sm"] and not grad_op.input("Lse")
    before = _counters()
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        got, = exe.run(main, feed={"x": logits, "y": label},
                       fetch_list=[gx])
    assert _lowered_since(before) == (0, 1)
    onehot = np.eye(logits.shape[1], dtype="f")[label[:, 0]]
    np.testing.assert_allclose(np.asarray(got),
                               (softmax_np(logits) - onehot) / len(logits),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("layout", ["slice", "reshape", "squeeze"])
def test_amp_layout_op_does_not_round_the_float32_loss(layout):
    """rewrite_bf16: a white-listed op that only moves its input passes a
    loss the xent op emitted in float32 through as it is (no cast reads
    it, the moved values are the loss's own bits); the same op on an
    ordinary float32 activation still takes it in bfloat16."""
    from paddle_tpu.contrib.mixed_precision import rewrite_bf16
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = pt.layers.data("x", [6, 11], append_batch_size=False)
        y = pt.layers.data("y", [6, 1], dtype="int64",
                           append_batch_size=False)
        logits = pt.layers.fc(x, 11)
        loss = pt.layers.softmax_with_cross_entropy(logits, y)
        move = {"slice": lambda v: pt.layers.slice(v, [0], [0], [5]),
                "reshape": lambda v: pt.layers.reshape(v, [2, 3]),
                "squeeze": lambda v: pt.layers.squeeze(v, [1])}[layout]
        moved_loss = move(loss)
        moved_act = move(pt.layers.slice(x, [1], [0], [1]))
        total = pt.layers.mean(moved_loss) + pt.layers.mean(moved_act)
    rewrite_bf16(main)
    blk = main.global_block
    assert blk.var(loss.name).dtype == "float32"
    assert blk.var(moved_loss.name).dtype == "float32"
    assert blk.var(moved_act.name).dtype == "bfloat16"
    casts = {op.input("X")[0]: op.attrs["out_dtype"] for op in blk.ops
             if op.type == "cast"}
    assert loss.name not in casts and moved_loss.name not in casts
    assert casts[moved_act.name] == "float32"      # for the mean
    rng = np.random.RandomState(2)
    feed = {"x": rng.randn(6, 11).astype("f"),
            "y": rng.randint(0, 11, (6, 1)).astype(np.int64)}
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        per_row, moved = exe.run(main, feed=feed,
                                 fetch_list=[loss, moved_loss])
    per_row, moved = np.asarray(per_row), np.asarray(moved)
    assert moved.dtype == np.float32
    want = {"slice": per_row[:5], "reshape": per_row.reshape(2, 3),
            "squeeze": per_row[:, 0]}[layout]
    np.testing.assert_array_equal(moved, want)


def _bench_loss_op():
    import os
    import sys
    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    import bench_loss_op
    return bench_loss_op


@pytest.mark.parametrize("shift", ["loss", "logits"])
def test_bench_loss_op_stages_are_the_cells_head_and_loss(shift):
    """tools/bench_loss_op.py times the op's own lowerings between the
    head's product and its two backward products: the mean over the
    b x (s - 1) predicting positions, the hidden state's gradient that of
    the float32 formulation (bfloat16 products), exactly zero at the last
    position; it counts no lowering and takes no time from a CPU."""
    from paddle_tpu.framework.registry import get_op_def
    bench = _bench_loss_op()
    before = _counters()
    line = bench.measure(get_op_def("softmax_with_cross_entropy"),
                         bench.TINY, timed=False, shift=shift)
    assert _counters() == before
    assert line["loss_rel_gap"] <= 2e-3 and line["dh_rel_err"] <= 2e-2
    assert line["dh_last_position_max"] == 0.0 and line["dwte_abs_sum"] > 0
    assert "ms" not in line


def test_bench_loss_op_measures_on_a_chip_or_not_at_all(monkeypatch, capsys):
    import sys
    bench = _bench_loss_op()
    monkeypatch.setattr(sys, "argv", ["bench_loss_op.py"])
    assert bench.main() == 1
    out = capsys.readouterr()
    assert not out.out and "not 'tpu'" in out.err
