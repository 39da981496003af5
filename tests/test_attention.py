"""Flash/ring/Ulysses attention tests (8-device CPU mesh from conftest).

Mirrors the reference's OpTest check_output/check_grad discipline
(op_test.py:689,:727) for the fused attention stack, plus a model-level
parity test: BERT with fused+context-parallel attention matches the einsum
attention graph.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as pt
from paddle_tpu.ops import flash_attention as fa
from paddle_tpu.ops.flash_attention import mha_reference, flash_attention
from paddle_tpu.parallel.ring import ring_attention, ulysses_attention


def _qkv(b=2, s=64, n=8, d=16, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, s, n, d).astype(np.float32))
    q, k, v = mk(), mk(), mk()
    bias_k = jnp.asarray(
        (rng.rand(b, s) > 0.9).astype(np.float32) * -1e4)
    return q, k, v, bias_k


@pytest.fixture(scope="module")
def mesh():
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:8]).reshape(8), ("cp",))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("with_bias", [False, True])
def test_flash_kernel_interpret(causal, with_bias):
    """Pallas kernel (interpret mode on CPU) vs XLA reference, fwd + grads."""
    q, k, v, bias_k = _qkv(b=1, s=128, n=2, d=32)
    bias4 = bias_k[:, None, None, :] if with_bias else None
    bk = bias4
    sm = 1.0 / np.sqrt(q.shape[-1])

    ref = mha_reference(q, k, v, bk, causal)
    out = flash_attention(q, k, v, bk, causal, sm, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

    g_ref = jax.grad(lambda *a: (mha_reference(*a, bk, causal) ** 2).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(
        lambda *a: (flash_attention(*a, bk, causal, sm, True) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)

    if with_bias:
        # learned-bias gradient through the flash backward kernel
        db_ref = jax.grad(
            lambda bb: (mha_reference(q, k, v, bb, causal) ** 2).sum())(bk)
        db_fl = jax.grad(
            lambda bb: (flash_attention(q, k, v, bb, causal,
                                        sm, True) ** 2).sum())(bk)
        np.testing.assert_allclose(np.asarray(db_fl), np.asarray(db_ref),
                                   atol=5e-4, rtol=5e-4)


# A bfloat16 gradient of the tiled backward against the float32 reference on
# the same rounded inputs, as a share of the reference's LARGEST entry.
# bfloat16 keeps 8 bits, so one rounding moves a value by at most 2**-9 of
# itself. Four roundings stand between the two gradients: the saved `o`
# behind delta, `p` and `ds` where they enter a product, and the result. Were
# all four aligned on the largest entry they would move it by 4 x 2**-9 =
# 2**-7; a sum whose terms are larger than the sum can lose as much again, so
# the limit is 2**-6 (1.6%). The interpreter reads 0.2-0.5% over these
# shapes; a backward that dropped a tile pair or a mask is off by tens of
# percent. `p` or `ds` COMPUTED in bfloat16 would still pass here: that is
# held by the float32 cases, which run the same code at 5e-5, and by
# `test_the_products_take_their_operands_type`.
BF16_REL_TOL = 2.0 ** -6

# (sq, sk): 1,024 at tiles of 512; 768 at tiles of 128; sq != sk; a length
# that is no multiple of its tile (1,000 rows are padded to 8 x 128)
TILED_SHAPES = [(1024, 1024), (768, 768), (512, 1024), (1000, 1000)]


def _tiled_inputs(sq, sk, d, bias, dtype, heads=2):
    rng = np.random.RandomState(sq + sk + d)
    mk = lambda s: jnp.asarray(
        rng.randn(1, s, heads, d).astype(np.float32)).astype(dtype)
    q, k, v = mk(sq), mk(sk), mk(sk)
    bias_k = None
    if bias:
        bias_k = jnp.asarray((rng.rand(1, sk) > 0.9).astype(np.float32)
                             * -1e4)[:, None, None, :]
    return q, k, v, bias_k


def _hold_tiled_backward(sq, sk, d, causal, bias, dtype, heads=2):
    """dq, dk, dv (and db) of `flash_attention`'s vjp, interpreted, against
    `jax.grad` of `mha_reference` in float32 on the same inputs."""
    q, k, v, bias_k = _tiled_inputs(sq, sk, d, bias, dtype, heads)
    sm = 1.0 / np.sqrt(d)
    f32 = lambda x: x.astype(jnp.float32)
    wrt = (0, 1, 2, 3) if bias else (0, 1, 2)
    want = jax.grad(
        lambda q, k, v, b: (mha_reference(q, k, v, b, causal) ** 2).sum(),
        argnums=wrt)(f32(q), f32(k), f32(v), bias_k)
    got = jax.grad(
        lambda q, k, v, b: (f32(flash_attention(q, k, v, b, causal, sm, True))
                            ** 2).sum(), argnums=wrt)(q, k, v, bias_k)
    for name, a, b in zip(("dq", "dk", "dv", "db"), got, want):
        assert a.dtype == (jnp.float32 if name == "db" else dtype)
        a, b = np.asarray(f32(a)), np.asarray(b)
        if dtype == jnp.float32:
            tol = 5e-4 if name == "db" else 5e-5
            np.testing.assert_allclose(a, b, atol=tol, rtol=tol, err_msg=name)
        else:
            assert np.abs(a - b).max() < BF16_REL_TOL * np.abs(b).max(), name


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("sq,sk", TILED_SHAPES)
@pytest.mark.parametrize("with_bias", [False, True])
@pytest.mark.parametrize("causal", [False, True])
def test_one_pass_backward_against_the_references_gradients(
        causal, with_bias, sq, sk, dtype):
    """The tiled backward (`_flash_bwd_call`: one Mosaic call that computes
    a tile pair's s, p, dp and ds once for dq, dk, dv and db)."""
    _hold_tiled_backward(sq, sk, 64, causal, with_bias, dtype)


def _eqns(jaxpr, name):
    """Every equation of primitive `name`, through sub-jaxprs and kernels."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _eqns(sub, name)
    return found


def _backward_jaxpr(heads, sq, sk, d, dtype, causal=True, bias=False):
    def shape(*dims, dtype=dtype):
        return jax.ShapeDtypeStruct(dims, dtype)

    tile = fa._pick_blocks(sq, sk)[0]      # the forward's: lse comes padded
    rows = -(-sq // tile) * tile
    return jax.make_jaxpr(
        lambda q, k, v, b, o, lse, do: fa._flash_bwd_call(
            q, k, v, b if bias else None, o, lse, do, causal, d ** -0.5,
            True))(
        shape(heads, sq, d), shape(heads, sk, d), shape(heads, sk, d),
        shape(heads, sk, dtype=jnp.float32), shape(heads, sq, d),
        shape(heads, rows, 128, dtype=jnp.float32), shape(heads, sq, d)).jaxpr


@pytest.mark.parametrize("rows,calls", [(3072, 1), (3584, 2)])
def test_the_dq_rule_on_each_side(rows, calls):
    """`backward_span_rows`: float32 rows of width 64 at tiles of 512 keep a
    head's whole float32 dq in VMEM up to 3,072 rows, ONE call; 3,584 rows
    are two spans (3,072 + 512), a call each over the keys its rows attend,
    dk, dv and db summed over them: the same gradients either way."""
    assert fa.backward_span_rows(rows, rows, 64, jnp.float32) == 3072
    jaxpr = _backward_jaxpr(1, rows, rows, 64, jnp.float32, bias=True)
    found = _eqns(jaxpr, "pallas_call")
    assert len(found) == calls
    assert {eqn.params["name"] for eqn in found} == {"flash_bwd"}
    _hold_tiled_backward(rows, rows, 64, True, True, jnp.float32, heads=1)


def test_the_dq_rule_is_a_function_of_rows_width_and_type():
    """What one call holds follows `_bwd_vmem_bytes` against Mosaic's grant:
    a step of 512 rows costs 512 x 128 lanes x (4 + 2 x itemsize) bytes, d
    below the lane width buys nothing, and the cells' layer is far inside."""
    span = fa.backward_span_rows
    assert span(1024, 1024, 64, jnp.bfloat16) == 1024      # the cells'
    assert span(4096, 4096, 128, jnp.bfloat16) == 4096
    for d in (64, 128):
        assert span(65536, 65536, d, jnp.bfloat16) == 6144
        assert span(65536, 65536, d, jnp.float32) == 3072
    assert span(65536, 65536, 192, jnp.bfloat16) == 2048
    for itemsize, rows in ((2, 6144), (4, 3072)):
        fits = fa._bwd_vmem_bytes(rows, 128, itemsize, 512, 512)
        over = fa._bwd_vmem_bytes(rows + 512, 128, itemsize, 512, 512)
        assert fits <= fa._BWD_VMEM < over
        assert over - fits == 512 * 128 * (4 + 2 * itemsize)
    # tiles of 128 (768 rows): a span is a multiple of the tile
    assert span(768, 768, 64, jnp.bfloat16) == 768


def test_the_cells_backward_is_one_call_named_flash_bwd():
    """8 x 12 heads of 1,024 rows x 64 in bfloat16, causal: ONE
    `pallas_call` (the parent had two: dk/dv, then dq) named `flash_bwd`,
    three visits a head on 512 x 512 tiles: (0, 0), (1, 0), (1, 1)."""
    found = _eqns(_backward_jaxpr(96, 1024, 1024, 64, jnp.bfloat16),
                  "pallas_call")
    assert len(found) == 1
    assert found[0].params["name"] == "flash_bwd"
    assert found[0].params["grid_mapping"].grid == (96, 3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_products_take_their_operands_type(dtype):
    """float32 operands: `p` and `ds` stay float32, nothing in the backward
    is rounded to fewer bits. bfloat16 operands: every product of the kernel
    takes both operands in bfloat16 and accumulates in float32; the scores,
    the exponential and ds's arithmetic stay float32."""
    jaxpr = _backward_jaxpr(2, 1024, 1024, 64, dtype, bias=True)
    kernel, = _eqns(jaxpr, "pallas_call")
    dots = _eqns(kernel.params["jaxpr"], "dot_general")
    # a whole pair and the diagonal's two halves, five products each
    assert len(dots) == 15
    for eqn in dots:
        assert {v.aval.dtype for v in eqn.invars} == {jnp.dtype(dtype)}
        assert eqn.outvars[0].aval.dtype == jnp.float32
    narrow = [eqn for eqn in _eqns(jaxpr, "convert_element_type")
              if jnp.dtype(eqn.params["new_dtype"]).itemsize < 4
              and jnp.issubdtype(eqn.params["new_dtype"], jnp.floating)]
    if dtype == jnp.float32:
        assert not narrow
    for eqn in _eqns(kernel.params["jaxpr"], "exp"):
        assert eqn.invars[0].aval.dtype == jnp.float32


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("nq,nk,bq,bk,kv_len,row0", [
    (2, 2, 512, 512, 1024, 0), (4, 4, 256, 256, 1024, 0),
    (3, 5, 256, 256, 1280, 0), (5, 3, 256, 256, 768, 0),
    (5, 5, 128, 128, 600, 0), (2, 4, 512, 256, 1000, 0),
    (4, 2, 256, 512, 1024, 0), (1, 4, 512, 128, 512, 0),
    (1, 7, 512, 512, 3584, 3072), (2, 6, 512, 512, 3072, 2048)])
def test_the_backwards_walk_visits_the_pairs_that_hold_work(
        causal, nq, nk, bq, bk, kv_len, row0):
    """KV-tile major, a KV tile's query tiles in order; every pair that
    holds an attended (row, column) once, and none other except the ONE
    visit a KV tile above every row keeps (it meets masks alone and stores
    zeros; the span loop hands none); the square pairs ON the diagonal
    marked for their two halves, also in a span that starts at `row0`."""
    qt, kt, bits = fa._bwd_walk(nq, nk, bq, bk, causal, row0)
    row = row0 + np.arange(nq * bq)[:, None]
    col = np.arange(nk * bk)[None, :]
    keep = (col < kv_len) & ((row >= col) if causal else (row >= 0))
    holds = keep.reshape(nq, bq, nk, bk).any((1, 3))

    visited = set(zip(qt.tolist(), kt.tolist()))
    assert len(visited) == len(qt)
    assert np.all(np.diff(kt) >= 0)
    for c in range(nk):
        mine = kt == c
        assert mine.sum() == max(holds[:, c].sum(), 1)
        assert np.all(np.diff(qt[mine]) == 1) and qt[mine][-1] == nq - 1
        assert (bits[mine] & fa._FIRST != 0).tolist() == \
            [True] + [False] * (mine.sum() - 1)
        assert (bits[mine] & fa._LAST != 0).tolist() == \
            [False] * (mine.sum() - 1) + [True]
        if holds[:, c].any():
            assert set(qt[mine]) == set(np.nonzero(holds[:, c])[0])
    for j, c, b in zip(qt, kt, bits):
        halves = (causal and bq == bk and bq % 256 == 0
                  and row0 + j * bq == c * bk)
        assert bool(b & fa._DIAGONAL) == halves


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(mesh, causal):
    q, k, v, bias_k = _qkv()
    ref = mha_reference(q, k, v, bias_k[:, None, None, :], causal)
    out = ring_attention(q, k, v, mesh, "cp", bias_k, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

    g_ref = jax.grad(
        lambda *a: (mha_reference(*a, bias_k[:, None, None, :],
                                  causal) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.grad(
        lambda *a: (ring_attention(*a, mesh, "cp", bias_k,
                                   causal) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_ring):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_reference(mesh, causal):
    q, k, v, bias_k = _qkv()
    ref = mha_reference(q, k, v, bias_k[:, None, None, :], causal)
    out = ulysses_attention(q, k, v, mesh, "cp", bias_k, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    g_ref = jax.grad(
        lambda *a: (mha_reference(*a, bias_k[:, None, None, :],
                                  causal) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g_u = jax.grad(
        lambda *a: (ulysses_attention(*a, mesh, "cp", bias_k,
                                      causal) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_u):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


def test_fused_attention_op_in_program():
    """Program-level fused_attention op output == composed einsum graph."""
    b, s, n, d = 2, 16, 4, 8
    rng = np.random.RandomState(3)
    qv, kv, vv = (rng.randn(b, s, n, d).astype(np.float32)
                  for _ in range(3))
    maskv = np.ones((b, s), np.float32)

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        q = pt.layers.data("q", [s, n, d])
        k = pt.layers.data("k", [s, n, d])
        v = pt.layers.data("v", [s, n, d])
        m = pt.layers.data("m", [s])
        neg_k = pt.layers.scale(m, scale=1e4, bias=-1e4)
        out = pt.layers.fused_attention(q, k, v, bias_k=neg_k)

    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        res, = exe.run(main, feed={"q": qv, "k": kv, "v": vv, "m": maskv},
                       fetch_list=[out])
    ref = mha_reference(jnp.asarray(qv), jnp.asarray(kv), jnp.asarray(vv),
                        None, False)
    np.testing.assert_allclose(res, np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_bert_fused_cp_train_step_matches_einsum(mesh):
    """Full BERT train step with ring-attention context parallelism over an
    8-device cp mesh == the einsum-attention graph on one device."""
    from paddle_tpu.models.bert import BertConfig, bert_pretrain_program

    seq, batch = 64, 2
    rng = np.random.RandomState(0)
    feed = {
        "src_ids": rng.randint(0, 512, (batch, seq)).astype(np.int64),
        "sent_ids": rng.randint(0, 2, (batch, seq)).astype(np.int64),
        "input_mask": np.ones((batch, seq), np.float32),
        "mlm_labels": rng.randint(0, 512, (batch, seq)).astype(np.int64),
    }

    losses = {}
    for mode in ("einsum", "fused_cp"):
        cfg = BertConfig(vocab_size=512, hidden=64, layers=2, heads=8,
                         ffn=128, max_pos=seq, dropout=0.0)
        if mode == "fused_cp":
            cfg.attn_impl = "fused"
            cfg.cp_axis = "cp"
        main, startup, fetches = bert_pretrain_program(cfg, seq,
                                                       learning_rate=1e-3)
        prog = main
        if mode == "fused_cp":
            prog = pt.CompiledProgram(main).with_sharding(
                {}, mesh_shape=(1, 8), axis_names=("dp", "cp"),
                feed_shardings={"src_ids": (None, "cp"),
                                "sent_ids": (None, "cp"),
                                "input_mask": (None, "cp"),
                                "mlm_labels": (None, "cp")})
        exe = pt.Executor()
        with pt.scope_guard(pt.Scope()):
            exe.run(startup)
            step_losses = []
            for _ in range(3):
                loss, = exe.run(prog, feed=feed,
                                fetch_list=[fetches["loss"]])
                step_losses.append(float(loss[0]))
        losses[mode] = step_losses

    np.testing.assert_allclose(losses["einsum"], losses["fused_cp"],
                               atol=1e-4, rtol=1e-4)
    assert losses["einsum"][-1] < losses["einsum"][0]
