"""A prompt's chunked delta-rule scan as a Pallas kernel
(paddle_tpu.ops.kda_chunk), interpreted on the CPU: held against the
recurrence token by token (`kda_step` T times) and against the `jax.numpy`
chunked form (`kda_chunked`, the CPU's path and the oracle) under decays
that overflow a naive exp(-G); a state carried in; rows and whole chunks past
`real_len`; and where models/kimi_linear.py takes it: never on the CPU (whose
prefill jaxpr is pinned as the parent commit traced it), one call a KDA
layer where the path is forced.

Tolerances are the `jax.numpy` form's own (tests/test_kimi_linear.py): both
sides are float32 with float32 products. That holds for the triangular system
only because it is solved by substitution: with CORRELATED keys (one token
repeated, a shared mean direction), slow decay and beta near 1 the system's
matrix is c times the all-ones triangle, whose powers pass 1e9 inside a chunk
where the solution's entries stay under 1. Random unit keys are nearly
orthogonal and show none of it, so the correlated cases below are what holds
the solve."""

import copy
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from test_kimi_linear import CFG, _kda_inputs

from paddle_tpu.models import _delta
from paddle_tpu.models import kimi_linear as kl
from paddle_tpu.ops import kda_chunk as kc
from paddle_tpu.serving import SlotKVCache

O_ATOL, S_ATOL = 5e-6, 5e-5


def _equations(jaxpr):
    """Every equation, a call's body once a CALL (the printed jaxpr shares a
    repeated body), a kernel's own body left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


def _scan(q, k, v, g, beta, S0=None):
    """`kda_step` a row at a time: (o (T, n, dv), S_T)."""
    n, d = q.shape[1:]
    S0 = jnp.zeros((n, d, v.shape[-1])) if S0 is None else S0
    S, o = jax.lax.scan(lambda S, x: _delta.kda_step(S, *x), S0,
                        (q, k, v, g, beta))
    return o, S


@pytest.mark.parametrize("length", [1, 63, 64, 65, 200, 512])
def test_the_kernel_is_the_recurrence_and_the_chunked_form(length):
    operands = _kda_inputs(length, length)
    if length > 4:          # every exponent <= 0, or this is inf
        assert not bool(jnp.isfinite(jnp.exp(-jnp.cumsum(operands[3], 0))).all())
    want_o, want_S = _scan(*operands)
    o, S, visited = kc.kda_chunk(*operands)
    assert o.shape == (length, 2, 16) and bool(jnp.isfinite(o).all())
    assert int(visited) == -(-length // kc.CHUNK)
    assert float(jnp.abs(o - want_o).max()) <= O_ATOL
    assert float(jnp.abs(S - want_S).max()) <= S_ATOL
    form_o, form_S = _delta.kda_chunked(*operands)
    assert float(jnp.abs(o - form_o).max()) <= 2 * O_ATOL
    assert float(jnp.abs(S - form_S).max()) <= 2 * S_ATOL


def _correlated_inputs(kind, length, seed):
    """Operands whose keys are NOT nearly orthogonal: `one_token` is one key
    row in every row of a head (what the width-4 convolution leaves of a run
    of one token) with g = -1e-3 and beta = 0.9; `one_token_undecayed` the
    same at g = -1e-5, beta = 1 (the system is I + the all-ones triangle);
    `shared_mean` random keys about a common direction three times their
    spread, slow random decays, beta about 0.7."""
    q, k, v, g, beta = _kda_inputs(length, seed, strong=False)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    if kind == "shared_mean":
        k = unit(k + 3.0 * k[:1] * k.shape[-1] ** 0.5)
        return q, k, v, g * 2e-2, jax.nn.sigmoid(1.0 + beta)
    k = jnp.broadcast_to(k[:1], k.shape)
    q = jnp.broadcast_to(k[:1], k.shape) * k.shape[-1] ** -0.5
    slow, full = (-1e-3, 0.9) if kind == "one_token" else (-1e-5, 1.0)
    return q, k, v, jnp.full_like(g, slow), jnp.full_like(beta, full)


@pytest.mark.parametrize("length", [64, 200])
@pytest.mark.parametrize("kind", ["one_token", "one_token_undecayed",
                                  "shared_mean"])
def test_correlated_keys_are_solved_as_the_recurrence_solves_them(kind, length):
    """The regime in which a series in the system's powers loses every
    digit (the first kernel's doubling read 1e9 here): the same tolerances
    as under random keys, against the token-by-token scan."""
    operands = _correlated_inputs(kind, length, 5)
    gram = jnp.einsum("tnd,snd->nts", operands[1][:64], operands[1][:64])
    assert float(gram.min()) > 0.5                        # not orthogonal
    want_o, want_S = _scan(*operands)
    o, S, _ = kc.kda_chunk(*operands)
    assert float(jnp.abs(want_o).max()) > 0.1
    assert float(jnp.abs(o - want_o).max()) <= O_ATOL
    assert float(jnp.abs(S - want_S).max()) <= S_ATOL
    form_o, form_S = _delta.kda_chunked(*operands)
    assert float(jnp.abs(o - form_o).max()) <= 2 * O_ATOL
    assert float(jnp.abs(S - form_S).max()) <= 2 * S_ATOL


def test_a_state_carried_in_is_the_whole_scan():
    operands = _kda_inputs(150, 9, strong=False)
    whole_o, whole_S, _ = kc.kda_chunk(*operands)
    cut = 70
    _, S0, _ = kc.kda_chunk(*(a[:cut] for a in operands))
    o, S, _ = kc.kda_chunk(*(a[cut:] for a in operands), S0=S0)
    assert float(jnp.abs(o - whole_o[cut:]).max()) <= O_ATOL
    assert float(jnp.abs(S - whole_S).max()) <= O_ATOL
    want_o, want_S = _scan(*(a[cut:] for a in operands), S0=S0)
    assert float(jnp.abs(o - want_o).max()) <= O_ATOL
    assert float(jnp.abs(S - want_S).max()) <= S_ATOL


@pytest.mark.parametrize("real_len,visited", [(130, 3), (128, 2), (1, 1),
                                              (256, 4)])
def test_rows_past_real_len_leave_the_state_and_dead_chunks_are_passed_by(
        real_len, visited):
    """A bucket of 256 rows (four chunks). Rows past `real_len` come with g
    = 0 and beta = 0 (`_kda_prompt`'s), and a chunk WHOLLY past it is not
    visited: its q, k, v are NaN here, its `o` rows come back zero, and the
    state is the one AT `real_len`."""
    q, k, v, g, beta = _kda_inputs(256, 11, strong=False)
    live = jnp.arange(256) < real_len
    g = jnp.where(live[:, None, None], g, 0.0)
    beta = jnp.where(live[:, None], beta, 0.0)
    dead = (jnp.arange(256) >= visited * kc.CHUNK)[:, None, None]
    poisoned = [jnp.where(dead, jnp.nan, a) for a in (q, k, v)]
    o, S, n = kc.kda_chunk(*poisoned, g, beta, real_len=jnp.int32(real_len))
    assert int(n) == visited
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(S).all())
    assert bool((o[visited * kc.CHUNK:] == 0).all())
    want_o, want_S = _scan(*(a[:real_len] for a in (q, k, v, g, beta)))
    assert float(jnp.abs(o[:real_len] - want_o).max()) <= O_ATOL
    assert float(jnp.abs(S - want_S).max()) <= S_ATOL


def test_every_product_of_the_kernel_is_float32_at_highest():
    """A bfloat16 product or a three-pass one is a different result, and no
    cell's `correct` sees a prefill's precision: the kernel's jaxpr does."""
    shape = jax.ShapeDtypeStruct
    rows, per_head = shape((64, 2, 16), jnp.float32), shape((64, 2), jnp.float32)
    jaxpr = jax.make_jaxpr(kc.kda_chunk)(rows, rows, rows, rows, per_head)
    kernel, = (eqn for eqn in _equations(jaxpr.jaxpr)
               if eqn.primitive.name == "pallas_call")
    products = [(eqn.params["precision"], eqn.params["preferred_element_type"])
                for eqn in _equations(kernel.params["jaxpr"])
                if eqn.primitive.name == "dot_general"]
    highest = jax.lax.Precision.HIGHEST
    # a pair of heads: the cumulative sum, three between sub-chunks a head,
    # four to merge the solved blocks, the system's right-hand side, two
    # over the state, B U, two into the state
    assert len(products) == 1 + 2 * 3 + 4 + 1 + 2 + 1 + 2
    assert set(products) == {((highest, highest), jnp.dtype("float32"))}


def test_an_odd_head_count_is_refused():
    assert (kc.CHUNK, kc.SUB) == (_delta.KDA_CHUNK, _delta.KDA_SUB)
    q, k, v, g, beta = _kda_inputs(8, 1)
    with pytest.raises(ValueError, match="pairs heads"):
        kc.kda_chunk(q[:, :1], k[:, :1], v[:, :1], g[:, :1], beta[:, :1])


# -- where the model takes it --------------------------------------------------------

def _prefill_jaxpr(bucket=16, max_len=48, outputs=slice(None), cfg=CFG):
    params = jax.eval_shape(lambda key: kl.init_params(cfg, key, jnp.float32),
                            jax.random.PRNGKey(0))
    kv = SlotKVCache(cfg, 2, max_len, jnp.float32, block_size=4)
    shape = jax.ShapeDtypeStruct
    arena = tuple(shape(a.shape, a.dtype) for a in kv.arena)
    return jax.make_jaxpr(
        lambda p, t, n, a, pages: kl.prefill_pages(p, cfg, t, 0, n, a,
                                                   pages)[outputs])(
            params, shape((1, bucket), jnp.int32), shape((), jnp.int32), arena,
            shape((kv.page_table.shape[1],), jnp.int32))


def test_the_cpus_prefill_is_the_parents_program():
    """No kernel on the CPU, and the program it traces is the one the parent
    commit traced (the digest was of 4029214's jaxpr of the logits and the
    arena under tests/conftest.py's settings; the counters gained one
    constant) but for the two blocks' writes at a prompt's end, which since
    PR 54's review round are ONE `dynamic_update_slice` each into the arena
    seen as a run of blocks (`models/_recurrent.write_block`'s one form)
    where an `.at[].set` stood: the digest was computed again on that
    tree, as `tests/test_served_programs.py`'s two prefill rows were."""
    assert kl.prefill_recurrence_path(CFG) == "xla"
    assert kl.prefill_recurrence_path(CFG, 256) == "xla"
    jaxpr = _prefill_jaxpr(outputs=slice(2))
    text = str(jaxpr)
    found = [eqn.primitive.name for eqn in _equations(jaxpr.jaxpr)]
    assert "pallas_call" not in found
    assert found.count("triangular_solve") == found.count("scan") == 4
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == "e02eaf81ae2960c0"


def test_with_the_path_forced_the_prefill_holds_one_kernel_a_kda_layer(
        monkeypatch):
    """As chip_smoke.py forces the step's path: one `kda_chunk` call a KDA
    layer, and no triangular solve and no loop of XLA's anywhere in the
    program (the latent layer's cold prefill and the expert layer have
    none)."""
    monkeypatch.setattr(kl, "prefill_recurrence_path",
                        lambda cfg, bucket=None: "kernel")
    found = list(_equations(_prefill_jaxpr(bucket=128, max_len=256,
                                           cfg=copy.copy(CFG)).jaxpr))
    kernels = [str(eqn.params["name"]) for eqn in found
               if eqn.primitive.name == "pallas_call"]
    assert kernels == ["kda_chunk"] * len(CFG.kda_layers)
    assert not {"triangular_solve", "while", "scan"} \
        & {eqn.primitive.name for eqn in found}


def test_the_engines_stats_say_what_the_traced_prefills_took(monkeypatch):
    """`engine.stats()["state"]` (the model's `describe`) reports the paths
    the prefills TRACED for this configuration took, bucket by bucket: the
    rule's word only until one is traced, and a bucket of no whole tile on
    a TPU (the rule below) is seen to keep `jax.numpy`."""
    cfg = copy.copy(CFG)
    said = lambda: kl.KIMI_LINEAR_SERVING_MODEL.describe(cfg)["state"]
    assert (said()["prefill_recurrence_path"],
            said()["prefill_kernel_buckets"]) == ("xla", [])
    monkeypatch.setattr(
        kl, "prefill_recurrence_path",
        lambda cfg, bucket=None: "xla" if (bucket or 0) % 128 else "kernel")
    assert said()["prefill_recurrence_path"] == "kernel"      # the rule's
    _prefill_jaxpr(bucket=16, cfg=cfg)
    assert (said()["prefill_recurrence_path"],
            said()["prefill_kernel_buckets"]) == ("xla", [])
    _prefill_jaxpr(bucket=128, max_len=256, cfg=cfg)
    assert (said()["prefill_recurrence_path"],
            said()["prefill_kernel_buckets"]) == ("kernel", [128])
    assert said()["recurrence_path"] == "xla"


@pytest.mark.parametrize("real_len", [37, 128])
def test_the_forced_prefill_is_the_cpus_prefill(monkeypatch, real_len):
    """The same prompt through both paths: logits, the state blocks and the
    history written agree to float32 rounding; the kernel's path counts
    the live chunks alone."""
    params = kl.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    kv = SlotKVCache(CFG, 2, 256, jnp.float32, block_size=4)
    row, _ = kv.map_slot(kv.alloc(), np.arange(real_len, dtype=np.int32) % 96,
                         real_len + 4)
    tokens = np.zeros((1, 128), np.int32)
    tokens[0, :real_len] = np.random.default_rng(3).integers(0, 96, real_len)
    args = (params, copy.copy(CFG), jnp.asarray(tokens), 0,
            jnp.int32(real_len), kv.arena, jnp.asarray(row))
    want, arena_x, c_x = kl.prefill_pages(*args)
    monkeypatch.setattr(kl, "prefill_recurrence_path",
                        lambda cfg, bucket=None: "kernel")
    got, arena_k, c_k = kl.prefill_pages(*args)
    assert float(jnp.abs(got - want).max()) <= 5e-5
    for x, k in zip(arena_x[1:], arena_k[1:]):
        assert float(jnp.abs(x - k).max()) <= 5e-5
    assert int(c_x["kda_prefill_chunks"]) == 4 * 2
    assert int(c_k["kda_prefill_chunks"]) == 4 * -(-real_len // 64)
    assert int(c_k["kda_prefill_rows"]) == 4 * real_len
