"""Qwen3-Next-80B-A3B-Instruct's block THROUGH THE ENGINE and through its
kernels, interpreted (the second half of tests/test_qwen3_next.py, a file of
its own so that each is one worker's few minutes): greedy tokens against the
reference, one stream whatever the chunk and the admission's time, a row pool
sized from the traffic beside the state pools, the stats, every refusal by
name, the step's two halves, the decode step through the kernel, the scan
kernel at this model's broadcast operands."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from test_qwen3_next import (BS, CFG, STEP, _engine, _gdn_operands,  # noqa: F401
                             params, ref, reference_logits, tokens_of, whole)

from paddle_tpu.models import _recurrent
from paddle_tpu.models import qwen3_next as qn
from paddle_tpu.serving import ServingConfig, SlotKVCache
from paddle_tpu.serving.model import (cache_groups, require_features,
                                      serving_model, state_groups)


# -- through the engine ----------------------------------------------------------

@pytest.mark.parametrize("p_len,new", [(3, 12), (8, 20), (13, 15)])
def test_the_engine_serves_the_references_greedy_tokens(params, p_len, new):
    eng = _engine(params)
    req = eng.submit(tokens_of(p_len + new, p_len), max_new_tokens=new)
    eng.run_until_drained()
    assert len(req.tokens) == new
    logits = reference_logits(params, req.output())[p_len - 1:-1]
    top = np.sort(logits, -1)
    clear = top[:, -1] - top[:, -2] > 1e-4
    assert clear.sum() >= new - 4
    assert (np.argmax(logits, -1) == np.asarray(req.tokens))[clear].all()
    st = eng.stats()
    assert st["model"] == "Qwen3-Next-80B-A3B-Instruct"
    assert st["experts_held"] == {"first": 2, "count": 4, "of": 8}
    assert st["vocab_slice"] == {"first": 96, "rows": 96, "of": 768}
    assert st["decode_attention"] == {"full": "gather"}
    assert st["gdn_state_steps"] == 3 * (new - 1)
    assert st["gdn_prefill_rows"] == 3 * p_len and st["gdn_prefill_chunks"] == 3
    assert st["moe_picks_routed"] == 2 * st["router_tokens"]
    assert st["moe_picks_held"] == sum(st["expert_tokens"])
    assert st["prefix_cache"].startswith("off: a hit is valid only with")
    assert st["compiled_executables"] <= 2 + 2
    eng.close()


def _streams(params, decode_chunk, late=False):
    eng = _engine(params, decode_chunk=decode_chunk)
    prompts = [tokens_of(s, n) for s, n in ((1, 7), (2, 12), (3, 5))]
    reqs = [eng.submit(p, max_new_tokens=14) for p in prompts[:2]]
    if late:
        for _ in range(3):
            eng.step()
    reqs.append(eng.submit(prompts[2], max_new_tokens=14))
    eng.run_until_drained()
    eng.close()
    return [list(map(int, r.tokens)) for r in reqs]


@pytest.fixture(scope="module")
def stream_of_four(params):
    return _streams(params, 4)


@pytest.mark.parametrize("decode_chunk,late", [(1, False), (5, False), (5, True)])
def test_one_stream_whatever_the_chunk_and_the_admission(params, stream_of_four,
                                                         decode_chunk, late):
    assert _streams(params, decode_chunk, late) == stream_of_four


def test_a_row_pool_sized_from_the_traffic_makes_admission_wait(params):
    """`kv_blocks` under slab-equivalent beside a state pool of a block a slot: three
    requests of 10 pages each want 30 blocks where the pool has 24, so the third waits
    for pages though a slot and its state blocks are free, and every one is served."""
    eng = _engine(params, kv_blocks=24 + 1)
    reqs = [eng.submit(tokens_of(s, 16), max_new_tokens=24) for s in (1, 2, 3)]
    eng.step()
    st = eng.stats()
    assert [g["blocks_total"] for g in st["groups"]] == [24, 3, 3]
    assert st["state"]["blocks_used"] == 2 * 2 and st["groups"][0]["blocks_used"] == 20
    eng.run_until_drained()
    assert all(len(r.tokens) == 24 for r in reqs)
    alone = _engine(params)
    want = alone.submit(tokens_of(3, 16), max_new_tokens=24)
    alone.run_until_drained()
    assert list(reqs[2].tokens) == list(want.tokens)
    eng.close(), alone.close()


def test_engine_stats_name_the_state_and_the_groups(params):
    eng = _engine(params)
    st = eng.stats()
    assert st["state"] == {"groups": ["gdn", "conv"], "blocks_total": 6,
                           "blocks_used": 0, "peak_blocks_used": 0,
                           "bytes_a_slot": 3 * 4 * 16 * 16 * 4 + 3 * 3 * 128 * 4,
                           "recurrence_path": "xla",
                           "prefill_recurrence_path": "xla",
                           "prefill_kernel_buckets": [],
                           "prefill_chunk_rows": 64}
    assert [g["name"] for g in st["groups"]] == ["full", "gdn", "conv"]
    assert st["prefill_attention"]["groups"] == {"full": "gather"}
    layout = cache_groups(serving_model(CFG), CFG, 48, BS)
    assert [(g.spec.name, g.pages) for g in layout] == [("full", 12), ("gdn", 1), ("conv", 1)]
    assert [s.name for s in state_groups(serving_model(CFG), CFG)] == ["gdn", "conv"]
    eng.close()


REFUSED = {"weight_dtype": ("int8", "no int8 path"), "kv_dtype": ("int8", "scale"),
           "max_adapters": (2, "LoRA"), "speculate_k": (2, "rejected draft"),
           "mesh_shape": ((1,), "one chip's program"),
           "prefill_chunk": (8, "carried in"), "preempt": (True, "no snapshot")}


@pytest.mark.parametrize("option", sorted(REFUSED))
def test_every_option_a_state_group_lacks_is_refused_by_name(params, option):
    """What `require_features` refuses Kimi-Linear it refuses this model."""
    value, says = REFUSED[option]
    extra = {"adapter_rank": 4} if option == "max_adapters" else {}
    with pytest.raises(ValueError, match="does not implement") as err:
        _engine(params, **{option: value}, **extra)
    assert "the state group 'gdn'" in str(err.value) and says in str(err.value)
    assert not serving_model(CFG).features
    require_features(serving_model(CFG), ServingConfig(), CFG)


# -- the step's two halves, and the kernels interpreted ---------------------------------

@pytest.mark.parametrize("path,kept_in", [("xla", "float32"), ("kernel", "float32"),
                                          ("xla", "bfloat16")])
def test_the_steps_two_halves_are_the_step_and_the_reference(params, path, kept_in):
    """`gdn_step_inputs` then `gdn_state_update` ARE a Gated-DeltaNet layer's step (what
    `decode_step_pages` runs, and what the cell's state-step limit runs on the engine's own
    blocks), on a float32 state arena beside a BFLOAT16 history: the state that comes back
    is the reference's float32 `gdn_step` on the same block and operands to float32
    rounding, by either path (the kernel ops/kda_step.py INTERPRETED, handed the scalar
    decay over a head's channels and a key head's q, k twice); kept in bfloat16 it is a
    thousandth off, which is how the limit tells."""
    lp, n = params["layers"][0], 3
    key = jax.random.split(jax.random.PRNGKey(9), 3)
    state = (0.1 * jax.random.normal(key[0], (1, 1, n + 1) + CFG.state_shape)
             ).astype(kept_in)
    conv = (0.1 * jax.random.normal(
        key[1], (1, 1, n + 1) + _recurrent.history_shape(3, CFG.conv_width))
        ).astype(jnp.bfloat16)
    u = jax.random.normal(key[2], (n, CFG.hidden)).astype(jnp.bfloat16)
    lp = dict(lp, w_qkvz=lp["w_qkvz"].astype(jnp.bfloat16),
              w_ba=lp["w_ba"].astype(jnp.bfloat16))
    ids, done = jnp.arange(1, n + 1), jnp.asarray([False, True, False])
    arenas = {qn.GDN: state, qn.CONV: conv}
    q, k, v, g, beta, z, arenas = qn.gdn_step_inputs(CFG, lp, u, arenas, 0, ids, done)
    o, arenas = qn.gdn_state_update(arenas, 0, ids, done, q, k, v, g, beta, path)
    want_S, want_o = jax.vmap(ref.gdn_step)(
        state[0, 0, ids].astype(jnp.float32), q, k, v, g[..., 0], beta)
    got_S = arenas[qn.GDN][0, 0, ids].astype(jnp.float32)
    size = lambda a: float(jnp.sqrt(jnp.sum(a * a)))
    error = size((got_S - want_S)[::2]) / size(want_S[::2])   # the live slots
    if kept_in == "float32":
        assert error < 1e-6 and float(jnp.abs(o - want_o)[::2].max()) < 1e-5
    else:
        assert 1e-4 < error < 1e-2
    # the frozen slot's block and history are as they were; scratch took its writes
    assert bool((arenas[qn.GDN][0, 0, 2] == state[0, 0, 2]).all())
    assert bool((arenas[qn.CONV][0, 0, 2] == conv[0, 0, 2]).all())
    assert arenas[qn.CONV].dtype == jnp.bfloat16
    assert not bool((arenas[qn.CONV][0, 0, 1] == conv[0, 0, 1]).all())


def test_the_decode_step_through_the_kernel_is_the_decode_step(params):
    kv = SlotKVCache(CFG, 2, 48, jnp.float32, block_size=BS)
    for seed in (1, 2):
        kv.map_slot(kv.alloc(), tokens_of(seed, 6), 20)
    arena = tuple(0.1 * jax.random.normal(jax.random.PRNGKey(i), a.shape, a.dtype)
                  for i, a in enumerate(kv.arena))
    pt = jnp.asarray(kv.page_table)
    args = (jnp.asarray([3, 4]), arena, pt, jnp.asarray([6, 6]),
            jnp.asarray([False, False]))
    want, arena_x, _ = STEP(params, *args, recurrence="xla")
    got, arena_k, _ = STEP(params, *args, recurrence="kernel")
    assert float(jnp.abs(got - want).max()) <= 1e-5
    assert float(jnp.abs(arena_k[1][:, :, 1:] - arena_x[1][:, :, 1:]).max()) <= 1e-5
    assert qn.recurrence_path(CFG) == "xla"                   # the CPU
    assert qn.prefill_recurrence_path(CFG, 128) == "xla"


@pytest.mark.parametrize("real", [64, 100, 128])
def test_the_scan_kernel_takes_the_broadcast_operands(real):
    """ops/kda_chunk.py INTERPRETED at this model's form of its operands (32 -> 4 value
    heads of 128 here, a scalar decay over the key channels, a key head read twice), for a
    prompt that fills one chunk, ends inside the second and fills both: the reference's
    recurrence token by token, and the chunks it visited."""
    T, nk, nv, d = 128, 2, 4, 128
    q, k, v, g, beta = _gdn_operands(T, seed=real, nk=nk, nv=nv, d=d)
    live = jnp.arange(T) < real
    g = jnp.where(live[:, None], g, 0.0)
    beta = jnp.where(live[:, None], beta, 0.0)
    wide = jnp.broadcast_to(g[..., None], q.shape)
    o, S, visited = qn.gdn_scan(q, k, v, wide, beta, jnp.int32(real), "kernel")
    want_o, want_S = ref.gdn_recurrence(q[:real], k[:real], v[:real], g[:real], beta[:real])
    assert int(visited) == -(-real // 64)
    size = lambda a: float(jnp.sqrt(jnp.sum(a * a)))
    assert size(S - want_S) / size(want_S) < 2e-5
    assert size(o[:real] - want_o) / size(want_o) < 2e-5
    assert float(jnp.abs(o[-(-real // 64) * 64:]).max() if real <= 64 else 0.0) == 0.0
