"""KV-cache autoregressive decoding (VERDICT r4 item 2).

Pins the O(1)-per-step decode contract (the reference's incremental
tensor-array decode state, test_machine_translation.py:110-136) for the
GPT family: cached == uncached logits/greedy/beam, program parity, and
the sampling modes."""

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models.gpt import GPTConfig, gpt_lm_program
from paddle_tpu.models import gpt_decode as gd


def tiny_cfg():
    return GPTConfig(vocab_size=97, hidden=32, layers=2, heads=4,
                     max_pos=64, dropout=0.0, attn_impl="xla")


@pytest.fixture(scope="module")
def trained():
    """A randomly initialised tiny GPT: (cfg, params, program logits fn)."""
    cfg = tiny_cfg()
    main, startup, fetches = gpt_lm_program(cfg, 8, is_test=True)
    exe = pt.Executor()
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup)
        params = gd.collect_gpt_params(scope, cfg)

        def program_logits(tokens):
            with pt.scope_guard(scope):
                out, = exe.run(main, feed={"tokens": tokens},
                               fetch_list=[fetches["logits"]])
            return out
    return cfg, params, program_logits


def test_forward_matches_program(trained):
    """The decode module's full forward reproduces the static-graph
    program's logits (same vars, same math)."""
    cfg, params, program_logits = trained
    rng = np.random.RandomState(0)
    toks = rng.randint(0, cfg.vocab_size, (2, 8)).astype(np.int64)
    ref = program_logits(toks)
    got = gd.gpt_forward_logits(params, cfg, np.asarray(toks, np.int32))
    np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-4, atol=2e-4)


def test_prefill_matches_full_forward(trained):
    cfg, params, _ = trained
    rng = np.random.RandomState(1)
    toks = np.asarray(rng.randint(0, cfg.vocab_size, (3, 6)), np.int32)
    full = np.asarray(gd.gpt_forward_logits(params, cfg, toks))
    logits, cache = gd.gpt_prefill(params, cfg, toks, max_len=16)
    np.testing.assert_allclose(np.asarray(logits), full[:, -1],
                               rtol=1e-5, atol=1e-5)
    assert cache.shape == (cfg.layers, 2, 3, cfg.heads, 16,
                           cfg.hidden // cfg.heads)


def test_cached_step_matches_full_forward(trained):
    """Step-by-step cached logits == full-prefix recompute at every
    position (the equality the VERDICT asked for)."""
    import jax.numpy as jnp
    cfg, params, _ = trained
    rng = np.random.RandomState(2)
    toks = np.asarray(rng.randint(0, cfg.vocab_size, (2, 10)), np.int32)
    full = np.asarray(gd.gpt_forward_logits(params, cfg, toks))
    # prefill on the first 4, then feed tokens 4..9 one at a time
    _, cache = gd.gpt_prefill(params, cfg, toks[:, :4], max_len=12)
    for t in range(4, 10):
        logits, cache = gd.gpt_decode_step(
            params, cfg, jnp.asarray(toks[:, t]), cache, t)
        np.testing.assert_allclose(np.asarray(logits), full[:, t],
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"position {t}")


def test_greedy_generate_matches_nocache(trained):
    cfg, params, _ = trained
    rng = np.random.RandomState(3)
    prompt = np.asarray(rng.randint(0, cfg.vocab_size, (2, 4)), np.int32)
    out = gd.gpt_generate(params, cfg, prompt, max_new_tokens=8)
    # no-cache reference: recompute the full prefix each step, argmax
    toks = prompt.copy()
    for _ in range(8):
        logits = np.asarray(gd.gpt_forward_logits(params, cfg, toks))
        nxt = logits[:, -1].argmax(-1).astype(np.int32)
        toks = np.concatenate([toks, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(out, toks)


def test_sampling_modes(trained):
    cfg, params, _ = trained
    prompt = np.zeros((2, 2), np.int32)
    a = gd.gpt_generate(params, cfg, prompt, 6, temperature=0.8,
                        top_k=5, seed=7)
    b = gd.gpt_generate(params, cfg, prompt, 6, temperature=0.8,
                        top_k=5, seed=7)
    np.testing.assert_array_equal(a, b)  # seeded -> deterministic
    c = gd.gpt_generate(params, cfg, prompt, 6, temperature=0.8,
                        top_k=5, seed=8)
    assert a.shape == c.shape == (2, 8)
    # top-k=1 at any temperature is greedy
    d = gd.gpt_generate(params, cfg, prompt, 6, temperature=1.0, top_k=1,
                        seed=0)
    e = gd.gpt_generate(params, cfg, prompt, 6, temperature=0.0)
    np.testing.assert_array_equal(d, e)


def test_eos_stops_rows(trained):
    cfg, params, _ = trained
    prompt = np.zeros((1, 2), np.int32)
    # force eos to be whatever greedy produces first -> everything after
    # must be eos
    first = gd.gpt_generate(params, cfg, prompt, 1)[0, -1]
    out = gd.gpt_generate(params, cfg, prompt, 6, eos_id=int(first))
    assert (out[0, 2:] == first).all()


def test_beam_search_cached_equals_uncached(trained):
    """beam_search_decode_on_device with a KV-cache stateful step returns
    the same sequences/scores as the full-prefix-recompute step."""
    import jax
    import jax.numpy as jnp
    cfg, params, _ = trained
    b, k, L = 2, 3, 6
    bos, eos = 1, 2

    def uncached_step(tokens, t):
        logits_all = gd.gpt_forward_logits(params, cfg, tokens)
        return jax.lax.dynamic_index_in_dim(logits_all, t, axis=1,
                                            keepdims=False)

    seqs_u, scores_u = pt.layers.decode.beam_search_decode_on_device(
        uncached_step, b, k, bos, eos, L)

    hd = cfg.hidden // cfg.heads
    cache0 = jnp.zeros((cfg.layers, 2, b * k, cfg.heads, L + 1, hd),
                       jnp.float32)

    def cached_step(tokens, t, cache):
        tok = jax.lax.dynamic_index_in_dim(tokens, t, axis=1,
                                           keepdims=False)
        return gd.gpt_decode_step(params, cfg, tok, cache, t)

    def reorder(cache, parent):
        flat = (parent + jnp.arange(b)[:, None] * k).reshape(-1)
        return cache[:, :, flat]

    seqs_c, scores_c = pt.layers.decode.beam_search_decode_on_device(
        cached_step, b, k, bos, eos, L,
        init_state=cache0, reorder_state=reorder)

    np.testing.assert_array_equal(seqs_c, seqs_u)
    np.testing.assert_allclose(scores_c, scores_u, rtol=1e-4, atol=1e-4)


def test_softmax_xent_aux_loss_through_softmax_output():
    """The custom softmax_with_cross_entropy grad must still propagate
    gradients that flow through the SOFTMAX output (entropy penalties,
    distillation) — code-review r5 regression pin."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    xv = rng.randn(4, 7).astype(np.float32)
    yv = rng.randint(0, 7, (4, 1)).astype(np.int64)

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = pt.layers.data("x", [4, 7], append_batch_size=False,
                           stop_gradient=False)
        y = pt.layers.data("y", [4, 1], dtype="int64",
                           append_batch_size=False)
        loss_ce, sm = pt.layers.softmax_with_cross_entropy(
            x, y, return_softmax=True)
        # aux loss through the softmax output: sum of squares
        total = pt.layers.mean(loss_ce) + \
            pt.layers.reduce_sum(sm * sm) * 0.3
        gx, = pt.gradients([total], [x])
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        g, = exe.run(main, feed={"x": xv, "y": yv}, fetch_list=[gx])

    def ref(logits):
        logp = jax.nn.log_softmax(logits)
        sm = jnp.exp(logp)
        ce = -jnp.take_along_axis(logp, jnp.asarray(yv, jnp.int32), 1)
        return ce.mean() + 0.3 * jnp.sum(sm * sm)

    g_ref = jax.grad(ref)(jnp.asarray(xv))
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-5)


def test_generate_past_max_pos_raises(trained):
    cfg, params, _ = trained
    prompt = np.zeros((1, 4), np.int32)
    with pytest.raises(ValueError, match="max_pos"):
        gd.gpt_generate(params, cfg, prompt, cfg.max_pos)


def test_beam_default_reorder_rejects_wrong_layout(trained):
    import jax.numpy as jnp
    cfg, params, _ = trained

    def cached_step(tokens, t, cache):
        return jnp.zeros((6, cfg.vocab_size)), cache

    bad_state = jnp.zeros((cfg.layers, 2, 6, cfg.heads, 8, 8))
    with pytest.raises(ValueError, match="reorder"):
        pt.layers.decode.beam_search_decode_on_device(
            cached_step, 2, 3, 1, 2, 4, init_state=bad_state)


# -- paged decode attention: the kernel against the gather path -------------

BS, PAGES = 16, 4          # block size and page-row width of the cases


def _gather_reference(q, arena, layer, pt, ts):
    """`gather_pages` + the einsum path of gpt_decode_step_pages, for
    one layer: the form the kernel must reproduce."""
    import jax.numpy as jnp
    hd = q.shape[-1]
    K, V = gd._kv_gather(arena, layer, pt, arena.dtype)
    mask = jnp.arange(K.shape[2])[None, :] <= ts[:, None]
    scores = jnp.einsum("bnd,bnkd->bnk", q, K,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(mask[:, None, :], scores / np.sqrt(hd), -1e30)
    probs = jnp.exp(scores - jnp.max(scores, -1, keepdims=True))
    probs = (probs / probs.sum(-1, keepdims=True)).astype(q.dtype)
    return jnp.einsum("bnk,bnkd->bnd", probs, V)


def _kernel_case(heads, dtype, ts, pt=None, done=None):
    return dict(heads=heads, dtype=dtype, ts=ts, pt=pt, done=done)


_TS = {"ts0": 0, "page_last_row": BS - 1, "page_first_row": BS,
       "row_end": PAGES * BS - 1}
KERNEL_CASES = {
    f"h{heads}-{dtype}-{name}": _kernel_case(heads, dtype, [t, 21])
    for heads in (12, 25) for dtype in ("float32", "bfloat16")
    for name, t in _TS.items()}
# pages out of order, the tail of each row on scratch block 0
KERNEL_CASES["out_of_order-scratch_tail"] = _kernel_case(
    12, "bfloat16", [BS + 3, 2 * BS],
    pt=[[7, 2, 0, 0], [5, 9, 3, 0]])
# a frozen slot between two live ones
KERNEL_CASES["done_slot"] = _kernel_case(
    12, "bfloat16", [5, 2 * BS + 1, 40],
    pt=[[1, 0, 0, 0], [4, 2, 6, 0], [8, 3, 5, 0]],
    done=[False, True, False])
KERNEL_CASES["chunk8-greedy-tokens"] = None


@pytest.mark.parametrize("case", list(KERNEL_CASES), ids=list(KERNEL_CASES))
def test_paged_attention_kernel(case, trained, monkeypatch):
    """ops/paged_attention, interpreted on the CPU, against the gather
    path: the context of every live slot, the arena after the step's
    own write (a frozen slot's went to scratch on the gather path and
    goes nowhere in the kernel: every block but scratch is equal), and
    zeros for a frozen slot. The last case runs one whole chunk of 8
    steps (serving.decode_loop) through either path."""
    import jax.numpy as jnp
    from paddle_tpu.ops.paged_attention import paged_attention

    if KERNEL_CASES[case] is None:
        _chunk_tokens_match(trained, monkeypatch)
        return
    c = KERNEL_CASES[case]
    heads, hd, layers, layer = c["heads"], 64, 2, 1
    dtype = jnp.dtype(c["dtype"])
    ts = jnp.asarray(c["ts"], jnp.int32)
    s_dim = ts.shape[0]
    num_blocks = s_dim * PAGES + 1
    rng = np.random.RandomState(len(case))
    if c["pt"] is None:         # in order, every page allocated
        pt_ = jnp.arange(1, num_blocks, dtype=jnp.int32).reshape(
            s_dim, PAGES)
    else:
        pt_ = jnp.asarray(c["pt"], jnp.int32)
    done = None if c["done"] is None else jnp.asarray(c["done"])
    shape, _ = gd.paged_arena_shapes(layers, num_blocks, heads, BS, hd)
    arena = jnp.asarray(rng.standard_normal(shape), dtype)
    q, k, v = (jnp.asarray(rng.standard_normal((s_dim, heads, hd)), dtype)
               for _ in range(3))

    got, arena_k = paged_attention(q, k, v, arena, layer, pt_, ts, done)

    wblk = pt_[jnp.arange(s_dim), ts // BS]
    if done is not None:
        wblk = jnp.where(done, 0, wblk)
    arena_g = gd._kv_write(arena, layer, wblk, ts % BS, k, v)
    want = _gather_reference(q, arena_g, layer, pt_, ts)
    live = np.ones(s_dim, bool) if done is None else ~np.asarray(done)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live], np.asarray(want, np.float32)[live],
        rtol=tol, atol=tol)
    assert not np.asarray(got, np.float32)[~live].any()
    # every block but scratch, in every layer, byte for byte
    np.testing.assert_array_equal(
        np.asarray(arena_k[:, :, 1:].astype(jnp.float32)),
        np.asarray(arena_g[:, :, 1:].astype(jnp.float32)))
    if done is not None:
        # and what the frozen slot's stale page row points at is as it was
        np.testing.assert_array_equal(
            np.asarray(arena_k[:, :, 1:].astype(jnp.float32))[:, :, [3, 1, 5]],
            np.asarray(arena[:, :, 1:].astype(jnp.float32))[:, :, [3, 1, 5]])


def _chunk_tokens_match(trained, monkeypatch):
    """One decode_loop.decode_chunk of 8 steps over the GPT's paged
    step: greedy tokens, positions and the frozen mask of the kernel
    path are the gather path's."""
    import jax.numpy as jnp
    from paddle_tpu.serving.decode_loop import DecodeCarry, decode_chunk
    cfg, params, _ = trained
    heads, hd = cfg.heads, cfg.hidden // cfg.heads
    s_dim, bs, pages = 3, 4, 6
    shape, _ = gd.paged_arena_shapes(cfg.layers, s_dim * pages + 1, heads,
                                     bs, hd)
    arena = jnp.zeros(shape, jnp.float32)
    pt_ = jnp.asarray(np.random.RandomState(3).permutation(
        s_dim * pages).reshape(s_dim, pages) + 1, jnp.int32)
    prompts = [np.arange(5) % 97, (np.arange(9) * 7 + 1) % 97]
    first = []
    for slot, prompt in enumerate(prompts):
        logits, arena = gd.gpt_prefill_pages(
            params, cfg, jnp.asarray(prompt[None], jnp.int32), 0,
            len(prompt), arena, pt_[slot])
        first.append(int(np.argmax(np.asarray(logits[0]))))
    carry = DecodeCarry(
        tokens=jnp.asarray(first + [0], jnp.int32),
        ts=jnp.asarray([5, 9, 2], jnp.int32),
        done=jnp.asarray([False, False, True]),      # slot 2 rides frozen
        remaining=jnp.asarray([8, 5, 0], jnp.int32),  # slot 1 ends inside
        temps=jnp.zeros((s_dim,), jnp.float32),
        eos_ids=jnp.full((s_dim,), -1, jnp.int32))

    def run():
        block, arena_f, _, c, _ = decode_chunk(
            gd.GPT_SERVING_MODEL, params, cfg, arena, pt_,
            jnp.zeros((s_dim, 2), jnp.uint32), carry, 8)
        return (block, c.tokens, c.ts, c.done, c.remaining), arena_f

    want, want_arena = run()
    monkeypatch.setattr(gd, "decode_attention_path",
                        lambda *a, **k: "paged_kernel")
    got, got_arena = run()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    np.testing.assert_allclose(np.asarray(got_arena)[:, :, 1:],
                               np.asarray(want_arena)[:, :, 1:],
                               rtol=1e-5, atol=1e-5)


def test_decode_attention_path_is_read_off_the_input(trained, monkeypatch):
    """No option picks the path: the backend, the arena's form and the
    mesh constraint do. The quantized pair, a constrained arena, the
    speculative verify pass and a row that is not whole lanes gather;
    engine.stats() says which."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving import ServingConfig, ServingEngine

    shape, scale_shape = gd.paged_arena_shapes(2, 5, 2, 4, 64)
    bare = jnp.zeros(shape, jnp.bfloat16)
    quantized = (jnp.zeros(shape, jnp.int8),
                 jnp.zeros(scale_shape, jnp.float32))
    narrow = jnp.zeros(gd.paged_arena_shapes(2, 5, 4, 4, 8)[0])
    assert gd.decode_attention_path(bare) == "gather"         # the CPU
    cfg = GPTConfig(vocab_size=97, hidden=128, layers=2, heads=2,
                    max_pos=64, dropout=0.0, attn_impl="xla")
    params = _params_like(cfg)
    sizes = dict(num_slots=2, max_len=32, block_size=4,
                 prefill_buckets=(8,))

    def path(**kw):
        engine = ServingEngine(params, cfg, ServingConfig(**sizes, **kw))
        try:
            return engine.stats()["decode_attention"]
        finally:
            engine.close()

    assert path() == "gather"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gd.decode_attention_path(bare) == "paged_kernel"
    assert gd.decode_attention_path(quantized) == "gather"
    assert gd.decode_attention_path(bare, lambda a: a) == "gather"
    assert gd.decode_attention_path(narrow) == "gather"
    assert path() == "paged_kernel"
    assert path(kv_dtype="int8") == "gather"
    assert path(speculate_k=2) == "gather"


# -- prefill attention: the flash forward against the gather path ------------

def _prefill_case(bucket, pfx_len=0):
    return dict(bucket=bucket, pfx_len=pfx_len)


PREFILL_CASES = {"b128": _prefill_case(128), "b256": _prefill_case(256),
                 "b512": _prefill_case(512),
                 # rows already cached: the cond's other branch
                 "b128-warm": _prefill_case(128, pfx_len=2 * BS)}


@pytest.fixture(scope="module")
def wide_bf16():
    """A GPT whose heads are 64 wide (the width the kernels serve), in
    bfloat16: (cfg, params)."""
    import jax.numpy as jnp
    cfg = GPTConfig(vocab_size=97, hidden=128, layers=2, heads=2,
                    max_pos=512, dropout=0.0, attn_impl="xla")
    main, startup, _ = gpt_lm_program(cfg, 8, is_test=True)
    exe, scope = pt.Executor(), pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup)
        return cfg, gd.collect_gpt_params(scope, cfg, dtype=jnp.bfloat16)


@pytest.mark.parametrize("case", list(PREFILL_CASES), ids=list(PREFILL_CASES))
def test_prefill_flash_matches_gather(case, wide_bf16, monkeypatch):
    """A cold prompt attended over its own rows by the flash forward
    (interpreted on the CPU, the path forced as the chip would choose
    it) against the gather path: real_len below the bucket and no
    multiple of the block size. The first layer's rows in the arena are
    bit-equal (same projections of the same input), deeper ones and the
    last position's logits within bfloat16's rounding (the forms round
    their probabilities at different places). With rows already cached
    the cond takes the gather branch and the result is today's
    exactly."""
    import jax.numpy as jnp
    cfg, params = wide_bf16
    c = PREFILL_CASES[case]
    bucket, pfx_len = c["bucket"], c["pfx_len"]
    real_len = bucket - 27                   # 101, 229, 485: BS divides none
    pages = -(-(pfx_len + bucket) // BS)
    shape, _ = gd.paged_arena_shapes(cfg.layers, pages + 1, cfg.heads, BS, 64)
    row = jnp.asarray(np.random.RandomState(bucket).permutation(pages) + 1,
                      jnp.int32)
    rng = np.random.RandomState(bucket + 1)
    arena = jnp.zeros(shape, jnp.bfloat16)
    if pfx_len:
        prefix = jnp.asarray(rng.randint(0, 97, (1, pfx_len)), jnp.int32)
        _, arena = gd.gpt_prefill_pages(params, cfg, prefix, 0, pfx_len,
                                        arena, row)
    tokens = np.zeros((1, bucket), np.int32)
    tokens[0, :real_len] = rng.randint(0, 97, real_len)

    def run():
        return gd.gpt_prefill_pages(params, cfg, jnp.asarray(tokens),
                                    jnp.int32(pfx_len), jnp.int32(real_len),
                                    arena, row)

    want, want_arena = run()
    monkeypatch.setattr(gd, "prefill_attention_path",
                        lambda *a, **k: "flash")
    got, got_arena = run()
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    if pfx_len:
        np.testing.assert_array_equal(f32(got), f32(want))
        np.testing.assert_array_equal(f32(got_arena), f32(want_arena))
        return
    assert np.abs(f32(want)).max() > 0.1      # logits of some size
    np.testing.assert_allclose(f32(got), f32(want), rtol=2e-2, atol=2e-2)
    # every block but scratch (pad rows of either form land there)
    np.testing.assert_array_equal(f32(got_arena)[0, :, 1:],
                                  f32(want_arena)[0, :, 1:])
    np.testing.assert_allclose(f32(got_arena)[1:, :, 1:],
                               f32(want_arena)[1:, :, 1:],
                               rtol=2e-2, atol=2e-2)


def test_prefill_attention_path_is_read_off_the_input(monkeypatch):
    """The twin of the decode test: the backend, the arena's form, the
    mesh constraint and the bucket pick a cold prefill's attention, and
    engine.stats() says which buckets run the flash forward."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving import ServingConfig, ServingEngine

    shape, scale_shape = gd.paged_arena_shapes(2, 5, 2, 4, 64)
    bare = jnp.zeros(shape, jnp.bfloat16)
    quantized = (jnp.zeros(shape, jnp.int8),
                 jnp.zeros(scale_shape, jnp.float32))
    narrow = jnp.zeros(gd.paged_arena_shapes(2, 5, 4, 4, 8)[0])
    assert gd.prefill_attention_path(bare, 128) == "gather"     # the CPU
    cfg = GPTConfig(vocab_size=97, hidden=128, layers=2, heads=2,
                    max_pos=256, dropout=0.0, attn_impl="xla")
    params = _params_like(cfg)
    sizes = dict(num_slots=2, max_len=256, block_size=4,
                 prefill_buckets=(64, 128))

    def verdict(**kw):
        engine = ServingEngine(params, cfg, ServingConfig(**sizes, **kw))
        try:
            s = engine.stats()["prefill_attention"]
            return s["path"], s["flash_buckets"]
        finally:
            engine.close()

    assert verdict() == ("gather", [])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gd.prefill_attention_path(bare, 128) == "flash"
    assert gd.prefill_attention_path(bare, 1024) == "flash"
    assert gd.prefill_attention_path(bare, 64) == "gather"
    assert gd.prefill_attention_path(bare, 192) == "gather"
    assert gd.prefill_attention_path(quantized, 128) == "gather"
    assert gd.prefill_attention_path(bare, 128, lambda a: a) == "gather"
    assert gd.prefill_attention_path(narrow, 128) == "gather"
    assert verdict() == ("flash", [128])
    assert verdict(kv_dtype="int8") == ("gather", [])
    assert verdict(mesh_shape=(2,)) == ("gather", [])


def _params_like(cfg):
    """Parameter pytree of `cfg`, from a fresh startup program."""
    main, startup, _ = gpt_lm_program(cfg, 8, is_test=True)
    exe, scope = pt.Executor(), pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup)
        return gd.collect_gpt_params(scope, cfg)
