"""chip_smoke.py: does the system still start on the chip?

`python chip_smoke.py` (no arguments) drives the main path once, in ONE
process, at the full width of GPT-2-small (768 hidden, 12 layers, 12
heads, vocab 50257, 1024 positions), weights random from the startup
program's seed:

  1. device     jax's default backend must be "tpu", or exit 1 before
                any work (JAX_PLATFORMS=cpu, or no platform set and no
                chip found, must never end in a CPU run that prints ok)
  2. kernels    both Pallas flash families, forward and backward, bf16,
                12 heads x 64, against mha_reference
  3. train      gpt_lm_program -> pt.Executor, a few steps per shape;
                the compiled step must contain the Mosaic custom call,
                its loss gradient must come from the saved log-sum-exp
                (`loss_lowerings`), and at s=1024 the first loss is held
                against the training cells' float32 reference
  4. serve      pt.server.serve over those weights in bf16; concurrent
                POST /v1/generate (SSE and JSON), health counters, and
                every greedy token checked on the float32 reference's
                logits
  5. placement  every live array sits on the chip (before shutdown)
  6. four chips (only when jax.device_count() >= 4) serve on a
                tensor-parallel mesh of 4 and train data-parallel

One line per phase, a `compile_log` line (executables, cache hits and
misses, seconds of trace / lowering / compile / cache load in the whole
run), a `summary=` line (versions, per-phase facts, the same totals), then
as the LAST line of stdout one JSON object with exactly these keys:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
Without a TPU nothing is written to stdout at all. Wall and compile
seconds are set-up facts, not performance records. Exit code 0 only if
every phase passed (a failed phase ends in "ok": false and exit 1). The phases are plain functions
of their sizes (tests/test_chip_smoke.py runs them at a toy size on the
CPU); main() alone insists on the TPU.
"""

import http.client
import json
import math
import sys
import threading
import time

import numpy as np

# Mosaic kernels reach XLA as this custom-call target.
MOSAIC_TARGET = "tpu_custom_call"

# kernels vs mha_reference (f32, highest matmul precision) on bf16
# inputs: max |kernel - ref| over max |ref|. bf16 keeps 8 significand
# bits (eps = 2^-8 = 3.9e-3); the kernel rounds p (forward) or ds
# (backward) to bf16 once before its second matmul and the result once
# on the way out, so a few eps of the largest element is what a correct
# kernel shows, and a wrong mask, scale or block index shows O(1).
KERNEL_REL_TOL = 2e-2

# A served greedy token's reference logit (float32 forward at highest
# matmul precision over the same bf16-rounded weights) must be within
# this of that position's maximum. The engine computes in bf16 with a
# bf16 KV cache and another batch shape, so its logits carry a few
# 2^-8-relative roundings per layer of O(1) activations; a seeded-random
# model's top two logits are often closer than that, so token identity
# is not the contract here, closeness on the reference's logits is.
# The largest logit of this model is about 2.5 (standard deviation 0.55),
# so one bf16 rounding of it is 0.01; the margin allows ten. A wrong
# token (bad page, bad position, bad shard) sits whole units below.
LOGIT_MARGIN = 0.1

# data-parallel loss vs the one-chip loss, same seed and batch: the
# batch mean and the gradients are reduced in another order (per-chip
# partial sums, then a cross-chip sum), and Adam's first steps amplify
# sign flips of tiny gradients. That is worth parts in 1e5; a mis-sharded
# batch or a missing all-reduce shows up as percents.
DP_LOSS_REL_TOL = 1e-3


# The first training loss (bfloat16 products under AMP, float32 softmax
# and mean) against benchmarks/reference/gpt2_ref.batch_loss in float32
# on the same weights and batch, relative: the training cells' own limit
# (benchmarks/modes/train.py LOSS_REL_TOL). Parts in 1e5 are bfloat16
# rounding; a loss op that lost its float32 inside reads percents.
LOSS_REL_TOL = 2e-4


class SmokeFailure(AssertionError):
    """A phase found something wrong."""


def _require(cond, message):
    if not cond:
        raise SmokeFailure(message)


class CompileClock:
    """Sums jax's own compile events, so a phase can report how much of
    its wall time was XLA compilation (or loading from the persistent
    cache: a cache hit is counted and costs its retrieval time only)."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring

        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, seconds, **_):
        if event == self._COMPILE:
            self.compile_s += seconds

    def _on_event(self, event, **_):
        if event == self._HIT:
            self.cache_hits += 1


# ---------------------------------------------------------------------------
# phase 2: kernels
# ---------------------------------------------------------------------------

def _rel_err(got, ref):
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


def phase_kernels(seqs=(512, 1024), batch=2, heads=12, head_dim=64):
    """Both kernel families (single-pass at s <= 512, tiled above),
    forward and backward, causal without bias (GPT) and non-causal with
    a per-key bias (BERT's padding mask), forced with impl="flash" and
    compared with mha_reference."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import flash_attention as fa

    worst = {}
    for s in seqs:
        family = "single_pass" if fa._small_ok(s, s) else "tiled"
        for causal, with_bias in ((True, False), (False, True)):
            ks = jax.random.split(jax.random.PRNGKey(s + int(causal)), 5)
            shape = (batch, s, heads, head_dim)
            q, k, v, g = (jax.random.normal(kk, shape, jnp.float32)
                          .astype(jnp.bfloat16) for kk in ks[:4])
            bias = None
            if with_bias:
                # a padding mask: the last quarter of the keys is masked
                # for odd rows of the batch, plus a small graded term so
                # the bias gradient has something to say
                keep = (jnp.arange(s)[None, :] < (3 * s) // 4) \
                    | (jnp.arange(batch)[:, None] % 2 == 0)
                bias = (jnp.where(keep, 0.0, -1e4)
                        + 0.1 * jax.random.normal(ks[4], (batch, s))
                        ).astype(jnp.float32)[:, None, None, :]

            def kernel_loss(q, k, v, bias):
                o = fa.attention(q, k, v, bias, causal=causal,
                                 impl="flash")
                return jnp.sum(o.astype(jnp.float32)
                               * g.astype(jnp.float32)), o

            def ref_loss(q, k, v, bias):
                o = fa.mha_reference(q, k, v, bias, causal=causal)
                return jnp.sum(o * g.astype(jnp.float32)), o

            argnums = (0, 1, 2, 3) if with_bias else (0, 1, 2)
            (_, o_k), grads_k = jax.jit(jax.value_and_grad(
                kernel_loss, argnums=argnums, has_aux=True))(q, k, v, bias)
            f32 = [x.astype(jnp.float32) for x in (q, k, v)]
            with jax.default_matmul_precision("highest"):
                (_, o_r), grads_r = jax.jit(jax.value_and_grad(
                    ref_loss, argnums=argnums, has_aux=True))(*f32, bias)
            names = ("out", "dq", "dk", "dv", "dbias")
            for name, a, b in zip(names, (o_k,) + tuple(grads_k),
                                  (o_r,) + tuple(grads_r)):
                _require(a.shape == b.shape,
                         f"kernels: {family} s={s} {name} shape "
                         f"{a.shape} != reference {b.shape}")
                a = np.asarray(a, np.float32)
                _require(np.isfinite(a).all(),
                         f"kernels: {family} s={s} {name} not finite")
                err = _rel_err(a, b)
                tag = f"{family}/{'causal' if causal else 'bias'}/{name}"
                worst[tag] = max(worst.get(tag, 0.0), err)
                _require(err <= KERNEL_REL_TOL,
                         f"kernels: {tag} s={s} off the reference by "
                         f"{err:.3e} of its largest element "
                         f"(tolerance {KERNEL_REL_TOL:.0e})")
    return {"cases": len(worst), "worst_rel_err": max(worst.values()),
            "worst_case": max(worst, key=worst.get)}


# ---------------------------------------------------------------------------
# phase 2b: the kernels of a chip's share of an expert-parallel model
# ---------------------------------------------------------------------------

def phase_share_kernels(heads=128, kv_heads=8, head_dim=128, window=4096,
                        block=128, hidden=4096, width=4096, experts=128,
                        held=16, picks=8, shared=4, rows=8192, tokens=512,
                        combine_tokens=2048, combine_hidden=2304):
    """command-a-plus-05-2026's shapes against `jax.numpy` twins: the
    grouped paged kernel at heads / kv_heads queries a KV head over a ring
    of window / block + 1 blocks (slots below, at and beyond the window,
    one frozen); the flash forward's band of `window` over `rows` rows
    with shared KV heads; and the expert layer that holds `held` of
    `experts` experts (`models/_experts.moe`: the sliced kernel where an
    expert's matrices do not fit VMEM whole) at a step's and a prompt's
    row counts; and the combine kernel (ops/routed_combine) against XLA's
    gather and sum of the same rows, at `combine_hidden` lanes with every
    pick held and at `hidden` with `held` of `experts`."""
    import types

    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import _experts as ex
    from paddle_tpu.ops.flash_attention import flash_causal_rows
    from paddle_tpu.ops.paged_attention import paged_attention

    facts = {}
    d, group = head_dim, heads // kv_heads
    ring = -(-window // block) + 1
    ks = iter(jax.random.split(jax.random.PRNGKey(38), 16))
    normal = lambda *shape: jax.random.normal(next(ks), shape, jnp.float32)

    # the grouped paged kernel over a ring
    ts = jnp.asarray([block - 3, window - 1, window + block + 7,
                      2 * window + 5 * block + 1, 3 * block], jnp.int32)
    done = jnp.asarray([False, False, False, False, True])
    S = ts.shape[0]
    arena = (0.5 * normal(1, 1, S * ring + 1, kv_heads, block, 2 * d)
             ).astype(jnp.bfloat16)
    table = 1 + jnp.arange(S * ring, dtype=jnp.int32).reshape(S, ring)
    q, k, v = ((0.5 * normal(S, n, d)).astype(jnp.bfloat16)
               for n in (heads, kv_heads, kv_heads))
    lo = jnp.maximum(ts - window + 1, 0)
    got, after = jax.jit(lambda *a: paged_attention(*a[:4], 0, *a[4:7],
                                                    lo=a[7]))(
        q, k, v, arena, table, ts, done, lo)
    after32 = np.asarray(after.astype(jnp.float32))
    worst = 0.0
    for s in range(S - 1):
        at = np.arange(int(lo[s]), int(ts[s]) + 1)
        blocks = np.asarray(table)[s, (at // block) % ring]
        kv = after32[0, 0, blocks, :, at % block]           # (L, kv, 2d)
        qs = np.asarray(q[s].astype(jnp.float32)).reshape(kv_heads, group, d)
        sc = np.einsum("kgd,lkd->kgl", qs, kv[..., :d]) / np.sqrt(d)
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        want = np.einsum("kgl,lkd->kgd", pr / pr.sum(-1, keepdims=True),
                         kv[..., d:]).reshape(heads, d)
        worst = max(worst, _rel_err(got[s], want))
    _require(float(jnp.abs(got[S - 1].astype(jnp.float32)).max()) == 0.0,
             "share_kernels: a frozen slot's context is not zero")
    _require(worst <= KERNEL_REL_TOL,
             f"share_kernels: the grouped paged kernel ({group} queries a KV "
             f"head, ring of {ring}) is off its twin by {worst:.3e}")
    facts.update(paged_group=group, paged_ring=ring,
                 paged_rel_err=worst)

    # the banded flash forward
    q, k, v = ((0.5 * normal(rows, n, d)).astype(jnp.bfloat16)
               for n in (heads, kv_heads, kv_heads))
    scale = d ** -0.5
    got = jax.jit(lambda q, k, v: flash_causal_rows(
        q, k, v, scale, window=window))(q, k, v)
    check = np.asarray([0, window - 1, window, rows // 2 + 3, rows - 1])
    i, j = check[:, None], np.arange(rows)[None, :]
    mask = jnp.asarray((j <= i) & (i - j < window))
    with jax.default_matmul_precision("highest"):
        kk, vv = (jnp.repeat(t.astype(jnp.float32), group, 1) for t in (k, v))
        sc = jnp.einsum("qnd,knd->nqk", q[check].astype(jnp.float32), kk) * scale
        pr = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), -1)
        want = jnp.einsum("nqk,knd->qnd", pr, vv)
    err = _rel_err(got[check], want)
    _require(err <= KERNEL_REL_TOL,
             f"share_kernels: the flash band of {window} over {rows} rows is "
             f"off its twin by {err:.3e}")
    facts.update(flash_rows=rows, flash_band_rel_err=err)

    # the expert layer of a share
    cfg = types.SimpleNamespace(
        experts_per_tok=picks, n_routed_experts=experts,
        n_shared_experts=shared, router_scoring="sigmoid",
        experts_held=(held, held), shared_expert_combination="average")
    w = lambda *shape: (0.02 * normal(*shape)).astype(jnp.bfloat16)
    lp = {"router": w(hidden, experts), "w_gate": w(held, hidden, width),
          "w_up": w(held, hidden, width), "w_down": w(held, width, hidden),
          "shared_gate": w(hidden, shared * width),
          "shared_up": w(hidden, shared * width),
          "shared_down": w(shared * width, hidden)}
    moe = jax.jit(lambda lp, x, live: ex.moe(cfg, lp, x, live))

    def twin(lp, x, live):
        """Every held expert on every token, weighted by the pick's weight
        (zero where it was not picked), the shared experts' mean. The
        weights are ARGUMENTS: as constants 2 GB of them cost the compiler
        tens of GB of the host's memory."""
        x32 = x.astype(jnp.float32)
        pk, pw = ex.route(cfg, lp, x)
        f32 = lambda a: a.astype(jnp.float32)
        y = ex.swiglu(x32, f32(lp["shared_gate"]), f32(lp["shared_up"]),
                       f32(lp["shared_down"])) / shared
        for e in range(held):
            we = jnp.sum(jnp.where(pk == held + e, pw, 0), -1)
            y = y + jnp.where(live, we, 0)[:, None] * ex.swiglu(
                x32, f32(lp["w_gate"][e]), f32(lp["w_up"][e]),
                f32(lp["w_down"][e]))
        return y

    for n in (32, tokens):
        x = normal(n, hidden).astype(jnp.bfloat16)
        live = jnp.arange(n) % 7 != 3
        got, counters = moe(lp, x, live)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(twin)(lp, x, live)
        err = _rel_err(got, want)
        _require(err <= KERNEL_REL_TOL,
                 f"share_kernels: the expert layer holding {held} of "
                 f"{experts} at {n} tokens is off its twin by {err:.3e}")
        held_picks = int(counters["expert_tokens"].sum())
        _require(0 < held_picks < int(live.sum()) * picks,
                 f"share_kernels: {held_picks} held picks of "
                 f"{int(live.sum()) * picks}")
        facts[f"moe_rel_err_{n}"] = err
        facts[f"moe_held_picks_{n}"] = held_picks
    facts["moe_path"] = ex.expert_product_path(lp)

    # the combine kernel against XLA's gather and sum of the same rows:
    # Mellum's widths (8 picks of 64, every pick held) and this share's (8
    # of 128, 16 held: the held picks alone are fetched)
    from paddle_tpu.ops.grouped_swiglu import (padded_rows, routed_positions,
                                               row_tile_for)
    ks = iter(jax.random.split(jax.random.PRNGKey(39), 16))
    for name, h, of, term in (("mellum", combine_hidden, held, None),
                              ("command_a", hidden, experts, 1.0 / shared)):
        n, tile = combine_tokens, row_tile_for(combine_tokens * picks, of)
        pk = jax.vmap(lambda key: jax.random.permutation(key, of)[:picks])(
            jax.random.split(next(ks), n)).astype(jnp.int32)
        live = jnp.arange(n) % 7 != 3
        pos, sizes = routed_positions(
            pk, live[:, None] & (pk < held) if of > held else live, held, tile)
        buffer = padded_rows(n * picks, held, tile)
        ys = normal(buffer, h).astype(jnp.bfloat16)
        bits = jax.lax.bitcast_convert_type(ys, jnp.uint16).astype(jnp.uint32)
        words = (bits[:, :h // 2] | (bits[:, h // 2:] << 16))[:, None, :]
        pw = jnp.where(pos < buffer, jnp.abs(normal(n, picks)), 0)
        both = jax.jit(lambda ys, sh, by_dma: ex._combine(
            ys, pos, pw, live, sh, term, jnp.bfloat16, by_dma),
            static_argnums=2)
        sh = None if term is None else normal(n, h).astype(jnp.bfloat16)
        got, want = both(words, sh, True), both(ys, sh, False)
        err = _rel_err(got, want)
        _require(err <= KERNEL_REL_TOL,
                 f"share_kernels: the combine kernel at {name}'s widths is "
                 f"off XLA's gather by {err:.3e}")
        facts[f"combine_rel_err_{name}"] = err
        facts[f"combine_rows_fetched_{name}"] = int(sizes.sum())
    return facts


# ---------------------------------------------------------------------------
# phase 2c: generation by diffusion over blocks through the kernel paths
# ---------------------------------------------------------------------------

def phase_block_diffusion(hidden=256, heads=8, kv_heads=2, head_dim=128,
                          width=128, experts=8, picks=2, vocab=512, layers=2,
                          prompt_len=130, max_new=6, bucket=256, page=128,
                          force_kernels=False):
    """One request of a small block-diffusion model (models/sdar:
    lane-aligned widths, bfloat16) through the engine: the prefill of the
    prompt's whole blocks by the flash forward with `block=`, then two
    blocks of passes through the block-row paged kernel (a prompt of 130
    tokens leaves two of the first block fixed, so the first commit emits
    2 tokens and the second 4). Every served token's logit, at the pass
    that fixed it, against its position's largest by the program's own
    float32 forward over the whole sequence (XLA attention, no cache).
    `force_kernels` (the CPU test): take the kernel paths interpreted."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.models import _grouped, sdar
    from paddle_tpu.serving import ServingConfig, ServingEngine

    cfg = sdar.SdarConfig(
        vocab_size=vocab, hidden=hidden, layers=layers, heads=heads,
        kv_heads=kv_heads, head_dim=head_dim, moe_intermediate=width,
        n_routed_experts=experts, experts_per_tok=picks,
        max_pos=max(4 * page, 2 * bucket), mask_token_id=vocab - 1,
        init_range=0.05, name="sdar-smoke")
    params = sdar.init_params(cfg, jax.random.PRNGKey(40), jnp.bfloat16)
    paths = (_grouped.decode_attention_path, _grouped.prefill_attention_path)
    if force_kernels:
        # the programs' and the verdicts' one source
        _grouped.decode_attention_path = \
            lambda a, c=None: {"full": "paged_kernel"}
        _grouped.prefill_attention_path = lambda a, b, c=None: "flash"
    try:
        engine = ServingEngine(params, cfg, ServingConfig(
            num_slots=2, prefill_buckets=(bucket,), max_len=cfg.max_pos,
            block_size=page))
        prompt = np.random.default_rng(40).integers(0, vocab - 1, prompt_len)
        req = engine.submit(prompt, max_new)
        engine.run_until_drained()
        stats = engine.stats()
    finally:
        _grouped.decode_attention_path, _grouped.prefill_attention_path = \
            paths
    B = cfg.block_length
    _require(req.state == "finished" and len(req.tokens) == max_new
             and len(req.fixed_at) == len(req.confidence) == max_new,
             f"block_diffusion: {len(req.tokens)} of {max_new} tokens served")
    _require(stats["decode_attention"] == {"full": "paged_kernel"}
             and stats["prefill_attention"]["path"] == "flash",
             "block_diffusion: a pass or the prefill gathered: "
             f"{stats['decode_attention']}, {stats['prefill_attention']}")
    whole = prompt_len // B * B
    first = B - (prompt_len - whole)
    blocks = 1 + -(-(max_new - first) // B)
    _require(stats["blocks_committed"] == blocks,
             f"block_diffusion: {stats['blocks_committed']} blocks committed, "
             f"not {blocks}")
    # replay each pass in float32: the block with the tokens fixed before it
    # in place and the mask elsewhere, behind the prompt and the blocks so far
    wide = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    seq = list(prompt) + list(req.tokens)
    fixed = [-1] * (prompt_len - whole) + list(req.fixed_at)
    sure = [1.0] * (prompt_len - whole) + list(req.confidence)
    worst = drift = 0.0
    for start in range(whole, len(seq) - B + 1, B):
        at = fixed[start - whole:start - whole + B]
        for s in range(max(at) + 1):
            block = [t if f < s else cfg.mask_token_id
                     for t, f in zip(seq[start:start + B], at)]
            logits = np.array(sdar.forward_logits(
                wide, cfg, jnp.asarray(seq[:start] + block)))[start:]
            logits[:, cfg.mask_token_id] = -np.inf
            for j in (j for j in range(B) if at[j] == s):
                served = float(logits[j, seq[start + j]])
                worst = max(worst, float(logits[j].max()) - served)
                # the confidence the pass returned against the replay's
                top = float(logits[j].max())
                lse = top + math.log(float(np.exp(logits[j] - top).sum()))
                drift = max(drift, abs(
                    math.log(sure[start - whole + j]) - (served - lse)))
    _require(worst <= LOGIT_MARGIN,
             f"block_diffusion: a served token lies {worst} under its "
             "position's best logit at the pass that fixed it")
    _require(drift <= LOGIT_MARGIN,
             f"block_diffusion: a returned confidence lies {drift} (in "
             "logs) from the replay's probability of its token")
    return {"blocks_committed": blocks, "block_passes": stats["block_passes"],
            "max_logit_deficit": worst, "max_confidence_drift": drift,
            "fixed_at": list(req.fixed_at)}


# ---------------------------------------------------------------------------
# phase 2d: a recurrent state beside a paged cache, through the kernel paths
# ---------------------------------------------------------------------------

def phase_state_group(hidden=256, heads=2, head_dim=128, rank=128, rope=128,
                      width=128, experts=8, picks=2, vocab=512,
                      prompt_lens=(130, 70), max_news=(18, 3), bucket=256,
                      page=128, force_kernels=False):
    """Two requests, one behind the other, of a small hybrid model
    (models/kimi_linear: 1 dense + 4 layers with one latent, lane-aligned
    widths, bfloat16, half of the experts held) through the engine: the
    flash prefill of the latent layer and the chunked scan of the KDA
    layers through ops/kda_chunk in both buckets (a prompt of 130 rows in
    a bucket of 256: the state written is the one AT row 130, and the
    bucket's fourth chunk is passed by; one of 70 in a bucket of 128),
    then chunks of recurrent steps through ops/kda_step beside the latent
    paged kernel, the slot's state a block of a float32 state group. Every
    served token's logit against its position's largest by the program's
    own float32 forward over the whole sequence (`kda_chunked` in
    `jax.numpy`, no cache: a prefill that is not finite fails here).
    `force_kernels` (the CPU test): take the kernel paths interpreted."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.models import _delta, _latent, kimi_linear
    from paddle_tpu.serving import ServingConfig, ServingEngine

    cfg = kimi_linear.KimiLinearConfig(
        vocab_size=vocab, hidden=hidden, layers=5, heads=heads,
        kv_lora_rank=rank, qk_nope_head_dim=head_dim, qk_rope_head_dim=rope,
        v_head_dim=head_dim, kda_heads=heads, kda_head_dim=head_dim,
        intermediate=2 * hidden, moe_intermediate=width,
        n_routed_experts=experts, experts_per_tok=picks,
        experts_held=(experts // 2, experts // 2), kda_decay_rank=head_dim,
        kda_gate_rank=head_dim, max_pos=max(4 * page, 2 * bucket),
        init_range=0.05, name="kimi-linear-smoke")
    params = kimi_linear.init_params(cfg, jax.random.PRNGKey(45), jnp.bfloat16)
    model = cfg.serving_model()
    forced = (_latent.decode_attention_path, kimi_linear.recurrence_path,
              kimi_linear.prefill_recurrence_path,
              type(model).prefill_attention_path)
    if force_kernels:
        # the programs' and the verdicts' one source
        _latent.decode_attention_path = lambda a, c=None: "latent_paged_kernel"
        kimi_linear.recurrence_path = lambda cfg: "kernel"
        # (the float32 forward below is `jax.numpy`'s whatever this says)
        kimi_linear.prefill_recurrence_path = \
            lambda cfg, bucket=None: "kernel"
        type(model).prefill_attention_path = lambda self, a, b, c=None: "flash"
    try:
        engine = ServingEngine(params, cfg, ServingConfig(
            num_slots=2, prefill_buckets=(bucket // 2, bucket),
            max_len=cfg.max_pos, block_size=page, decode_chunk=8))
        rng = np.random.default_rng(45)
        served = []
        for prompt_len, max_new in zip(prompt_lens, max_news):
            prompt = rng.integers(0, vocab, prompt_len)
            req = engine.submit(prompt, max_new)
            engine.run_until_drained()
            served.append((prompt, req, max_new))
        stats = engine.stats()
        wide = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        worst = 0.0
        for prompt, req, max_new in served:
            _require(req.state == "finished" and len(req.tokens) == max_new,
                     f"state_group: {len(req.tokens)} of {max_new} tokens "
                     "served")
            logits = np.asarray(kimi_linear.forward_logits(
                wide, cfg, jnp.asarray(list(prompt) + list(req.tokens)))
            )[len(prompt) - 1:-1]
            deficit = logits.max(-1) - logits[np.arange(max_new),
                                              np.asarray(req.tokens)]
            _require(float(deficit.max()) <= LOGIT_MARGIN,
                     f"state_group: a served token lies {float(deficit.max())}"
                     " under its position's best logit")
            worst = max(worst, float(deficit.max()))
    finally:
        (_latent.decode_attention_path, kimi_linear.recurrence_path,
         kimi_linear.prefill_recurrence_path,
         type(model).prefill_attention_path) = forced
    state = stats["state"]
    _require(stats["decode_attention"] == {"latent": "latent_paged_kernel"}
             and state["recurrence_path"] == "kernel"
             and state["prefill_recurrence_path"] == "kernel"
             and state["prefill_kernel_buckets"] == [bucket // 2, bucket],
             "state_group: a step gathered or the recurrence ran in XLA: "
             f"{stats['decode_attention']}, {state}")
    _require(state["peak_blocks_used"] == 2 and state["blocks_used"] == 0,
             f"state_group: the slot's two state blocks: {state}")
    chunks = sum(-(-n // _delta.KDA_CHUNK) for n in prompt_lens)
    _require(stats["kda_state_steps"] == 4 * (sum(max_news) - len(max_news))
             and stats["kda_prefill_rows"] == 4 * sum(prompt_lens)
             and stats["kda_prefill_chunks"] == 4 * chunks,
             f"state_group: {stats['kda_state_steps']} state steps, "
             f"{stats['kda_prefill_rows']} prefill rows in "
             f"{stats['kda_prefill_chunks']} chunks (of {4 * chunks} live)")
    return {"state": state, "kda_state_steps": stats["kda_state_steps"],
            "kda_prefill_chunks": stats["kda_prefill_chunks"],
            "max_logit_deficit": worst}


# ---------------------------------------------------------------------------
# phase 2e: a state-space mixer's state beside a paged cache
# ---------------------------------------------------------------------------

def phase_state_space(hidden=256, heads=2, kv_heads=1, mamba_heads=8,
                      mamba_head_dim=64, mamba_state=128, width=128,
                      experts=8, picks=3, vocab=512, prompt_lens=(200, 70),
                      max_news=(18, 3), bucket=256, page=128, chunk=64,
                      force_kernels=False):
    """Two requests, one behind the other, of a small hybrid model
    (models/granite_hybrid: three Mamba-2 layers and one position-free
    grouped-query attention layer, lane-aligned widths, bfloat16, half of
    the experts held) through the engine: the flash prefill of the
    attention layer at the published scale and the chunked scan of the
    mamba layers ending MID-BUCKET (a prompt of 200 rows in a bucket of
    256, in chunks of 64: the state written is the one AT row 200; one of
    70 in a bucket of 128), then chunks of recurrent steps through
    ops/ssd_step beside the grouped paged kernel, the slot's state a block
    of a float32 state group. Every served token's logit against its
    position's largest by the program's own float32 forward over the whole
    sequence (no cache, no kernel). `force_kernels` (the CPU test): take
    the kernel paths interpreted."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.models import _grouped, granite_hybrid
    from paddle_tpu.serving import ServingConfig, ServingEngine

    cfg = granite_hybrid.GraniteHybridConfig(
        vocab_size=vocab, hidden=hidden, layers=4, heads=heads,
        kv_heads=kv_heads,
        layer_types=("mamba", "attention", "mamba", "mamba"),
        mamba_heads=mamba_heads, mamba_head_dim=mamba_head_dim,
        mamba_state=mamba_state, mamba_chunk=chunk, moe_intermediate=width,
        shared_intermediate=2 * width, n_routed_experts=experts,
        experts_per_tok=picks, experts_held=(experts // 2, experts // 2),
        max_pos=max(4 * page, 2 * bucket), init_range=0.05,
        name="granite-hybrid-smoke")
    params = granite_hybrid.init_params(cfg, jax.random.PRNGKey(54),
                                        jnp.bfloat16)
    forced = (_grouped.decode_attention_path, granite_hybrid.recurrence_path,
              _grouped.prefill_attention_path)
    if force_kernels:
        # the programs' and the verdicts' one source
        _grouped.decode_attention_path = \
            lambda a, c=None: {"full": "paged_kernel"}
        granite_hybrid.recurrence_path = lambda cfg: "kernel"
        _grouped.prefill_attention_path = lambda a, b, c=None: "flash"
    try:
        engine = ServingEngine(params, cfg, ServingConfig(
            num_slots=2, prefill_buckets=(bucket // 2, bucket),
            max_len=cfg.max_pos, block_size=page, decode_chunk=8))
        rng = np.random.default_rng(54)
        served = []
        for prompt_len, max_new in zip(prompt_lens, max_news):
            prompt = rng.integers(0, vocab, prompt_len)
            req = engine.submit(prompt, max_new)
            engine.run_until_drained()
            served.append((prompt, req, max_new))
        stats = engine.stats()
        wide = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        worst = 0.0
        for prompt, req, max_new in served:
            _require(req.state == "finished" and len(req.tokens) == max_new,
                     f"state_space: {len(req.tokens)} of {max_new} tokens "
                     "served")
            logits = np.asarray(granite_hybrid.forward_logits(
                wide, cfg, jnp.asarray(list(prompt) + list(req.tokens)))
            )[len(prompt) - 1:-1]
            deficit = logits.max(-1) - logits[np.arange(max_new),
                                              np.asarray(req.tokens)]
            # the logits are the published ones, after / logits_scaling
            _require(float(deficit.max())
                     <= LOGIT_MARGIN / cfg.logits_scaling,
                     f"state_space: a served token lies {float(deficit.max())}"
                     " under its position's best logit")
            worst = max(worst, float(deficit.max()))
    finally:
        (_grouped.decode_attention_path, granite_hybrid.recurrence_path,
         _grouped.prefill_attention_path) = forced
    state = stats["state"]
    _require(stats["decode_attention"] == {"full": "paged_kernel"}
             and state["recurrence_path"] == "kernel"
             and stats["prefill_attention"]["path"] == "flash",
             "state_space: a step gathered or the recurrence ran in XLA: "
             f"{stats['decode_attention']}, {state}, "
             f"{stats['prefill_attention']}")
    _require(state["peak_blocks_used"] == 2 and state["blocks_used"] == 0,
             f"state_space: the slot's two state blocks: {state}")
    _require(stats["ssd_state_steps"] == 3 * (sum(max_news) - len(max_news))
             and stats["ssd_prefill_rows"] == 3 * sum(prompt_lens),
             f"state_space: {stats['ssd_state_steps']} state steps, "
             f"{stats['ssd_prefill_rows']} prefill rows")
    return {"state": state, "ssd_state_steps": stats["ssd_state_steps"],
            "ssd_prefill_rows": stats["ssd_prefill_rows"],
            "max_logit_deficit": worst}


def phase_gated_delta(hidden=256, heads=2, kv_heads=1, head_dim=256,
                      key_heads=2, value_heads=4, width=128, experts=8,
                      picks=3, vocab=512, prompt_lens=(200, 70),
                      max_news=(18, 3), bucket=256, page=128,
                      force_kernels=False):
    """Two requests, one behind the other, of a small hybrid model
    (models/qwen3_next: three Gated-DeltaNet layers, key heads shared 2 : 1
    by the value heads under a SCALAR decay a head, and one GATED
    grouped-query attention layer of head size 256 with a partial rotation,
    lane-aligned widths, bfloat16, half of the experts held, the shared
    expert weighed by the token) through the engine: the flash prefill at d
    = dv = 256 and the scan kernel ops/kda_chunk over broadcast operands
    ending MID-BUCKET (a prompt of 200 rows in a bucket of 256: the state
    written is the one AT row 200, the fourth chunk's; one of 70 in a bucket
    of 128), then chunks of recurrent steps through ops/kda_step beside the
    grouped paged kernel over (kv_heads, 128, 512) pages, the slot's state a
    block of a float32 state group. Every served token's logit against its
    position's largest by the program's own float32 forward over the whole
    sequence (no cache, no kernel). `force_kernels` (the CPU test): take
    the kernel paths interpreted."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.models import _delta, _grouped, qwen3_next
    from paddle_tpu.serving import ServingConfig, ServingEngine

    cfg = qwen3_next.Qwen3NextConfig(
        vocab_size=vocab, hidden=hidden, layers=4, heads=heads,
        kv_heads=kv_heads, head_dim=head_dim, gdn_key_heads=key_heads,
        gdn_value_heads=value_heads, moe_intermediate=width,
        shared_intermediate=width, n_routed_experts=experts,
        experts_per_tok=picks, experts_held=(experts // 2, experts // 2),
        max_pos=max(4 * page, 2 * bucket), init_range=0.05,
        name="qwen3-next-smoke")
    params = qwen3_next.init_params(cfg, jax.random.PRNGKey(56),
                                    jnp.bfloat16)
    forced = (_grouped.decode_attention_path, qwen3_next.recurrence_path,
              qwen3_next.prefill_recurrence_path,
              _grouped.prefill_attention_path)
    if force_kernels:
        # the programs' and the verdicts' one source
        _grouped.decode_attention_path = \
            lambda a, c=None: {"full": "paged_kernel"}
        qwen3_next.recurrence_path = lambda cfg: "kernel"
        qwen3_next.prefill_recurrence_path = \
            lambda cfg, bucket=None: "kernel"
        _grouped.prefill_attention_path = lambda a, b, c=None: "flash"
    try:
        engine = ServingEngine(params, cfg, ServingConfig(
            num_slots=2, prefill_buckets=(bucket // 2, bucket),
            max_len=cfg.max_pos, block_size=page, decode_chunk=8))
        rng = np.random.default_rng(56)
        served = []
        for prompt_len, max_new in zip(prompt_lens, max_news):
            prompt = rng.integers(0, vocab, prompt_len)
            req = engine.submit(prompt, max_new)
            engine.run_until_drained()
            served.append((prompt, req, max_new))
        stats = engine.stats()
        wide = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        worst = 0.0
        for prompt, req, max_new in served:
            _require(req.state == "finished" and len(req.tokens) == max_new,
                     f"gated_delta: {len(req.tokens)} of {max_new} tokens "
                     "served")
            logits = np.asarray(qwen3_next.forward_logits(
                wide, cfg, jnp.asarray(list(prompt) + list(req.tokens)))
            )[len(prompt) - 1:-1]
            deficit = logits.max(-1) - logits[np.arange(max_new),
                                              np.asarray(req.tokens)]
            _require(float(deficit.max()) <= LOGIT_MARGIN,
                     f"gated_delta: a served token lies {float(deficit.max())}"
                     " under its position's best logit")
            worst = max(worst, float(deficit.max()))
    finally:
        (_grouped.decode_attention_path, qwen3_next.recurrence_path,
         qwen3_next.prefill_recurrence_path,
         _grouped.prefill_attention_path) = forced
    state = stats["state"]
    _require(stats["decode_attention"] == {"full": "paged_kernel"}
             and state["recurrence_path"] == "kernel"
             and state["prefill_recurrence_path"] == "kernel"
             and stats["prefill_attention"]["path"] == "flash",
             "gated_delta: a step gathered or a recurrence ran in XLA: "
             f"{stats['decode_attention']}, {state}, "
             f"{stats['prefill_attention']}")
    _require(state["peak_blocks_used"] == 2 and state["blocks_used"] == 0,
             f"gated_delta: the slot's two state blocks: {state}")
    chunks = sum(-(-n // _delta.KDA_CHUNK) for n in prompt_lens)
    _require(stats["gdn_state_steps"] == 3 * (sum(max_news) - len(max_news))
             and stats["gdn_prefill_rows"] == 3 * sum(prompt_lens)
             and stats["gdn_prefill_chunks"] == 3 * chunks,
             f"gated_delta: {stats['gdn_state_steps']} state steps, "
             f"{stats['gdn_prefill_rows']} prefill rows, "
             f"{stats['gdn_prefill_chunks']} chunks visited")
    return {"state": state, "gdn_state_steps": stats["gdn_state_steps"],
            "gdn_prefill_rows": stats["gdn_prefill_rows"],
            "gdn_prefill_chunks": stats["gdn_prefill_chunks"],
            "max_logit_deficit": worst}


# ---------------------------------------------------------------------------
# phase 3: train
# ---------------------------------------------------------------------------

def phase_train(cfg, batch, seq, steps=3, learning_rate=1e-4,
                data_parallel=False, one_chip_losses=None,
                reference=False):
    """`steps` Adam steps of the causal-LM program on ONE repeated batch
    through pt.Executor (bf16 AMP, dropout must be 0 in cfg so that the
    loss falling is not left to luck). data_parallel runs the same step
    under CompiledProgram.with_data_parallel over every device, and
    one_chip_losses (same sizes, one device) is what it must reproduce.
    Returns the losses, the scope (the serve phase reads its weights),
    how many Mosaic custom calls the compiled step holds, and per step
    how many scope variables the executor handed to its plan's _put
    (`scope_vars_placed`) with the runs that found every one in place:
    only the first step after the startup program may place any.
    `loss_lowerings` is how often the step's loss gradient was lowered
    from the saved row log-sum-exp and how often in a program that keeps
    a vocabulary-wide softmax: the causal-LM program must read (>= 1, 0).
    reference=True also holds the first loss against the float32
    reference of the training cells on the same weights and batch
    (`reference_loss`, `loss_rel_gap`, limit LOSS_REL_TOL)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models.gpt import gpt_lm_program
    from paddle_tpu.observability.metrics import get_registry

    def counted(name):
        return int(get_registry().counter(name).value)

    _require(cfg.dropout == 0.0, "train: cfg.dropout must be 0")
    main, startup, fetches = gpt_lm_program(
        cfg, seq, learning_rate=learning_rate, amp=True)
    loss_var = fetches["loss"]
    target = main
    if data_parallel:
        target = pt.CompiledProgram(main).with_data_parallel(
            loss_name=loss_var.name)
    rng = np.random.RandomState(seq)
    feed = {"tokens": jnp.asarray(rng.randint(
        0, cfg.vocab_size, (batch, seq)).astype(np.int64))}

    exe = pt.Executor()
    scope = pt.Scope()
    losses, placed_by_step = [], []
    lowerings = ("loss_lse_lowerings_total",
                 "loss_softmax_kept_lowerings_total")
    with pt.scope_guard(scope):
        exe.run(startup)
        if reference:
            # a copy: the executor donates its buffers to the next step
            bench_model, gpt2_ref = _cells_reference()
            initial = bench_model.scope_params(scope, {"n_layer": cfg.layers})
        lowered0 = [counted(name) for name in lowerings]
        in_place0 = counted("executor_scope_in_place_runs_total")
        for step in range(steps):
            # the optimized HLO of the step, once: it is a second
            # lower+compile of the same computation
            exe.capture_hlo = step == 0
            before = counted("executor_scope_vars_placed_total")
            out, = exe.run(target, feed=feed, fetch_list=[loss_var])
            placed_by_step.append(
                counted("executor_scope_vars_placed_total") - before)
            losses.append(float(np.asarray(out).reshape(-1)[0]))
        in_place = counted("executor_scope_in_place_runs_total") - in_place0
        lowered = [counted(name) - before
                   for name, before in zip(lowerings, lowered0)]
    tag = f"train: b={batch} s={seq}" + (" dp" if data_parallel else "")
    _require(lowered[0] >= 1 and lowered[1] == 0,
             f"{tag}: the loss gradient was lowered {lowered[0]} times from "
             f"the saved log-sum-exp and {lowered[1]} times with a "
             "vocabulary-wide softmax kept; expected (>= 1, 0)")
    _require(not any(placed_by_step[1:]) and in_place >= steps - 1,
             f"{tag}: a step after the first placed scope variables "
             f"(by step {placed_by_step}; {in_place} of {steps} runs "
             "found the scope in place)")
    _require(bool(placed_by_step[0]) == bool(data_parallel),
             f"{tag}: the first step placed {placed_by_step[0]} scope "
             "variables")
    if data_parallel:
        # replicated parameters, one copy per chip: a plan that had only
        # met a virtual mesh could leave everything on the first device
        placed = len(scope.find_var("gpt/wte").sharding.device_set)
        _require(placed == jax.device_count(),
                 f"{tag}: parameters sit on {placed} of "
                 f"{jax.device_count()} devices")
    _require(exe.last_hlo is not None,
             f"{tag}: no HLO captured "
             f"({getattr(exe, 'last_hlo_error', 'no error recorded')})")
    mosaic_calls = exe.last_hlo.count(MOSAIC_TARGET)
    if jax.default_backend() == "tpu":
        # auto dispatch takes the kernel at s >= 256 on a TPU; a step
        # that fell back to the einsum reference must not pass
        _require(mosaic_calls >= 2 * cfg.layers,
                 f"{tag}: compiled step holds {mosaic_calls} "
                 f"{MOSAIC_TARGET} calls, expected a forward and a "
                 f"backward kernel in each of {cfg.layers} layers")
    _require(all(np.isfinite(losses)), f"{tag}: losses {losses}")
    _require(losses[-1] < losses[0],
             f"{tag}: loss did not fall on a repeated batch: {losses}")
    facts = {"losses": losses, "mosaic_calls": mosaic_calls,
             "scope_vars_placed": placed_by_step, "runs_in_place": in_place,
             "loss_lowerings": lowered, "scope": scope}
    if reference:
        want = gpt2_ref.batch_loss(initial, np.asarray(feed["tokens"]),
                                   cfg.heads)
        gap = abs(losses[0] - want) / abs(want)
        facts.update(reference_loss=want, loss_rel_gap=gap)
        _require(gap <= LOSS_REL_TOL,
                 f"{tag}: first loss {losses[0]} is {gap:.3g} relative from "
                 f"the float32 reference's {want} (limit {LOSS_REL_TOL})")
    if one_chip_losses is not None:
        gap = max(abs(a - b) / abs(a)
                  for a, b in zip(one_chip_losses, losses))
        facts["max_rel_gap_vs_one_chip"] = gap
        _require(gap <= DP_LOSS_REL_TOL,
                 f"{tag}: losses {losses} differ from the one-chip "
                 f"{one_chip_losses} by {gap:.3g} relative "
                 f"(tolerance {DP_LOSS_REL_TOL})")
    return facts


def _cells_reference():
    """The training cells' own reader of a scope's weights and their
    float32 reference (benchmarks/lib/model.py, reference/gpt2_ref.py)."""
    import os
    bench = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from lib import model
    from reference import gpt2_ref
    return model, gpt2_ref


# ---------------------------------------------------------------------------
# phases 4 and 5: serve, placement
# ---------------------------------------------------------------------------

def _generate(port, payload, timeout):
    """POST /v1/generate. Returns (status, tokens, done) where done
    carries finish_reason and the server's token count; SSE unless
    payload["stream"] is False."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/v1/generate", json.dumps(payload),
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        if r.status != 200:
            return r.status, [], {"error": r.read().decode(errors="replace")}
        if payload.get("stream") is False:
            body = json.loads(r.read())
            return r.status, body["tokens"], {
                "finish_reason": body["finish_reason"],
                "tokens": len(body["tokens"])}
        tokens, done, event = [], None, "message"
        for line in iter(r.readline, b""):
            line = line.decode().rstrip("\n")
            if not line:
                event = "message"
            elif line.startswith("event: "):
                event = line[7:]
            elif line.startswith("data: "):
                obj = json.loads(line[6:])
                if event == "done":
                    done = obj
                else:
                    tokens.append(obj["token"])
        return r.status, tokens, done or {}
    finally:
        conn.close()


def _get_json(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, json.loads(r.read())
    finally:
        conn.close()


def _reference_deficits(params, cfg, sequences, prompt_lens):
    """How far below its position's maximum each served token's logit
    is, on gpt_forward_logits in float32 at highest matmul precision.
    sequences: prompt + output per request; padded to one length (the
    causal mask keeps the padding out of every real position)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.gpt_decode import gpt_forward_logits

    f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    width = max(len(s) for s in sequences)
    padded = np.zeros((len(sequences), width), np.int32)
    for i, s in enumerate(sequences):
        padded[i, :len(s)] = s
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(jax.jit(
            lambda p, t: gpt_forward_logits(p, cfg, t))(f32, padded))
    deficits = []
    for i, (s, p_len) in enumerate(zip(sequences, prompt_lens)):
        # output token j was sampled from the logits at position
        # p_len - 1 + j
        pos = np.arange(p_len - 1, len(s) - 1)
        rows = logits[i, pos]
        chosen = rows[np.arange(len(pos)), np.asarray(s[p_len:])]
        deficits.append(rows.max(-1) - chosen)
    return np.concatenate(deficits), float(logits.std())


def _placement(platform):
    """Phase 5: every live jax array sits on a `platform` device."""
    import jax

    live = jax.live_arrays()
    stray = [(a.shape, str(a.dtype), sorted(d.platform for d in a.devices()))
             for a in live
             if any(d.platform != platform for d in a.devices())]
    _require(not stray, f"placement: {len(stray)} of {len(live)} live "
             f"arrays off {platform}: {stray[:5]}")
    return len(live)


def _mesh_facts(engine, tp):
    """Four-chip layout: a column-parallel weight and the KV arena each
    split over `tp` distinct devices, pool_bytes / tp on each chip."""
    w = engine.scheduler.params["blocks"][0]["q"]["w"]
    arena = engine.kv.kv
    for name, arr in (("q.w", w), ("arena", arena)):
        devices = {sh.device for sh in arr.addressable_shards}
        _require(len(devices) == tp,
                 f"serve: {name} sits on {len(devices)} devices, "
                 f"expected {tp}: {sorted(map(str, devices))}")
    _require(w.addressable_shards[0].data.shape[1] * tp == w.shape[1],
             f"serve: q.w shard {w.addressable_shards[0].data.shape} "
             f"of {w.shape} is not a 1/{tp} column slice")
    per_chip = {sh.data.nbytes for sh in arena.addressable_shards}
    expected = engine.kv.pool_bytes // tp
    _require(per_chip == {expected} == {engine.kv.hbm_per_chip_bytes},
             f"serve: arena bytes per chip {sorted(per_chip)}, reported "
             f"{engine.kv.hbm_per_chip_bytes}, expected pool_bytes/{tp} "
             f"= {expected}")
    return {"arena_bytes_per_chip": expected}


def phase_serve(params, cfg, prompt_lens, shared_prefix, max_new_tokens,
                num_slots, prefill_buckets, max_len, mesh_shape=None,
                request_timeout=600.0):
    """pt.server.serve over `params`, every option but the sizes at its
    default. len(prompt_lens) concurrent POST /v1/generate from threads
    of this process; requests 0 and 1 share their first `shared_prefix`
    tokens; even requests are greedy, odd ones sampled from a seed;
    requests 2 and 3 ask for one JSON body, the rest stream SSE. Runs
    the placement check before shutdown."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu.serving import ServingConfig

    n = len(prompt_lens)
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, cfg.vocab_size, (p_len,)).tolist()
               for p_len in prompt_lens]
    prompts[1][:shared_prefix] = prompts[0][:shared_prefix]
    payloads = []
    for i, prompt in enumerate(prompts):
        body = {"prompt": prompt, "max_new_tokens": max_new_tokens}
        if i % 2:
            body.update(temperature=0.8, seed=1000 + i)
        if i in (2, 3):
            body["stream"] = False
        payloads.append(body)

    server = pt.server.serve(params, cfg, pt.server.ServerConfig(
        port=0, replicas=1, serving=ServingConfig(
            num_slots=num_slots, prefill_buckets=prefill_buckets,
            max_len=max_len, mesh_shape=mesh_shape)))
    facts = {}
    try:
        engine = server.router.replicas[0].engine
        results = [None] * n

        def client(i):
            try:
                results[i] = _generate(server.port, payloads[i],
                                       request_timeout)
            except Exception as e:   # reported below, per request
                results[i] = e

        threads = [threading.Thread(target=client, args=(i,),
                                    name=f"smoke-client-{i}")
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(request_timeout + 30.0)
        _require(not any(t.is_alive() for t in threads),
                 "serve: a client thread is still waiting")

        for i, res in enumerate(results):
            _require(not isinstance(res, Exception) and res is not None,
                     f"serve: request {i} raised {res!r}")
            status, tokens, done = res
            _require(status == 200, f"serve: request {i} HTTP {status}: "
                     f"{done}")
            reason = done.get("finish_reason")
            _require(reason in ("length", "stop"),
                     f"serve: request {i} finish_reason {reason!r}")
            _require(len(tokens) == done.get("tokens") == max_new_tokens,
                     f"serve: request {i} streamed {len(tokens)} tokens, "
                     f"server counted {done.get('tokens')}, asked for "
                     f"{max_new_tokens}")
            _require(all(0 <= t < cfg.vocab_size for t in tokens),
                     f"serve: request {i} token out of range")

        status, health = _get_json(server.port, "/healthz")
        _require(status == 200, f"serve: /healthz HTTP {status}")
        _require(health["replica_failures"] == 0
                 and health["replica_restarts"] == 0,
                 f"serve: replica_failures={health['replica_failures']} "
                 f"replica_restarts={health['replica_restarts']}")
        _require(all(r["state"] == "ok" for r in health["replicas"]),
                 f"serve: replica states "
                 f"{[r['state'] for r in health['replicas']]}")

        stats = engine.stats()
        # scheduler.py "Compile discipline": one prefill per bucket, one
        # fused decode chunk, one admission sampler, one release (built
        # on the first cancel; none is sent here)
        bound = len(prefill_buckets) + 3
        _require(stats["compiled_executables"] <= bound,
                 f"serve: {stats['compiled_executables']} executables, "
                 f"documented bound {bound}: "
                 f"{engine.scheduler.compile_events}")
        facts.update(compiled_executables=stats["compiled_executables"],
                     prefix_hits=stats["prefix_hits"],
                     pool_bytes=stats["pool_bytes"],
                     decode_attention=stats["decode_attention"],
                     prefill_attention=stats["prefill_attention"])

        greedy = [i for i in range(n) if i % 2 == 0]
        deficits, logit_std = _reference_deficits(
            params, cfg,
            [prompts[i] + results[i][1] for i in greedy],
            [prompt_lens[i] for i in greedy])
        facts.update(max_logit_deficit=float(deficits.max()),
                     exact_argmax_share=float((deficits == 0).mean()),
                     reference_logit_std=logit_std)
        _require(deficits.max() <= LOGIT_MARGIN,
                 f"serve: a greedy token sits {deficits.max():.3f} below "
                 f"the float32 reference's best logit (margin "
                 f"{LOGIT_MARGIN}, logit std {logit_std:.3f})")

        if mesh_shape is not None:
            facts.update(_mesh_facts(engine, mesh_shape[0]))
        facts["live_arrays"] = _placement(jax.default_backend())
    finally:
        server.shutdown()
    used = engine.kv.blocks_used
    _require(used == 0, f"serve: {used} KV blocks in use after shutdown")
    return facts


# ---------------------------------------------------------------------------
# the command
# ---------------------------------------------------------------------------

def _versions():
    import jax
    import jaxlib
    from importlib import metadata

    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": metadata.version("libtpu")}


def _fmt(value):
    if isinstance(value, float):
        return f"{value:.4g}"
    if isinstance(value, list):
        return "[" + ",".join(_fmt(v) for v in value) + "]"
    return str(value)


def main():
    import jax

    platform = jax.default_backend()
    if platform != "tpu":
        print(f"chip_smoke: jax's default backend is {platform!r}, not "
              "'tpu'; this command runs on the chip only",
              file=sys.stderr)
        return 1
    import jax.numpy as jnp
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.models.gpt_decode import collect_gpt_params
    from paddle_tpu.observability.device_peaks import device_report
    from paddle_tpu.utils.compile_cache import ensure_compile_cache

    device = device_report()
    chips = device["count"]
    print(f"phase=device ok platform={device['platform']} "
          f"kind={device['kind']!r} chips={chips} "
          f"compile_cache={ensure_compile_cache()}", flush=True)

    clock = CompileClock()
    phases = {}
    failed = []

    def run(name, fn, *args, **kwargs):
        t0, c0, h0 = time.monotonic(), clock.compile_s, clock.cache_hits
        try:
            facts = fn(*args, **kwargs)
        except Exception as e:
            import traceback
            traceback.print_exc()
            failed.append(name)
            print(f"phase={name} FAILED {type(e).__name__}: {e}",
                  flush=True)
            return None
        shown = {k: v for k, v in facts.items() if k != "scope"}
        shown.update(wall_s=round(time.monotonic() - t0, 1),
                     compile_s=round(clock.compile_s - c0, 1),
                     cache_hits=clock.cache_hits - h0)
        phases[name] = shown
        print(f"phase={name} ok "
              + " ".join(f"{k}={_fmt(v)}" for k, v in shown.items()),
              flush=True)
        return facts

    cfg = GPTConfig(dropout=0.0)     # GPT-2-small, every width published
    serve_sizes = dict(
        prompt_lens=(56, 60, 20, 100, 150, 200, 40, 256),
        shared_prefix=48, max_new_tokens=32, num_slots=8,
        prefill_buckets=(64, 256), max_len=1024)

    run("kernels", phase_kernels)
    run("share_kernels", phase_share_kernels)
    run("block_diffusion", phase_block_diffusion)
    run("state_group", phase_state_group)
    run("state_space", phase_state_space)
    run("gated_delta", phase_gated_delta)
    # the published context (tiled kernels), then s=512 (single-pass)
    long_run = run("train_s1024", phase_train, cfg, batch=8, seq=1024,
                   reference=True)
    run("train_s512", phase_train, cfg, batch=16, seq=512)
    params = None
    if long_run is not None:
        params = collect_gpt_params(long_run["scope"], cfg,
                                    dtype=jnp.bfloat16)
        serve = run("serve", phase_serve, params, cfg, **serve_sizes)
        if serve is not None:
            print(f"phase=placement ok live_arrays={serve['live_arrays']} "
                  f"platform={platform}", flush=True)
    else:
        failed.append("serve")
        print("phase=serve FAILED no weights: train_s1024 failed",
              flush=True)

    four_chips = "ran" if chips >= 4 else f"not run: chips={chips}"
    if chips < 4:
        print(f"phase=four_chips not run chips={chips} (needs 4)",
              flush=True)
    elif params is not None:
        run("serve_tp4", phase_serve, params, cfg, mesh_shape=(4,),
            **serve_sizes)
        run("train_dp4", phase_train, cfg, batch=8, seq=1024,
            data_parallel=True, one_chip_losses=long_run["losses"])

    if failed:
        print(f"chip_smoke: FAILED phases: {', '.join(failed)}",
              file=sys.stderr)
    # what the whole run traced, lowered, compiled or loaded, by the
    # program's own compile log (the benchmark's set-up metrics read the
    # same records): a warm machine shows hits and cache_load_s, a cold
    # one misses and backend_compile_s
    from paddle_tpu.observability import compile_log
    compiled = compile_log().totals()
    print("compile_log executables={executables} hit={hit} miss={miss} "
          "off={off} trace_s={trace_s:.1f} lower_s={lower_s:.1f} "
          "backend_compile_s={backend_compile_s:.1f} "
          "cache_load_s={cache_load_s:.1f} inner_traces={inner_traces}"
          .format(**compiled, **compiled["cache"]), flush=True)
    print("summary=" + json.dumps(
        {"versions": _versions(), "four_chips": four_chips,
         "failed": failed, "phases": phases, "compile_log": compiled,
         "claim": None}), flush=True)
    # The last line of stdout is the verdict and the device as jax
    # reports it, these keys and no others: the driver's check reads it.
    print(json.dumps({"ok": not failed, "device": device}), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
