"""KV-cache autoregressive decoding for the GPT family.

The reference's incremental-decode contract is O(1) state per step: its
RNN decoder reads the previous step's state from a tensor array and never
re-runs the prefix (python/paddle/fluid/tests/book/
test_machine_translation.py:110-136 `pd.array_read(state_array, i=counter)`
feeding `pd.beam_search`; operators/beam_search_op.cc). This module is the
TPU-native form of that contract for a decoder-only transformer:

  * a PREFILL pass runs the whole prompt once and fills a KV cache of
    shape (layers, 2, b, heads, max_len, head_dim),
  * a DECODE step consumes one token + the cache (dynamic_update_slice at
    position t, masked attention over [0, t]) — O(max_len·d) per step
    instead of the O(t²·model) full-prefix recompute,
  * the whole sampling loop (greedy / top-k / temperature) runs inside
    ONE jitted lax.fori_loop — a single dispatch for the entire
    generation, no per-step host round trips.

Weights are read from the training scope by the var names gpt_lm_program
creates, so a trained static-graph model generates without any export
step. Forward math mirrors models/gpt.py exactly (pre-LN, separate
q/k/v, tanh gelu, tied wte head, f32 LN stats).

The engine serves this family through the paged forms below
(gpt_prefill_pages, gpt_decode_step_pages, gpt_decode_verify_pages): one
prefill, one decode step and one multi-position verify pass over a block
arena. The fused chunk loop around the step, its sampler, its finish rule
and the speculative drafter are the engine's (serving/decode_loop.py).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from ..serving import pages as _pages
from . import _decoder

__all__ = ["collect_gpt_params", "quantize_params", "gpt_forward_logits",
           "gpt_prefill", "gpt_decode_step", "gpt_prefill_pages",
           "gpt_decode_step_pages", "gpt_decode_verify_pages",
           "paged_arena_shapes", "decode_attention_path",
           "prefill_attention_path", "gpt_generate",
           "ADAPTER_PROJECTIONS", "GPT_SERVING_MODEL"]

# projections the low-rank adapter path covers (every matmul in the
# block: attention q/k/v/out + both MLP projections)
ADAPTER_PROJECTIONS = ("q", "k", "v", "out", "mlp1", "mlp2")


def _ln_names(name):
    return f"{name}.scale", f"{name}.bias"


def collect_gpt_params(scope, cfg, prefix="gpt", dtype=None):
    """Pull the GPT parameter pytree out of an executor scope (the vars
    models/gpt.py's programs create). dtype=jnp.bfloat16 casts the copy
    used for decoding (halves HBM traffic; master weights untouched)."""
    import jax.numpy as jnp

    def get(name):
        v = scope.find_var(name)
        if v is None:
            raise KeyError(f"param {name!r} not found in scope")
        arr = jnp.asarray(v)
        return arr.astype(dtype) if dtype is not None else arr

    def ln(name):
        s, b = _ln_names(name)
        return {"g": get(s), "b": get(b)}

    p = {"wte": get(f"{prefix}/wte"), "wpe": get(f"{prefix}/wpe"),
         "lnf": ln(f"{prefix}/lnf"), "blocks": []}
    for i in range(cfg.layers):
        pre = f"{prefix}/l{i}"
        blk = {"ln1": ln(f"{pre}/ln1"), "ln2": ln(f"{pre}/ln2")}
        for nm in ("q", "k", "v", "out", "mlp1", "mlp2"):
            blk[nm] = {"w": get(f"{pre}/{nm}.w"), "b": get(f"{pre}/{nm}.b")}
        p["blocks"].append(blk)
    return p


def quantize_params(params, cfg):
    """Weight-only int8 quantization of the decode parameter pytree:
    the q/k/v/out/mlp1/mlp2 matmul weights become per-OUTPUT-CHANNEL
    abs-max int8 (the reference's FakeChannelWiseQuantizeAbsMax
    discipline, quant_axis=1 for [in, out] mul weights) with f32
    scales; embeddings, layer norms, and biases stay full precision —
    they are a rounding error of the byte budget and the LN statistics
    are the numerics the token-identity tests lean on. The returned
    pytree's quantized projections hold {"w_q": int8 (in, out),
    "w_s": f32 (out,), "b": ...}; _dense applies the dequant IN-GRAPH
    as (x @ w_q) * w_s, so the fp32 weight matrix is never
    materialized — HBM holds one byte per weight plus one scale per
    output channel, and XLA fuses the scale multiply into the matmul's
    consumer. Deterministic: a pure function of the weights, so two
    engines quantizing the same checkpoint serve bit-identical
    streams."""
    import jax.numpy as jnp

    def q(w):
        w32 = jnp.asarray(w).astype(jnp.float32)
        s = jnp.max(jnp.abs(w32), axis=0)            # (out,)
        safe = jnp.where(s > 0, s, 1.0)
        wq = jnp.clip(jnp.round(w32 * (127.0 / safe)),
                      -127, 127).astype(jnp.int8)
        return wq, (s / 127.0).astype(jnp.float32)

    out = {"wte": params["wte"], "wpe": params["wpe"],
           "lnf": params["lnf"], "blocks": []}
    for blk in params["blocks"]:
        nb = {"ln1": blk["ln1"], "ln2": blk["ln2"]}
        for nm in ("q", "k", "v", "out", "mlp1", "mlp2"):
            wq, ws = q(blk[nm]["w"])
            nb[nm] = {"w_q": wq, "w_s": ws, "b": blk[nm]["b"]}
        out["blocks"].append(nb)
    return out


def _stage(name):
    """A stage's scope (`<layer>/<stage>`, the vocabulary of the expert
    models): metadata on the traced operations, read back from a device
    trace by benchmarks/lib/stage_times.py; no instruction changes."""
    import jax
    return jax.named_scope(name)


def _ln(x, p, eps=1e-5):
    """A block's layer norm, the stage `norm`; the final one is part of
    `head` and calls `_layer_norm` itself."""
    with _stage("norm"):
        return _layer_norm(x, p, eps)


def _layer_norm(x, p, eps=1e-5):
    import jax
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    m = xf.mean(-1, keepdims=True)
    v = ((xf - m) ** 2).mean(-1, keepdims=True)
    y = (xf - m) * jax.lax.rsqrt(v + eps)
    return (y * p["g"].astype(jnp.float32)
            + p["b"].astype(jnp.float32)).astype(x.dtype)


def _dense(x, p):
    if "w_q" in p:
        # weight-only int8: dequant fused into the matmul epilogue —
        # (x @ w_q) * s == x @ (w_q * s) exactly for per-output-channel
        # scales (the scale factors out of the contraction), so the
        # int8 matrix is the only weight tensor resident
        y = (x @ p["w_q"].astype(x.dtype)) * p["w_s"].astype(x.dtype)
        return y + p["b"].astype(x.dtype)
    return x @ p["w"].astype(x.dtype) + p["b"].astype(x.dtype)


def _gelu_tanh(x):
    import jax
    return jax.nn.gelu(x, approximate=True)


def _probs(scores, mask, hd, dtype):
    """softmax over the keys of `scores` / sqrt(hd) under `mask`, the
    statistics in float32, the result in `dtype`."""
    import jax.numpy as jnp
    scores = jnp.where(mask, scores / np.sqrt(hd), -1e30)
    probs = jnp.exp(scores - jnp.max(scores, -1, keepdims=True))
    return (probs / probs.sum(-1, keepdims=True)).astype(dtype)


# -- multi-tenant LoRA adapter path -----------------------------------------
#
# An adapter pool is the pytree {proj: {"a": (N, L, in, rank),
# "b": (N, L, rank, out)}} over ADAPTER_PROJECTIONS — N device-resident
# low-rank variants stacked on a leading adapter axis (row 0 is the
# reserved identity: all zeros, so base-model requests gather a
# mathematically-exact no-op). The serving kernels gather each slot's
# A/B rows by its adapter id and add x @ A_s @ B_s to the base
# projection output — a batched gather-matmul (BGMV), so S co-batched
# slots can each hit a DIFFERENT adapter inside one fused dispatch with
# zero shape change and zero extra executables. The base matmul is
# untouched (int8 weights keep their fused dequant); the low-rank path
# runs in f32 regardless of the serving dtype — at rank r it is a
# rounding error of the FLOPs and the adapters are trained artifacts
# whose numerics should not depend on the engine's storage dtype.

def _lora_layer(adapters, adapter_ids, li, live):
    """Per-layer gathered LoRA operands: {proj: (A, B, live) | None}.
    adapter_ids is an (S,) int32 vector (per-slot decode) or a traced
    scalar (single-sequence prefill); `live` is the pre-broadcast
    (adapter_ids != 0) mask selecting the base output bit-exactly for
    identity rows (adding an all-zero delta could still flip -0.0)."""
    if adapters is None:
        return {nm: None for nm in ADAPTER_PROJECTIONS}
    return {nm: (adapters[nm]["a"][adapter_ids, li],
                 adapters[nm]["b"][adapter_ids, li], live)
            for nm in ADAPTER_PROJECTIONS}


def _dense_a(x, p, lora):
    """_dense plus the gathered low-rank delta: y + (x @ A_s @ B_s) in
    f32, selected per slot so adapter-0 rows return the base `y`
    BIT-IDENTICALLY (jnp.where on the whole row, not an add of zeros).
    lora=None is the adapterless engine: exactly _dense, same graph."""
    import jax.numpy as jnp
    y = _dense(x, p)
    if lora is None:
        return y
    a, b, live = lora
    xf = x.astype(jnp.float32)
    if a.ndim == 2:                      # single-sequence prefill
        d = (xf @ a) @ b
    else:                                # per-slot gathered (S, ...)
        d = jnp.einsum("s...r,sro->s...o",
                       jnp.einsum("s...i,sir->s...r", xf, a), b)
    return jnp.where(live, y + d.astype(y.dtype), y)


def _embed(params, tokens, pos, dtype):
    """The stage `embed`: the two table reads and the cast."""
    with _stage("embed"):
        return (params["wte"][tokens] + params["wpe"][pos]).astype(dtype)


def _mlp(h, blk, la=None):
    """The stage `ffn/dense`: both products and the GELU."""
    la = la or {"mlp1": None, "mlp2": None}
    with _stage("ffn/dense"):
        return _dense_a(_gelu_tanh(_dense_a(h, blk["mlp1"], la["mlp1"])),
                        blk["mlp2"], la["mlp2"])


def _split_heads(x, heads):
    b, s, h = x.shape
    return x.reshape(b, s, heads, h // heads)


def gpt_forward_logits(params, cfg, tokens):
    """Full-prefix forward (no cache): tokens (b, s) -> logits (b, s, V).
    The no-cache reference the equality tests pin the cached path to."""
    import jax.numpy as jnp

    b, s = tokens.shape
    dtype = _decoder.act_dtype(params)
    x = (params["wte"][tokens] + params["wpe"][:s]).astype(dtype)
    mask = jnp.tril(jnp.ones((s, s), bool))
    for blk in params["blocks"]:
        h = _ln(x, blk["ln1"])
        q = _split_heads(_dense(h, blk["q"]), cfg.heads)
        k = _split_heads(_dense(h, blk["k"]), cfg.heads)
        v = _split_heads(_dense(h, blk["v"]), cfg.heads)
        hd = q.shape[-1]
        scores = jnp.einsum("bqnd,bknd->bnqk", q, k,
                            preferred_element_type=jnp.float32)
        probs = _probs(scores, mask, hd, dtype)
        ctx = jnp.einsum("bnqk,bknd->bqnd", probs, v).reshape(b, s, -1)
        x = x + _dense(ctx, blk["out"])
        h = _ln(x, blk["ln2"])
        x = x + _dense(_gelu_tanh(_dense(h, blk["mlp1"])), blk["mlp2"])
    x = _ln(x, params["lnf"])
    return (x @ params["wte"].T.astype(x.dtype)).astype(jnp.float32)


def _prefill_blocks(params, cfg, tokens, max_len):
    """The sequential prefill's body: run the whole prompt through every
    block, filling the KV cache. Returns (hidden states (b, P, h) BEFORE
    the final LN, cache)."""
    import jax.numpy as jnp

    b, p_len = tokens.shape
    heads, hd = cfg.heads, cfg.hidden // cfg.heads
    dtype = _decoder.act_dtype(params)
    x = _embed(params, tokens, slice(None, p_len), dtype)
    mask = jnp.tril(jnp.ones((p_len, p_len), bool))
    cache = jnp.zeros((cfg.layers, 2, b, heads, max_len, hd), dtype)
    for li, blk in enumerate(params["blocks"]):
        h = _ln(x, blk["ln1"])
        with _stage("attn/project"):
            q = _split_heads(_dense(h, blk["q"]), heads)
            k = _split_heads(_dense(h, blk["k"]), heads)
            v = _split_heads(_dense(h, blk["v"]), heads)
        # cache layout (.., heads, seq, hd): seq-major per head so the
        # decode step's dynamic_update_slice touches one lane-row
        with _stage("attn/write"):
            cache = cache.at[li, 0, :, :, :p_len].set(
                k.transpose(0, 2, 1, 3))
            cache = cache.at[li, 1, :, :, :p_len].set(
                v.transpose(0, 2, 1, 3))
        with _stage("attn/attend"):
            scores = jnp.einsum("bqnd,bknd->bnqk", q, k,
                                preferred_element_type=jnp.float32)
            probs = _probs(scores, mask, hd, dtype)
            ctx = jnp.einsum("bnqk,bknd->bqnd", probs, v).reshape(
                b, p_len, -1)
        with _stage("attn/project"):
            x = x + _dense(ctx, blk["out"])
        x = x + _mlp(_ln(x, blk["ln2"]), blk)
    return x, cache


def _head_logits(params, last):
    """Final LN + tied-wte head over a (b, 1, h) slice -> (b, V) f32."""
    import jax.numpy as jnp
    with _stage("head"):
        last = _layer_norm(last, params["lnf"])
        logits = (last @ params["wte"].T.astype(last.dtype))[:, 0]
        return logits.astype(jnp.float32)


def gpt_prefill(params, cfg, tokens, max_len):
    """Run the prompt once, filling the KV cache.

    tokens: (b, P) int32. Returns (logits_last (b, V) f32,
    cache (layers, 2, b, heads, max_len, head_dim))."""
    x, cache = _prefill_blocks(params, cfg, tokens, max_len)
    return _head_logits(params, x[:, -1:]), cache


def gpt_decode_step(params, cfg, token, cache, t):
    """One cached decode step. token: (b,) int32, t: traced scalar index
    of the ABSOLUTE position being computed. Returns (logits (b, V) f32,
    updated cache). Attention reads keys [0, t] only — O(max_len) work,
    never O(t²)."""
    import jax
    import jax.numpy as jnp

    heads = cfg.heads
    hd = cfg.hidden // cfg.heads
    max_len = cache.shape[4]
    b = token.shape[0]
    dtype = cache.dtype
    x = _embed(params, token, t, dtype)[:, None]
    pos_mask = (jnp.arange(max_len) <= t)          # [S]
    for li, blk in enumerate(params["blocks"]):
        h = _ln(x, blk["ln1"])
        with _stage("attn/project"):
            q = _dense(h, blk["q"]).reshape(b, heads, 1, hd)
            k = _dense(h, blk["k"]).reshape(b, heads, 1, hd)
            v = _dense(h, blk["v"]).reshape(b, heads, 1, hd)
        with _stage("attn/write"):
            cache = jax.lax.dynamic_update_slice(
                cache, k[None, None], (li, 0, 0, 0, t, 0))
            cache = jax.lax.dynamic_update_slice(
                cache, v[None, None], (li, 1, 0, 0, t, 0))
        with _stage("attn/attend"):
            K, V = cache[li, 0], cache[li, 1]          # (b, n, S, hd)
            scores = jnp.einsum("bnqd,bnkd->bnqk", q, K,
                                preferred_element_type=jnp.float32)
            probs = _probs(scores, pos_mask[None, None, None, :], hd, dtype)
            ctx = jnp.einsum("bnqk,bnkd->bnqd", probs, V)
            ctx = ctx.transpose(0, 2, 1, 3).reshape(b, 1, -1)
        with _stage("attn/project"):
            x = x + _dense(ctx, blk["out"])
        x = x + _mlp(_ln(x, blk["ln2"]), blk)
    return _head_logits(params, x), cache


def gpt_decode_verify_pages(params, cfg, toks, arena, pt, ts, done=None,
                            adapters=None, adapter_ids=None):
    """Multi-position decode step over the paged pool — the speculative
    VERIFY pass. toks: (S, D) int32 candidate tokens at absolute
    positions ts..ts+D-1 per slot (column 0 is each slot's committed
    current token, columns 1.. the drafter's proposals). One batched
    pass writes all D K/V rows through the page table and returns logits
    for EVERY position — (S, D, V) f32 — so one model dispatch scores
    the whole draft run instead of D sequential steps.

    Causality inside the window: the query at offset j attends
    [0, ts+j], and rows ts..ts+j are written THIS pass before the
    layer's attention gather — so a previous pass's rejected-tail rows
    in [ts, ts+D) are always rewritten before anything reads them (the
    write-pointer "rewind" is implicit in re-verifying from the
    committed position). Per-position math is gpt_decode_step_pages'
    row-for-row: D=1 is exactly that step on the gather path.

    Two redirects keep the arena sound — `done` slots write the
    reserved scratch block (the frozen-slot discipline: a retired slot's
    reallocated blocks must never be dirtied by its ride-along verify),
    and positions whose page index runs past the page row land in
    scratch too (draft overshoot past a sequence's allocated tail, same
    rule as gpt_prefill_pages' pad writes). Candidates at such positions
    read garbage and are never committed — the budget mask stops
    strictly before the allocated region ends."""
    import jax.numpy as jnp

    heads = cfg.heads
    hd = cfg.hidden // cfg.heads
    data, _scales = _arena_parts(arena)
    bs = data.shape[4]
    s_dim, P = pt.shape
    D = toks.shape[1]
    L = P * bs
    dtype = _arena_compute_dtype(params, data, _scales)
    live = None if adapters is None \
        else (adapter_ids != 0)[:, None, None]
    rows = jnp.arange(s_dim)[:, None]
    pos = ts[:, None] + jnp.arange(D)[None, :]           # (S, D)
    x = _embed(params, toks, pos, dtype)
    pos_mask = (jnp.arange(L)[None, None, :] <= pos[:, :, None])
    pidx = pos // bs
    wblk = jnp.where(pidx < P, pt[rows, jnp.minimum(pidx, P - 1)], 0)
    if done is not None:
        wblk = jnp.where(done[:, None], 0, wblk)
    woff = pos % bs
    for li, blk in enumerate(params["blocks"]):
        with _stage("attn/project"):
            la = _lora_layer(adapters, adapter_ids, li, live)
        h = _ln(x, blk["ln1"])
        with _stage("attn/project"):
            q = _dense_a(h, blk["q"], la["q"]).reshape(s_dim, D, heads, hd)
            k = _dense_a(h, blk["k"], la["k"]).reshape(s_dim, D, heads, hd)
            v = _dense_a(h, blk["v"], la["v"]).reshape(s_dim, D, heads, hd)
        with _stage("attn/write"):
            arena = _kv_write(arena, li, wblk, woff, k, v)
        with _stage("attn/attend"):
            K, V = _kv_gather(arena, li, pt, dtype)  # (S, n, L, hd)
            scores = jnp.einsum("bqnd,bnkd->bnqk", q, K,
                                preferred_element_type=jnp.float32)
            probs = _probs(scores, pos_mask[:, None, :, :], hd, dtype)
            ctx = jnp.einsum("bnqk,bnkd->bqnd", probs, V).reshape(
                s_dim, D, -1)
        with _stage("attn/project"):
            x = x + _dense_a(ctx, blk["out"], la["out"])
        x = x + _mlp(_ln(x, blk["ln2"]), blk, la)
    with _stage("head"):
        x = _layer_norm(x, params["lnf"])
        return (x @ params["wte"].T.astype(x.dtype)).astype(
            jnp.float32), arena


def paged_arena_shapes(layers, num_blocks, heads, block_size, hd):
    """(data shape, scale-plane shape) of a paged block arena — the ONE
    place its physical layout is written down (serving/kv_cache.py
    allocates from it).

    data: (layers, 1, num_blocks, heads, block_size, 2*hd). A row holds
    the K and the V of one position of one head SIDE BY SIDE, K in lanes
    [0, hd) and V in [hd, 2*hd): at hd = 64 the minor dimension is the
    TPU's 128 lanes exactly, so a (block_size, 2*hd) page tile lies in
    HBM without padding (an hd-wide minor dimension is padded to 128
    lanes: twice the bytes, and no DMA can slice it) and one page of one
    layer is ONE contiguous (heads, block_size, 2*hd) piece the decode
    kernel copies as it lies. Axis 1 is kept at size 1 so that blocks
    stay axis 2 and heads axis 3: the mesh plan shards axis 3, and the
    swap payloads and migration tickets index axis 2.
    scales (quantized arena only): (layers, 1, num_blocks, heads,
    block_size, 2) — the K scale and the V scale of that row."""
    data = (layers, 1, num_blocks, heads, block_size, 2 * hd)
    return data, data[:-1] + (2,)


# -- quantized block arena ---------------------------------------------------
#
# A quantized arena is the pytree (data, scales): data is the usual
# (layers, 1, num_blocks, heads, block_size, 2*hd) laid down in int8,
# and scales is the per-block scale PLANE (layers, 1, num_blocks, heads,
# block_size, 2) — one f32 abs-max scale per written K and per written V
# row per head, so
# every scatter quantizes chip-locally (the heads axis shards over the
# tp mesh exactly like the data) and every page gather dequantizes
# in-graph right before the attention matmul. The paged kernels below
# accept either form; the scratch-block discipline covers BOTH leaves
# (a frozen slot's redirected write dirties scratch data AND scratch
# scales, never a reallocated block's).

def _arena_parts(arena):
    """(data, scales) of a paged arena — scales is None for the
    full-precision (bare-array) form."""
    if isinstance(arena, tuple):
        return arena
    return arena, None


def _arena_compute_dtype(params, data, scales):
    """The activation dtype a paged kernel runs in: the arena dtype for
    the full-precision form (f32/bf16 engines), the params' wte-derived
    dtype for a quantized arena (int8 is storage, never math)."""
    return data.dtype if scales is None else _decoder.act_dtype(params)


def _quantize_rows(val):
    """Per-(row, head) abs-max int8: val (..., heads, hd) ->
    (q int8 same shape, scale f32 (..., heads)). Zero rows quantize to
    zero with scale zero — dequant reproduces the zeros exactly."""
    import jax.numpy as jnp
    v32 = val.astype(jnp.float32)
    a = jnp.max(jnp.abs(v32), axis=-1)               # (..., heads)
    safe = jnp.where(a > 0, a, 1.0)
    q = jnp.clip(jnp.round(v32 * (127.0 / safe[..., None])),
                 -127, 127).astype(jnp.int8)
    return q, (a / 127.0).astype(jnp.float32)


def _kv_rows(scales, k, v):
    """(data rows, scale rows) of K|V rows as the arena stores them: k
    and v side by side; on a quantized arena (scales not None) int8
    rows and their (K scale, V scale) pairs, else no scale rows."""
    import jax.numpy as jnp
    if scales is None:
        return jnp.concatenate([k, v], -1), None
    qk, sk = _quantize_rows(k)
    qv, sv = _quantize_rows(v)
    return jnp.concatenate([qk, qv], -1), jnp.stack([sk, sv], -1)


def _kv_write(arena, li, wblk, woff, k, v):
    """One ride-along K|V scatter (k, v: (..., heads, hd), one row per
    entry of wblk/woff): plain write on a full-precision arena,
    quantize-at-scatter on a quantized one (data row + its scale-plane
    entry land through the SAME redirected block index, so the
    scratch/frozen-slot discipline holds for both)."""
    data, scales = _arena_parts(arena)
    rows, srows = _kv_rows(scales, k, v)
    data = data.at[li, 0, wblk, :, woff, :].set(rows)
    if scales is None:
        return data
    return data, scales.at[li, 0, wblk, :, woff, :].set(srows)


def _kv_write_pages(arena, li, pages, start, real_len, k, v):
    """The prefill's K|V write of one sequence's suffix (k, v: (B, heads,
    hd), row j at position start + j, rows past real_len are padding):
    whole pages through serving/pages.write_pages, quantize-at-write on a
    quantized arena (data rows and their scale-plane entries ride the same
    page ids). Leaves every real row exactly as `_kv_write` would."""
    data, scales = _arena_parts(arena)
    rows, srows = _kv_rows(scales, k, v)
    data = _pages.write_pages(data, li, pages, start, real_len, rows)
    if scales is None:
        return data
    return data, _pages.write_pages(scales, li, pages, start, real_len, srows)


def _kv_gather(arena, li, pages, dtype):
    """Page-gather one layer's (K, V) matrices, dequantized in-graph for
    a quantized arena: rows come back as int8 * their scale-plane entry,
    fused right before the attention einsum — the only dequant site,
    no fp32 copy of the pool ever exists."""
    data, scales = _arena_parts(arena)
    g = _pages.gather_pages(data, li, pages)       # (..., heads, L, 2*hd)
    hd = g.shape[-1] // 2
    k, v = g[..., :hd], g[..., hd:]
    if scales is None:
        return k, v
    s = _pages.gather_pages(scales, li, pages).astype(dtype)   # (.., L, 2)
    return k.astype(dtype) * s[..., 0:1], v.astype(dtype) * s[..., 1:2]


def prefill_attention_path(arena, bucket, arena_constraint=None):
    """Which attention a COLD prompt's prefill runs in a bucket of
    `bucket` rows, read off its input like decode_attention_path:
    "flash" (ops/flash_attention's forward over the prompt's own rows)
    where serving/pages.kernel_beside lets a kernel sit beside the arena
    and the bucket is whole 128-row tiles; "gather" (the page row gathered
    back and masked by position) for everything else. On the quantized
    arena a cold prompt must go on attending over its dequantized rows, or
    a chunked prefill, whose later chunks read those rows, and a whole
    one stop agreeing. A prefill with rows already cached (pfx_len > 0)
    gathers whatever this says."""
    return "flash" if _pages.kernel_beside(arena, arena_constraint, bucket) \
        else "gather"


def gpt_prefill_pages(params, cfg, tokens, pfx_len, real_len, arena,
                      pages, adapters=None, adapter_id=None,
                      arena_constraint=None):
    """Paged prefill of ONE sequence's prompt SUFFIX into its arena
    blocks, attending over an already-cached prefix through the page
    table — the single prefill entry point of the paged serving pool
    (vLLM-style PagedAttention over hashed shared prefixes).

    tokens: (1, B) int32 suffix, right-padded to a shape bucket.
    pfx_len: traced scalar — how many leading prompt positions are
    ALREADY resident in this sequence's blocks: a prefix-cache hit (a
    multiple of the block size; 0 = cold prompt) or, under chunked
    prefill, the previous chunk's fill frontier, an ARBITRARY position
    (enqueued-in-order dispatches make the earlier rows resident
    without a sync). The per-position math does not depend on where the
    suffix starts, so a suffix run as N chunks leaves the same K/V rows,
    and on its final chunk the same last-position logits, as one
    dispatch: what keeps chunked streams token-identical to
    prefill_chunk=None. real_len: traced scalar,
    the real (unpadded) suffix length, >= 1 — admission never shares
    the block holding position p_len-1, so the last prompt position is
    always computed here and the first-token logits need no cached
    activations. arena: see paged_arena_shapes.
    pages: (P,) int32 — THIS sequence's page row; suffix K/V rows are
    written to block pages[pos // bs] offset pos % bs as whole pages.
    The attention is ONE algorithm, the causal softmax of the suffix's
    queries, in two forms (serving/pages.cold_or_warm). WARM: the whole
    page row is gathered back (prefix blocks included, so hit blocks are
    never recomputed) and masked by position. COLD: the bucket's queries
    attend over the prompt's own k, v, nothing gathered, where
    prefill_attention_path says "flash" through the flash forward (no
    score matrix in HBM, no work on the keys past the bucket); else
    through the gather as well, with no cond. `arena_constraint` is the
    mesh plan's layout pin or None, only asked whether there is one. Pad
    positions (j >= real_len) compute values nobody reads in either form
    and write to the SCRATCH block unconditionally: with a large hit
    prefix and a small suffix bucket, pfx_len + bucket can run past
    max_pages*bs, where a clamped page gather would collide a pad write
    with a real row — and no real query ever reads a pad row anyway (the
    causal mask stops at pos <= p_len - 1).

    Returns (logits of position pfx_len+real_len-1, (1, V) f32, arena).
    Compiles once per SUFFIX bucket — prefix-cache hits shrink the
    suffix into the small buckets, which is where the TTFT win on
    shared-prompt traffic comes from.

    `adapters`/`adapter_id` (multi-tenant serving, else None): the
    device-resident LoRA pool and THIS sequence's traced adapter id —
    every projection gathers its A/B rows and adds the low-rank delta
    (id 0 selects the base output bit-exactly), so the prompt's K/V
    rows are computed under the same adapter the decode path serves."""
    import jax.numpy as jnp

    heads, hd = cfg.heads, cfg.hidden // cfg.heads
    b, B = tokens.shape
    data, _scales = _arena_parts(arena)
    bs = data.shape[4]
    L = pages.shape[0] * bs
    dtype = _arena_compute_dtype(params, data, _scales)
    attention = prefill_attention_path(arena, B, arena_constraint)
    if attention == "flash":
        from ..ops.flash_attention import flash_causal_rows
    live = None if adapters is None else (adapter_id != 0)
    j = jnp.arange(B)
    pos = pfx_len + j                              # absolute positions
    x = _embed(params, tokens[0], pos, dtype)
    mask = jnp.arange(L)[None, :] <= pos[:, None]  # (B, L) causal
    for li, blk in enumerate(params["blocks"]):
        with _stage("attn/project"):
            la = _lora_layer(adapters, adapter_id, li, live)
        h = _ln(x, blk["ln1"])
        with _stage("attn/project"):
            q = _dense_a(h, blk["q"], la["q"]).reshape(B, heads, hd)
            k = _dense_a(h, blk["k"], la["k"]).reshape(B, heads, hd)
            v = _dense_a(h, blk["v"], la["v"]).reshape(B, heads, hd)
        # pad rows reach no page but scratch block 0 (see docstring)
        with _stage("attn/write"):
            arena = _kv_write_pages(arena, li, pages, pfx_len, real_len,
                                    k, v)

        def warm(arena, li=li, q=q):
            K, V = _kv_gather(arena, li, pages, dtype)  # (heads, L, hd)
            scores = jnp.einsum("bnd,nkd->bnk", q, K,
                                preferred_element_type=jnp.float32)
            probs = _probs(scores, mask[:, None, :], hd, dtype)
            return jnp.einsum("bnk,nkd->bnd", probs, V)

        with _stage("attn/attend"):
            if attention == "flash":
                def cold(arena, q=q, k=k, v=v):
                    return flash_causal_rows(q, k, v, 1.0 / np.sqrt(hd))
                ctx = _pages.cold_or_warm(pfx_len, cold, warm, arena)
            else:
                ctx = warm(arena)
        with _stage("attn/project"):
            x = x + _dense_a(ctx.reshape(B, -1), blk["out"], la["out"])
        x = x + _mlp(_ln(x, blk["ln2"]), blk, la)
    last = x[real_len - 1][None, None]             # (1, 1, h)
    return _head_logits(params, last), arena


def decode_attention_path(arena, arena_constraint=None):
    """Which attention the paged decode step runs, read off its input:
    "paged_kernel" (ops/paged_attention: each slot's live pages straight
    out of the arena) where serving/pages.kernel_beside lets a kernel sit
    beside it; "gather" (`_kv_gather` + einsum) for everything else: the
    quantized (int8, scales) arena, the tensor-parallel plan, a backend
    that is not a TPU. The speculative verify pass
    (gpt_decode_verify_pages, several query rows a slot) always
    gathers. One algorithm; the form of the input says whether the
    kernel applies, and no option or environment variable does."""
    return "paged_kernel" if _pages.kernel_beside(arena, arena_constraint) \
        else "gather"


def gpt_decode_step_pages(params, cfg, tokens, arena, pt, ts, done=None,
                          adapters=None, adapter_ids=None,
                          arena_constraint=None):
    """One cached decode step over the SLOT dimension of a paged pool
    (continuous batching): every slot advances at its OWN absolute
    position, its K/V in arena blocks indirected through a page table.
    tokens/ts: (S,) int32, pt: (S, P) int32 page table, arena: see
    paged_arena_shapes. Returns (logits (S, V) f32, updated arena).

    Per-slot math is exactly gpt_decode_step's — the shared-t
    dynamic_update_slice becomes a per-row write at ts[s] and the [0, t]
    attention window a per-row mask — so a slot's logits match what the
    same sequence produces on the sequential path.

    A retired slot's blocks are REALLOCATED to other sequences, so a
    frozen slot riding along must not keep writing through its stale
    page row. `done` (S,) bool redirects frozen slots' K/V writes to
    the reserved scratch block 0 in-graph (their gathers still read
    stale blocks — garbage logits the host discards). done=None keeps
    every write live (single-sequence/unit-test use).

    `adapters`/`adapter_ids` (multi-tenant serving, else None): the
    LoRA pool + an (S,) int32 per-slot adapter-id vector — every
    projection gathers each slot's A/B rows and adds x @ A_s @ B_s, so
    co-batched slots hit DIFFERENT adapters in this one dispatch
    (id 0 rows select the base output bit-exactly).

    Which attention runs is decode_attention_path's verdict on the
    arena and `arena_constraint` (the mesh plan's layout pin the chunk
    loop applies, else None; only asked whether there is one). The
    kernel reads only live pages and gives a frozen slot zeros where
    the gather gives it garbage; either way the host discards those
    logits."""
    import jax.numpy as jnp

    heads = cfg.heads
    hd = cfg.hidden // cfg.heads
    data, _scales = _arena_parts(arena)
    bs = data.shape[4]
    s_dim, P = pt.shape
    L = P * bs
    attention = decode_attention_path(arena, arena_constraint)
    if attention == "paged_kernel":
        # imported where it is used: `import paddle_tpu` stays free of
        # Pallas for programs that never serve
        from ..ops.paged_attention import paged_attention
    else:
        pos_mask = (jnp.arange(L)[None, :] <= ts[:, None])     # [S, L]
        wblk = pt[jnp.arange(s_dim), ts // bs]
        if done is not None:
            wblk = jnp.where(done, 0, wblk)    # frozen -> scratch block
        woff = ts % bs
    dtype = _arena_compute_dtype(params, data, _scales)
    live = None if adapters is None \
        else (adapter_ids != 0)[:, None, None]
    x = _embed(params, tokens, ts, dtype)[:, None]
    for li, blk in enumerate(params["blocks"]):
        with _stage("attn/project"):
            la = _lora_layer(adapters, adapter_ids, li, live)
        h = _ln(x, blk["ln1"])
        with _stage("attn/project"):
            q = _dense_a(h, blk["q"], la["q"]).reshape(s_dim, heads, 1, hd)
            k = _dense_a(h, blk["k"], la["k"]).reshape(s_dim, heads, hd)
            v = _dense_a(h, blk["v"], la["v"]).reshape(s_dim, heads, hd)
        if attention == "paged_kernel":
            # the kernel writes the row too (where a frozen slot's went
            # to scratch it now goes nowhere), so no XLA scatter asks
            # for the arena in another layout
            with _stage("attn/attend"):
                ctx, arena = paged_attention(q[:, :, 0], k, v, arena, li,
                                             pt, ts, done)
                ctx = ctx.reshape(s_dim, 1, -1)
        else:
            with _stage("attn/write"):
                arena = _kv_write(arena, li, wblk, woff, k, v)
            with _stage("attn/attend"):
                K, V = _kv_gather(arena, li, pt, dtype)  # (S, heads, L, hd)
                scores = jnp.einsum("bnqd,bnkd->bnqk", q, K,
                                    preferred_element_type=jnp.float32)
                probs = _probs(scores, pos_mask[:, None, None, :], hd, dtype)
                ctx = jnp.einsum("bnqk,bnkd->bnqd", probs, V)
                ctx = ctx.transpose(0, 2, 1, 3).reshape(s_dim, 1, -1)
        with _stage("attn/project"):
            x = x + _dense_a(ctx, blk["out"], la["out"])
        x = x + _mlp(_ln(x, blk["ln2"]), blk, la)
    return _head_logits(params, x), arena


def _sample(logits, key, temperature, top_k):
    import jax
    import jax.numpy as jnp
    if temperature == 0.0:                      # greedy
        return jnp.argmax(logits, -1).astype(jnp.int32)
    logits = logits / temperature
    if top_k > 0:
        vals, idx = jax.lax.top_k(logits, top_k)
        choice = jax.random.categorical(key, vals)
        return jnp.take_along_axis(
            idx, choice[:, None], 1)[:, 0].astype(jnp.int32)
    return jax.random.categorical(key, logits).astype(jnp.int32)


def _generate_impl(params, cfg, prompt, max_new, temperature, top_k,
                   eos_id, key):
    import jax
    import jax.numpy as jnp

    b, p_len = prompt.shape
    total = p_len + max_new
    logits, cache = gpt_prefill(params, cfg, prompt, total)
    tokens = jnp.concatenate(
        [prompt.astype(jnp.int32),
         jnp.zeros((b, max_new), jnp.int32)], axis=1)
    done0 = jnp.zeros((b,), bool)

    def body(i, carry):
        tokens, cache, logits, key, done = carry
        key, sub = jax.random.split(key)
        nxt = _sample(logits, sub, temperature, top_k)
        if eos_id is not None:
            nxt = jnp.where(done, eos_id, nxt)
            done = done | (nxt == eos_id)
        tokens = tokens.at[:, p_len + i].set(nxt)
        logits, cache = gpt_decode_step(params, cfg, nxt, cache,
                                        p_len + i)
        return tokens, cache, logits, key, done

    tokens, _, _, _, _ = jax.lax.fori_loop(
        0, max_new, body, (tokens, cache, logits, key, done0))
    return tokens


_GENERATE_JIT = None


def gpt_generate(params, cfg, prompt, max_new_tokens,
                 temperature: float = 0.0, top_k: int = 0,
                 eos_id: Optional[int] = None, seed: int = 0):
    """Generate continuations. prompt: (b, P) int array. temperature=0 is
    greedy; top_k>0 samples among the k best at the given temperature.
    One jitted dispatch for prefill + all decode steps."""
    import jax
    import jax.numpy as jnp
    p_len = int(np.asarray(prompt).shape[1])
    if p_len + int(max_new_tokens) > cfg.max_pos:
        # a traced wpe[t] index CLAMPS past the table under jit — every
        # token beyond max_pos would silently reuse the last position
        raise ValueError(
            f"prompt ({p_len}) + max_new_tokens ({max_new_tokens}) "
            f"exceeds cfg.max_pos ({cfg.max_pos})")
    global _GENERATE_JIT
    if _GENERATE_JIT is None:
        _GENERATE_JIT = jax.jit(
            _generate_impl,
            static_argnames=("cfg", "max_new", "temperature", "top_k",
                             "eos_id"))
    prompt = jnp.asarray(np.asarray(prompt), jnp.int32)
    out = _GENERATE_JIT(params, cfg, prompt, int(max_new_tokens),
                        float(temperature), int(top_k), eos_id,
                        jax.random.PRNGKey(seed))
    return np.asarray(out)


# -- the engine's view of this model ------------------------------------------

from ..serving.model import FEATURES, CacheSpec, ServingModel  # noqa: E402


class _GPTServingModel(ServingModel):
    """The GPT family behind serving.model.ServingModel: every paged
    kernel above carries the quantized-arena dequant and the per-slot
    adapter path (the verify pass included), so it declares every
    feature the engine has."""

    name = "gpt2"
    features = frozenset(FEATURES)

    def max_positions(self, cfg):
        return cfg.max_pos

    def cache_spec(self, cfg):
        shape, _ = paged_arena_shapes(cfg.layers, 1, cfg.heads, 1,
                                      cfg.hidden // cfg.heads)
        return CacheSpec(shape[0], shape[3], shape[5])

    def activation_dtype(self, params):
        return _decoder.act_dtype(params)

    def decode_attention_path(self, arena, arena_constraint=None):
        return decode_attention_path(arena, arena_constraint)

    def prefill_attention_path(self, arena, bucket, arena_constraint=None):
        return prefill_attention_path(arena, bucket, arena_constraint)

    def prefill(self, *args, **kw):
        return gpt_prefill_pages(*args, **kw) + (None,)

    def decode_step(self, *args, **kw):
        return gpt_decode_step_pages(*args, **kw) + (None,)

    def verify(self, *args, **kw):
        return gpt_decode_verify_pages(*args, **kw)

    def quantize_params(self, params, cfg):
        return quantize_params(params, cfg)


GPT_SERVING_MODEL = _GPTServingModel()
