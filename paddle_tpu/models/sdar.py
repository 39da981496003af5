"""SDAR-30B-A3B-Chat (`model_type: sdar_moe`) on the serving path: Qwen3-MoE's
layer, generated by DIFFUSION OVER BLOCKS.

Pre-norm decoder, RMSNorm, no bias, untied embedding and head. The layer is
models/_grouped.py's with every layer full (one cache group), plain rotary
positions and a per-head RMSNorm of q and k before the rotation (`q_norm`,
`k_norm`, (d,) each); every layer sparse, the shared expert layer
(models/_experts.py) under softmax scoring with the picks' weights over
their sum, no shared expert.

What differs from every other served model is HOW IT GENERATES. The sequence
is cut in blocks of B = `block_length` positions from position 0, and
attention is BLOCK-CAUSAL: position i attends j with j // B <= i // B, its own
block whole. A block starts as the mask token everywhere (the first one opens
with the prompt's last p mod B tokens, fixed) and is denoised by PASSES: a
pass runs the block's B positions through the model, writes their K|V, and
from the logits AT each masked position (not shifted: logits at i score the
token at i) fixes the positions whose confidence clears
`confidence_threshold`, or the B / `denoising_steps` most confident if fewer
do. A block with no mask left runs once more for its K|V alone and COMMITS.
The loop, the unmasking rule and the commit are the engine's
(serving/decode_loop.py's third body, driven by `diffusion(cfg)`); what this
module supplies is the block pass:

  * PREFILL attends the prompt's WHOLE blocks (floor(p / B) * B rows)
    block-causally and writes them: the flash forward with `block=` on a TPU,
    masked XLA attention elsewhere. It returns no logits: they would pick
    nothing.
  * `block_step_pages`: B rows a slot at positions ts .. ts + B - 1 (`ts` a
    multiple of B, so a block never straddles a page), written through the
    live page, EVERY row attending 0 .. ts + B - 1: the grouped paged kernel
    with block rows (ops/paged_attention: B x group query rows a KV head, one
    length for all of them, no mask inside the block), a gather and two
    einsums on the CPU. Every pass writes; a row's last write, the commit
    pass's, is the final one. The expert layer sees S x B rows, the head all
    of them.

Shared, not copied: models/_grouped.py's config base, weights, cache group,
prompt attention (`attend_rows`, the block-causal rule) and path verdicts;
models/_experts.py's `ffn` and counters; models/_decoder.py's `rms`, `rope`,
`embed`, `head`; serving/pages.py's page reads and writes.

Parameters: `_grouped.init_params`' tree, and layers[i]: q_norm, k_norm (d,).

Named scopes: `embed`, `norm` (norm1 and the q/k norms), `attn/project`,
`attn/full`, `moe/*`, `head`. In-graph counters beside the expert layer's:
`decode_rows_full` (sum over live slots of ts + B, a layer a pass), and the
loop's `block_passes`, `blocks_committed`, `tokens_fixed_by_threshold`,
`tokens_fixed_by_rank`.

ASSUMED (the published config has no key; the configuration file of the
benchmark lists each): the q/k norm (Qwen3's rule), the generation
parameters (the family's `block_diffusion_generate` as the -Chat checkpoints
use it), unshifted logits, the mask token's own logit at -inf.

Refused by the engine from `features` ({"block_diffusion"} alone): int8
weights or cache, adapters, speculation, a mesh plan, chunked prefill; and,
because the decode carry holds a block, host swap and migration. Prefix hits
are off (a hit would need a block-causal warm prefill).
"""

from __future__ import annotations

import math

from ..serving import pages as _pages
from ..serving.model import BLOCK_DIFFUSION, DIFFUSION_COUNTERS
from . import _decoder, _experts, _grouped

__all__ = ["SdarConfig", "init_params", "forward_logits", "prefill_pages",
           "block_step_pages", "SDAR_SERVING_MODEL"]

REMASKING = "low_confidence_dynamic"


class SdarConfig(_grouped.GroupedConfig):
    """The published keys under this package's names (defaults are
    SDAR-30B-A3B-Chat's `config.json`) and the generation parameters the
    family's loop takes."""

    n_shared_experts = 0
    router_scoring = "softmax"

    def __init__(self, vocab_size=151936, hidden=2048, layers=48, heads=32,
                 kv_heads=4, head_dim=128, moe_intermediate=768,
                 n_routed_experts=128, experts_per_tok=8, rms_eps=1e-6,
                 rope_theta=1000000.0, max_pos=32768, block_length=4,
                 denoising_steps=4, confidence_threshold=0.9,
                 remasking=REMASKING, mask_token_id=151669,
                 init_range=0.02, name="SDAR-30B-A3B-Chat"):
        super().__init__(
            vocab_size=vocab_size, hidden=hidden, layers=layers, heads=heads,
            kv_heads=kv_heads, head_dim=head_dim,
            moe_intermediate=moe_intermediate,
            n_routed_experts=n_routed_experts,
            experts_per_tok=experts_per_tok,
            layer_types=(_grouped.FULL,) * layers, rms_eps=rms_eps,
            rope_theta=rope_theta, max_pos=max_pos, init_range=init_range,
            name=name)
        B, steps = int(block_length), int(denoising_steps)
        if B < 1 or B & (B - 1):
            raise ValueError(f"block_length {block_length} is no power of "
                             "two (a block never straddles a page)")
        if steps < 1 or B % steps:
            raise ValueError(f"denoising_steps {denoising_steps} does not "
                             f"divide block_length {block_length}")
        if remasking != REMASKING:
            raise ValueError(f"remasking is {REMASKING!r} (the one rule the "
                             f"loop has), not {remasking!r}")
        if not 0 <= int(mask_token_id) < vocab_size:
            raise ValueError(f"mask_token_id {mask_token_id} is no row of a "
                             f"vocabulary of {vocab_size}")
        self.block_length = B
        self.denoising_steps = steps
        self.confidence_threshold = float(confidence_threshold)
        self.remasking = remasking
        self.mask_token_id = int(mask_token_id)

    def serving_model(self):
        if self.name == SDAR_SERVING_MODEL.name:
            return SDAR_SERVING_MODEL
        return _SdarServingModel(self.name)


def init_params(cfg: SdarConfig, key, dtype):
    """`_grouped.init_params`' seeded weights and a unit `q_norm`, `k_norm`
    a layer."""
    import jax.numpy as jnp
    params = _grouped.init_params(cfg, key, dtype)
    for lp in params["layers"]:
        lp.update(q_norm=jnp.ones((cfg.head_dim,), dtype),
                  k_norm=jnp.ones((cfg.head_dim,), dtype))
    return params


# -- the layer's pieces ----------------------------------------------------------

def _project(cfg, lp, x, pos):
    """norm1 and the projections of tokens x (T, h) at positions pos (T,):
    q (T, heads, d), k, v (T, kv_heads, d); q and k normed over a head's d
    values (a learned scale each), then rotated on all d values in halves,
    plain frequencies."""
    import jax
    T, d = x.shape[0], cfg.head_dim
    with jax.named_scope("norm"):
        h = _decoder.rms(x, lp["norm1"], cfg.rms_eps)
    q = (h @ lp["wq"]).reshape(T, cfg.heads, d)
    k = (h @ lp["wk"]).reshape(T, cfg.kv_heads, d)
    v = (h @ lp["wv"]).reshape(T, cfg.kv_heads, d)
    with jax.named_scope("norm"):
        q = _decoder.rms(q, lp["q_norm"], cfg.rms_eps)
        k = _decoder.rms(k, lp["k_norm"], cfg.rms_eps)
    q = _decoder.rope(q, pos[:, None], cfg.rope_theta, None,
                      interleaved=False)
    k = _decoder.rope(k, pos[:, None], cfg.rope_theta, None,
                      interleaved=False)
    return q, k, v


# -- the whole sequence, no cache (tests; generation never runs it) --------------

def forward_logits(params, cfg, tokens):
    """Logits (T, V) float32 of one sequence tokens (T,) under the
    block-causal mask: the served math without a cache. Row i scores the
    token AT i."""
    import jax.numpy as jnp
    T = tokens.shape[0]
    pos = jnp.arange(T)
    x = _decoder.embed(params, tokens, _decoder.act_dtype(params))
    counters = _experts.zero_counters(cfg)
    live = jnp.ones((T,), bool)
    for lp in params["layers"]:
        q, k, v = _project(cfg, lp, x, pos)
        o = _grouped.attend_rows(cfg, q, k, v, "full", False,
                                 block=cfg.block_length)
        x = x + o.reshape(T, -1) @ lp["wo"]
        y, counters, _ = _experts.ffn(cfg, lp, x, live, counters)
        x = x + y
    return _decoder.head(cfg, params, x)


# -- prefill into the pages --------------------------------------------------------

def prefill_pages(params, cfg, tokens, pfx_len, real_len, arena, pages):
    """Prefill ONE sequence's COLD prompt tokens (1, L) (right-padded to its
    bucket; real_len real) into its page row: the prompt's WHOLE blocks,
    floor(real_len / B) * B rows, attended block-causally and written; its
    last real_len mod B tokens are the first generated block's and are
    written by that block's passes. `pfx_len` is 0 (no prefix hits). Returns
    (None, arena, counters): no logits, which would pick nothing (a row's
    logits score the token AT it), so neither the final norm nor the head
    runs."""
    import jax
    import jax.numpy as jnp

    L = tokens.shape[1]
    dtype = arena.dtype
    B = cfg.block_length
    whole = real_len // B * B
    flash = _grouped.prefill_attention_path(arena, L) == "flash"
    j = jnp.arange(L)
    pos = pfx_len + j
    live = j < whole
    x = _decoder.embed(params, tokens[0], dtype)
    counters = _experts.zero_counters(cfg)
    for li, lp in enumerate(params["layers"]):
        with jax.named_scope("attn/project"):
            q, k, v = _project(cfg, lp, x, pos)
            kv = jnp.concatenate([k, v], -1).astype(dtype)
            arena = _pages.write_pages(arena, li, pages, pfx_len, whole, kv)
        with jax.named_scope("attn/full"):
            o = _grouped.attend_rows(cfg, q, k, v, "full", flash, whole,
                                     block=B)
        with jax.named_scope("attn/project"):
            x = x + o.reshape(L, -1) @ lp["wo"]
        y, counters, _ = _experts.ffn(cfg, lp, x, live, counters)
        x = x + y
    return None, arena, counters


# -- the block pass through the pages ----------------------------------------------

def _attend_block(cfg, q, k, v, arena, li, pt, ts, done, path):
    """One layer's attention of a block pass, with its write: q (S, B,
    heads, d), k, v (S, B, kv_heads, d) of positions ts .. ts + B - 1; every
    row attends 0 .. ts + B - 1. `path`: "paged_kernel" or "gather".
    Returns (o (S, B, heads, d), the arena)."""
    import jax.numpy as jnp
    if path == "paged_kernel":
        from ..ops.paged_attention import paged_attention
        return paged_attention(q, k, v, arena, li, pt, ts, done)
    S, B, d = q.shape[0], q.shape[1], cfg.head_dim
    bs, dtype = arena.shape[4], arena.dtype
    wblk = pt[jnp.arange(S), ts // bs]
    if done is not None:
        wblk = jnp.where(done, 0, wblk)
    rows_at = (ts % bs)[:, None] + jnp.arange(B)[None, :]
    a = arena.at[li, 0, wblk[:, None], :, rows_at].set(
        jnp.concatenate([k, v], -1).astype(dtype))
    rows = _pages.gather_pages(a, li, pt)          # (S, kv, P*bs, 2d)
    keep = jnp.arange(rows.shape[2])[None, :] < (ts + B)[:, None]
    qg = q.reshape(S, B, cfg.kv_heads, cfg.group, d)
    s = jnp.einsum("sbkgd,skld->sbkgl", qg, rows[..., :d],
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    s = jnp.where(keep[:, None, None, None, :], s, -1e30)
    p = jnp.exp(s - jnp.max(s, -1, keepdims=True))
    p = (p / p.sum(-1, keepdims=True)).astype(rows.dtype)
    o = jnp.einsum("sbkgl,skld->sbkgd", p, rows[..., d:])
    return o.reshape(S, B, cfg.heads, d), a


def block_step_pages(params, cfg, toks, arena, pt, ts, done=None,
                     attention=None):
    """One PASS over every slot's current block: toks (S, B) (the mask
    token where a position is still masked), ts (S,) the block's first
    position, a multiple of B. Writes the B rows' K|V at ts .. ts + B - 1
    and every row attends 0 .. ts + B - 1. A frozen slot (`done`) writes to
    the scratch block (the gather) or nowhere (the kernel) and its logits
    are discarded. Returns (logits (S, B, V) float32, row j scoring the
    token AT ts + j; arena; counters)."""
    import jax
    import jax.numpy as jnp

    S, B = toks.shape
    dtype = arena.dtype
    if attention is None:
        attention = _grouped.decode_attention_path(arena)["full"]
    live = jnp.ones((S,), bool) if done is None else ~done
    rows_live = jnp.repeat(live, B)
    pos = (ts[:, None] + jnp.arange(B)[None, :]).reshape(-1)
    x = _decoder.embed(params, toks.reshape(-1), dtype)
    counters = _experts.zero_counters(cfg)
    for li, lp in enumerate(params["layers"]):
        with jax.named_scope("attn/project"):
            q, k, v = _project(cfg, lp, x, pos)
        with jax.named_scope("attn/full"):
            o, arena = _attend_block(
                cfg, q.reshape(S, B, cfg.heads, -1),
                k.reshape(S, B, cfg.kv_heads, -1),
                v.reshape(S, B, cfg.kv_heads, -1), arena, li, pt, ts, done,
                attention)
        with jax.named_scope("attn/project"):
            x = x + o.reshape(S * B, -1).astype(dtype) @ lp["wo"]
        y, counters, _ = _experts.ffn(cfg, lp, x, rows_live, counters)
        x = x + y
    counters = dict(counters, decode_rows_full=(
        jnp.sum(jnp.where(live, ts + B, 0)).astype(jnp.int32) * cfg.layers))
    return _decoder.head(cfg, params, x).reshape(S, B, -1), arena, counters


# -- the engine's view of this model ---------------------------------------------

class _SdarServingModel(_grouped.GroupedBlockModel):
    features = frozenset({BLOCK_DIFFUSION})
    prefill_pages = staticmethod(prefill_pages)
    # the loop's own ride with the block's, at zero from both programs
    own_counters = DIFFUSION_COUNTERS

    def diffusion(self, cfg):
        return {"block_length": cfg.block_length,
                "denoising_steps": cfg.denoising_steps,
                "confidence_threshold": cfg.confidence_threshold,
                "remasking": cfg.remasking,
                "mask_token_id": cfg.mask_token_id}

    def decode_step(self, params, cfg, tokens, arena, pt, ts, done, **_):
        raise NotImplementedError(
            f"{self.name} generates by block passes (`block_step`), not one "
            "causal token a step")

    def block_step(self, params, cfg, toks, arena, pt, ts, done):
        logits, arena, c = block_step_pages(
            params, cfg, toks, arena, pt, ts, done,
            attention=self.decode_attention_path(arena)["full"])
        return logits, arena, self._counters(cfg, c, decode=True)


SDAR_SERVING_MODEL = _SdarServingModel("SDAR-30B-A3B-Chat")
