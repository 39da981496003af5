"""Every served program traces the jaxpr it traced at the parent commit, and
every `init_params` draws the weights it drew.

PR 43 moved the pieces several blocks share out of the model files
(`models/_decoder.py`, `_experts.py`, `_grouped.py`, `serving/pages.py`) and
changed no program. The table below is sha256 (16 hex) of
`str(jax.make_jaxpr(..))` of each program at a small size, computed at
3ea5e0c (PR 42) BY THIS FILE (`python tests/test_served_programs.py` prints
it; the file names only addresses that both trees have). A case is
`<model>.<program>[.<variant>][.tpu]`:

  * through the serving class (`serving_model(cfg).prefill / decode_step /
    verify / block_step`), so the counters' renaming is traced too;
  * `.kernel`: the step with its Mosaic kernel forced by the programs' own
    `attention=` argument (`latent_paged_kernel`, `paged_kernel`);
  * `.tpu`: `jax.default_backend` answers "tpu", widths are lane-aligned and
    the prefill bucket is 128 rows, so the verdicts are the chip's and the
    program traced is the one the chip serves: the flash forward under the
    cold branch, the paged kernels, the grouped expert kernel
    (`.tpu.int8kv` and `.tpu.pinned`: GPT's quantized cache and a mesh pin
    stay on the gather there);
  * GPT's variants: `int8kv` (the (int8, scales) arena), `adapters` (a LoRA
    pool), `chunked` (a bucket that is no multiple of the block size, from a
    traced start); `unpaged_*` and `forward_logits`, its sequential programs.
  * `<model>.init`: sha256 of the bytes of the float32 weights a seed
    draws, leaves in sorted order.

Kimi-Linear's rows were computed at 4c84304 (PR 48) by PR 50, which gave
`models/_experts.route` its third rule, `moe` the identity pick and
`models/_latent.project` its two scales: with the new keys absent every
program of the six older models traces what it traced. `longcat_flash.*`
(PR 50's leaf) is its own tree's, the first that has it.

`command_a.prefill.tpu` and `command_a.decode.tpu` were computed again at PR
45 (its review round): a layer that holds a SHARE of the experts now sums its
picks by a select, pick by pick (`models/_experts._weighted_sum`), so that a
pick held elsewhere never multiplies the routed buffer's never-written last
row by 0; every model that holds all its experts traces what it traced.

`mellum.decode.tpu`, `command_a.decode.tpu`, `sdar.block.kernel` and
`sdar.block.tpu` were computed again at PR 53 (parent d089719) on its own
final tree: they hold `ops/paged_attention._grouped_kernel`, whose decode walk
became ONE stream of page groups across slots; every other row, the gather
programs of the same three models among them, passes with the hash it had.

`granite_hybrid.*` (PR 54's leaf: a Mamba-2 mixer on the state groups, one
position-free attention layer through `models/_grouped.py` under its new key
`attention_scale`) is its own tree's, the first that has it. The same PR moved Kimi-Linear's state-block and convolution-history
plumbing into `models/_recurrent.py`, which both leaves call: the move
itself left every `kimi_linear.*` row with the hash it had, and with the key
at its default so do Mellum's, command-a's and SDAR's. Its review round then
made `_recurrent.write_block` ONE form, the arena viewed as one run of
blocks and the block put in by one `dynamic_update_slice` (what granite's
state needed so that the TPU's compiler would not copy a 3.4 GB arena around
one block), so `kimi_linear.prefill` and `kimi_linear.prefill.tpu` were
computed again: the same block written to the same place by a
`dynamic_update_slice` where an `.at[].set` stood (compiled for a described v5e at the
cell's size: the same 44 updates and 27 scatters, temporaries 0.088 and
0.703 GB as before; PERF.md section 6). Kimi-Linear's decode rows pass with
the hash they had.

`granite_hybrid.decode.tpu` was computed again at PR 55 (parent 4fa0813) on
its own final tree: it holds `ops/ssd_step._kernel`, which takes the decay as
a third scalar-prefetched operand and dt x alone as its columns, and gives
`y` as rows of two heads (its contraction is the MXU's). Every other row
passes with the hash it had, `granite_hybrid.decode` (the CPU's
gather-update-scatter), `.prefill`, `.prefill.tpu` and `.init` among them:
nothing that two leaves share moved.

`qwen3_next.*` (PR 56's leaf: a Gated-DeltaNet mixer on the state groups
and a gated attention layer through `models/_grouped.py`) is its own tree's,
the first that has it. The same PR moved the delta rule's two `jax.numpy`
forms and their two dispatches out of `models/kimi_linear.py` into
`models/_delta.py`, which both leaves call, gave `models/_decoder.rms` its
key `centred` (the family's `1 + w`) and `models/_experts.moe` its third
`shared_expert_combination`, "token_gate": with the keys at their defaults
EVERY other row passes with the hash it had, Kimi-Linear's four among them
(a move of code changes no jaxpr).

Mellum's and command-a's programs are pinned by tests/test_sdar.py::PARENT,
the CPU's pair of GPT, Moonlight and Xing by tests/test_mellum.py::PARENT.
The CPU gives identity, never a time.
"""

import functools
import hashlib

if __name__ == "__main__":
    # printing the table: the tests' own settings (tests/conftest.py: the
    # CPU, full-precision products) before jax is imported, as under pytest
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import conftest  # noqa: F401

import numpy as np
import pytest

import jax
import jax.numpy as jnp

PARENT = {
    "gpt.prefill": "0431313d390e6c16",
    "gpt.prefill.int8kv": "a041402a271b3f20",
    "gpt.prefill.adapters": "e0dca1a3c764e114",
    "gpt.prefill.tpu": "5ff4f9a2c7344059",
    "gpt.prefill.tpu.int8kv": "ca8c3f76588ad49b",
    "gpt.decode": "fb196d7e555b8c15",
    "gpt.decode.int8kv": "ed63b02d044265da",
    "gpt.decode.adapters": "6a6e3e57307074c9",
    "gpt.decode.tpu": "f511a2b136e63f6a",
    "gpt.decode.tpu.int8kv": "2e5416c1931b71f7",
    "gpt.verify": "e98fe92c71e9e84d",
    "gpt.verify.int8kv": "f5868dd1753d638b",
    "gpt.verify.adapters": "5f8daa0c51883867",
    "gpt.verify.tpu": "1e6f7c52cef96cf3",
    "gpt.verify.tpu.int8kv": "708e1a766cd231a0",
    "gpt.prefill.tpu.pinned": "2b382fb5801ca59f",
    "gpt.decode.tpu.pinned": "628f01bf1cf37748",
    "gpt.prefill.chunked": "218fc55a56602718",
    "gpt.prefill.chunked.int8kv": "34f0fa111ffa8d4d",
    "gpt.unpaged_prefill": "176cc5a97107b517",
    "gpt.unpaged_decode": "a7a9258cf5c24322",
    "gpt.forward_logits": "96721dff4716e6e2",
    "moonlight.prefill": "fcaf2c02fe681145",
    "moonlight.decode": "33d3b761c5438fd5",
    "moonlight.decode.kernel": "518a4e17ed79a376",
    "moonlight.prefill.tpu": "1de93bb30c59b93f",
    "moonlight.decode.tpu": "27b0bfe3b80d6c22",
    "xing.prefill": "ae5a444c955826a3",
    "xing.decode": "7ce7c7063d864aa9",
    "xing.decode.kernel": "20bd16deeedac7cc",
    "xing.prefill.tpu": "13b71fc4b5b0dd35",
    "xing.decode.tpu": "8660304e6bd49c29",
    "mellum.prefill.tpu": "db3a92150d64f7ea",
    "mellum.decode.tpu": "a52b812dffbacd36",
    "command_a.prefill.tpu": "50700700e8c46ab4",
    "command_a.decode.tpu": "a39b15511bffd6be",
    "sdar.prefill": "f13aba2086490cba",
    "sdar.block": "56b73e3a985d01c4",
    "sdar.block.kernel": "6d0832f8eeaa0593",
    "sdar.prefill.tpu": "5d9996e6bffff0f8",
    "sdar.block.tpu": "d6c8ad5bad4fa3aa",
    "kimi_linear.prefill": "1b18b3f38dc4f076",
    "kimi_linear.decode": "1dc7ee60c3e0add4",
    "kimi_linear.prefill.tpu": "658728eb5ae0f119",
    "kimi_linear.decode.tpu": "de2cc0338d01d47d",
    "longcat_flash.prefill": "3c68f19406e8f493",
    "longcat_flash.decode": "5154a249c2274267",
    "longcat_flash.decode.kernel": "67710daedcec2457",
    "longcat_flash.prefill.tpu": "6f578ef9457cd909",
    "longcat_flash.decode.tpu": "4a49cfa8a10bf9a2",
    "gpt.init": "ea19118c5abc3d80",
    "moonlight.init": "7afc17e1dfebb9b0",
    "xing.init": "a3aa526a0907c2bd",
    "mellum.init": "a90678c41fef4f5f",
    "command_a.init": "8fb555e52d9ebabe",
    "sdar.init": "cb8aeed9efda9803",
    "kimi_linear.init": "a979e4e58ac39a0a",
    "longcat_flash.init": "d2b6fc261e496288",
    "granite_hybrid.prefill": "7a722f63d130020d",
    "granite_hybrid.decode": "2291079b72bbe03e",
    "granite_hybrid.prefill.tpu": "d0c35c86d052900a",
    "granite_hybrid.decode.tpu": "a4688ee096887fcc",
    "granite_hybrid.init": "e9d4705ca900bc67",
    "qwen3_next.prefill": "265634792235a427",
    "qwen3_next.decode": "5c732b71912ea21a",
    "qwen3_next.prefill.tpu": "384dd40b198b3c96",
    "qwen3_next.decode.tpu": "3d4376b4b0774f40",
    "qwen3_next.init": "07d394916d954b03",
}

_YARN = {"type": "yarn", "factor": 4, "beta_fast": 32, "beta_slow": 1,
         "original_max_position_embeddings": 32, "mscale": 1,
         "mscale_all_dim": 1}


def _config(model, wide):
    """(config, init_params or None) of a tiny model; `wide`: lane-aligned
    widths, which the kernels' verdicts ask for."""
    if model == "gpt":
        from paddle_tpu.models.gpt import GPTConfig
        return GPTConfig(vocab_size=97, hidden=128 if wide else 32,
                         layers=2, heads=2 if wide else 4, max_pos=256,
                         dropout=0.0, attn_impl="xla"), None
    if model in ("moonlight", "xing"):
        from paddle_tpu.models.moonlight import MoonlightConfig, init_params
        extra = {} if model == "moonlight" else dict(
            q_lora_rank=16, hc_mult=4, hc_sinkhorn_iters=6,
            name="Xing4.0-29B-A4B", rope_scaling=_YARN)
        return MoonlightConfig(
            vocab_size=211, hidden=128 if wide else 64, layers=3, heads=4,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, intermediate=96,
            moe_intermediate=128 if wide else 32, n_routed_experts=8,
            n_shared_experts=1, experts_per_tok=2, max_pos=256,
            **extra), init_params
    if model == "mellum":
        from paddle_tpu.models.mellum import MellumConfig, init_params
        return MellumConfig(
            vocab_size=211, hidden=128 if wide else 64, layers=4, heads=4,
            kv_heads=1, head_dim=64 if wide else 16,
            moe_intermediate=128 if wide else 32, n_routed_experts=8,
            experts_per_tok=2, sliding_window=8, max_pos=256,
            rope_scaling=dict(_YARN, original_max_position_embeddings=16)
        ), init_params
    if model == "command_a":
        from paddle_tpu.models.command_a import CommandAConfig, init_params
        return CommandAConfig(
            vocab_size=96, hidden=128 if wide else 64, layers=4, heads=8,
            kv_heads=2, head_dim=64 if wide else 16,
            moe_intermediate=128 if wide else 32, n_routed_experts=16,
            n_shared_experts=2, experts_per_tok=4, experts_held=(4, 4),
            vocab_slice=(0, 96, 768), sliding_window=8,
            max_pos=256), init_params
    if model == "kimi_linear":
        from paddle_tpu.models.kimi_linear import (KimiLinearConfig,
                                                   init_params)
        return KimiLinearConfig(
            vocab_size=96, hidden=128 if wide else 64, layers=5, heads=4,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, kda_heads=2, kda_head_dim=128 if wide else 16,
            intermediate=96, moe_intermediate=128 if wide else 32,
            n_routed_experts=8, n_shared_experts=1, experts_per_tok=2,
            experts_held=(2, 4), vocab_slice=(96, 96, 768),
            kda_decay_rank=16, kda_gate_rank=16, max_pos=256,
            init_range=0.08), init_params
    if model == "granite_hybrid":
        from paddle_tpu.models.granite_hybrid import (GraniteHybridConfig,
                                                      init_params)
        return GraniteHybridConfig(
            vocab_size=96, hidden=128 if wide else 64, layers=4,
            heads=2 if wide else 4, kv_heads=1 if wide else 2,
            layer_types=("mamba", "attention", "mamba", "mamba"),
            mamba_heads=16 if wide else 8, mamba_head_dim=16,
            mamba_state=128 if wide else 16, mamba_chunk=64 if wide else 8,
            moe_intermediate=128 if wide else 32,
            shared_intermediate=256 if wide else 48, n_routed_experts=8,
            experts_per_tok=3, experts_held=(4, 4),
            vocab_slice=(96, 96, 768), max_pos=256,
            init_range=0.08), init_params
    if model == "qwen3_next":
        from paddle_tpu.models.qwen3_next import Qwen3NextConfig, init_params
        return Qwen3NextConfig(
            vocab_size=96, hidden=128 if wide else 64, layers=4,
            heads=2 if wide else 4, kv_heads=1 if wide else 2,
            head_dim=64 if wide else 16, gdn_key_heads=1 if wide else 2,
            gdn_value_heads=2 if wide else 4,
            gdn_key_dim=128 if wide else 16,
            gdn_value_dim=128 if wide else 16,
            moe_intermediate=128 if wide else 32,
            shared_intermediate=128 if wide else 32, n_routed_experts=8,
            experts_per_tok=3, experts_held=(4, 4),
            vocab_slice=(96, 96, 768), max_pos=256,
            init_range=0.08), init_params
    if model == "longcat_flash":
        from paddle_tpu.models.longcat_flash import (LongcatFlashConfig,
                                                     init_params)
        return LongcatFlashConfig(
            vocab_size=96, hidden=128 if wide else 64, layers=2, heads=4,
            q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, intermediate=96,
            moe_intermediate=128 if wide else 32, n_routed_experts=8,
            zero_expert_num=4, experts_per_tok=4, experts_held=(2, 2),
            vocab_slice=(96, 96, 768), max_pos=256,
            init_range=0.08), init_params
    from paddle_tpu.models.sdar import SdarConfig, init_params
    return SdarConfig(
        vocab_size=211, hidden=128 if wide else 64, layers=2, heads=4,
        kv_heads=2, head_dim=64 if wide else 16,
        moe_intermediate=128 if wide else 32, n_routed_experts=8,
        experts_per_tok=2, max_pos=256, mask_token_id=210,
        init_range=0.08), init_params


@functools.lru_cache(maxsize=None)
def _model(model, wide):
    cfg, init = _config(model, wide)
    return cfg, _params(cfg, init)


def _params(cfg, init):
    if init is not None:
        return init(cfg, jax.random.PRNGKey(0), jnp.float32)
    import paddle_tpu as pt
    from paddle_tpu.models.gpt import gpt_lm_program
    from paddle_tpu.models.gpt_decode import collect_gpt_params
    _, startup, _ = gpt_lm_program(cfg, 8, is_test=True)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        pt.Executor().run(startup)
        return collect_gpt_params(scope, cfg)


def _hex(data):
    return hashlib.sha256(data).hexdigest()[:16]


def _jaxpr(fn, *args):
    return _hex(str(jax.make_jaxpr(fn)(*args)).encode())


def _forced(model, cfg):
    """The programs' own `attention=` argument, the Mosaic kernel named."""
    import importlib
    mod = importlib.import_module("paddle_tpu.models." + (
        "moonlight" if model == "xing" else model))
    if model == "sdar":
        return lambda p, t, a, pt, ts, d: mod.block_step_pages(
            p, cfg, t, a, pt, ts, d, attention="paged_kernel")
    return lambda p, t, a, pt, ts, d: mod.decode_step_pages(
        p, cfg, t, a, pt, ts, d, attention="latent_paged_kernel")


def _unpaged(program, cfg):
    """GPT's sequential programs (`gpt_generate`'s pair and the no-cache
    forward), which share the paged ones' pieces: tokens (2, 6), a cache of
    12 positions."""
    from paddle_tpu.models import gpt_decode as gd
    if program == "unpaged_prefill":
        return lambda p, t, c: gd.gpt_prefill(p, cfg, t, 12)
    if program == "unpaged_decode":
        return lambda p, t, c: gd.gpt_decode_step(p, cfg, t[:, 0], c, 6)
    return lambda p, t, c: gd.gpt_forward_logits(p, cfg, t)


def digest_of(case, force_backend):
    """The digest of one case; `force_backend(name)` makes
    `jax.default_backend()` answer `name` until the test ends."""
    from paddle_tpu.serving import SlotKVCache
    from paddle_tpu.serving.model import serving_model
    parts = case.split(".")
    model, program, variants = parts[0], parts[1], set(parts[2:])
    tpu = "tpu" in variants
    cfg, params = _model(model, tpu)
    if program == "init":
        leaves = jax.tree_util.tree_leaves(params)    # dicts: sorted keys
        return _hex(b"".join(np.asarray(x, np.float32).tobytes()
                             for x in leaves))
    if program in ("unpaged_prefill", "unpaged_decode", "forward_logits"):
        return _jaxpr(_unpaged(program, cfg), params,
                      jnp.zeros((2, 6), jnp.int32),
                      jnp.zeros((cfg.layers, 2, 2, cfg.heads, 12, 8)))
    if tpu:
        force_backend("tpu")
    served = serving_model(cfg)
    S, bs, bucket = 3, 4, 128 if tpu else 6 if "chunked" in variants else 16
    kv = SlotKVCache(cfg, S, 160, jnp.float32, block_size=bs,
                     kv_dtype="int8" if "int8kv" in variants else None)
    arena, pt = kv.arena, jnp.asarray(kv.page_table)
    kw = {}
    if "adapters" in variants:
        from paddle_tpu.serving.adapters import AdapterPool
        kw["adapters"] = AdapterPool(cfg, 3, 2).pool
    if "pinned" in variants:
        kw["arena_constraint"] = lambda a: a
    step = (jnp.zeros((S,), jnp.int32), arena, pt,
            jnp.ones((S,), jnp.int32), jnp.zeros((S,), bool))
    if program == "prefill":
        if "adapters" in kw:
            kw["adapter_id"] = jnp.int32(1)
        return _jaxpr(
            lambda p, t, a, pg, start, n: served.prefill(
                p, cfg, t, start, n, a, pg, **kw),
            params, jnp.zeros((1, bucket), jnp.int32), arena, pt[0],
            jnp.int32(0), jnp.int32(bucket - 3))
    if program == "verify":
        if "adapters" in kw:
            kw["adapter_ids"] = jnp.ones((S,), jnp.int32)
        return _jaxpr(
            lambda p, t, a, pt, ts, d: served.verify(p, cfg, t, a, pt, ts, d,
                                                     **kw),
            params, jnp.zeros((S, 3), jnp.int32), *step[1:])
    if program == "block":
        step = (jnp.zeros((S, cfg.block_length), jnp.int32), arena, pt,
                jnp.full((S,), 4, jnp.int32), jnp.zeros((S,), bool))
        fn = _forced(model, cfg) if "kernel" in variants else \
            lambda p, t, a, pt, ts, d: served.block_step(p, cfg, t, a, pt,
                                                         ts, d)
        return _jaxpr(fn, params, *step)
    assert program == "decode", case
    if "adapters" in kw:
        kw["adapter_ids"] = jnp.ones((S,), jnp.int32)
    fn = _forced(model, cfg) if "kernel" in variants else \
        lambda p, t, a, pt, ts, d: served.decode_step(p, cfg, t, a, pt, ts, d,
                                                      **kw)
    return _jaxpr(fn, params, *step)


CASES = (
    [f"gpt.{program}{variant}"
     for program in ("prefill", "decode", "verify")
     for variant in ("", ".int8kv", ".adapters", ".tpu", ".tpu.int8kv")]
    + ["gpt.prefill.tpu.pinned", "gpt.decode.tpu.pinned",
       "gpt.prefill.chunked", "gpt.prefill.chunked.int8kv",
       "gpt.unpaged_prefill", "gpt.unpaged_decode", "gpt.forward_logits"]
    + [f"{model}.{program}"
       for model in ("moonlight", "xing")
       for program in ("prefill", "decode", "decode.kernel", "prefill.tpu",
                       "decode.tpu")]
    + [f"{model}.{program}"
       for model in ("mellum", "command_a")
       for program in ("prefill.tpu", "decode.tpu")]
    + [f"sdar.{program}"
       for program in ("prefill", "block", "block.kernel", "prefill.tpu",
                       "block.tpu")]
    + [f"kimi_linear.{program}"
       for program in ("prefill", "decode", "prefill.tpu", "decode.tpu")]
    + [f"longcat_flash.{program}"
       for program in ("prefill", "decode", "decode.kernel", "prefill.tpu",
                       "decode.tpu")]
    + [f"granite_hybrid.{program}"
       for program in ("prefill", "decode", "prefill.tpu", "decode.tpu")]
    + [f"qwen3_next.{program}"
       for program in ("prefill", "decode", "prefill.tpu", "decode.tpu")]
    + [f"{model}.init" for model in ("gpt", "moonlight", "xing", "mellum",
                                     "command_a", "sdar", "kimi_linear",
                                     "longcat_flash", "granite_hybrid",
                                     "qwen3_next")])


@pytest.mark.parametrize("name", CASES)
def test_program_traces_what_the_parent_traced(name, monkeypatch):
    got = digest_of(name, lambda backend: monkeypatch.setattr(
        jax, "default_backend", lambda: backend))
    assert got == PARENT[name], (name, got)


if __name__ == "__main__":
    # the table, on whatever tree this file is run from
    class _Patch:
        def __call__(self, backend):
            self.real, jax.default_backend = jax.default_backend, \
                lambda: backend

    print("PARENT = {")
    for case in CASES:
        patch = _Patch()
        try:
            print(f'    "{case}": "{digest_of(case, patch)}",')
        finally:
            if hasattr(patch, "real"):
                jax.default_backend = patch.real
    print("}")
