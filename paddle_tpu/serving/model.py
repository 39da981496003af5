"""What a model supplies to the serving engine.

The engine, the scheduler and the cache manager know no architecture. A
model is served through one `ServingModel`: its prefill of one prompt
suffix into its pages, its fused decode chunk over the whole pool, a
description of the cache state a token leaves behind in a layer
(`CacheSpec`: how many "heads" the arena has and how wide a row is), and
the engine features it implements. Everything else (slots, blocks, page
tables, prefix hashing, swap and migration payloads on the block axis,
the sampler, the done mask, the HTTP service) is the engine's and is the
same for every model.

A config object names its model by a `serving_model()` method
(`models.gpt.GPTConfig`, `models.moonlight.MoonlightConfig`); a config
without one is served as a GPT, which is what the engine did before it
had this interface. The model's module is imported when the first engine
over such a config is built, never by `import paddle_tpu`.
"""

from __future__ import annotations

from typing import NamedTuple

__all__ = ["FEATURES", "CacheSpec", "ServingModel", "serving_model",
           "require_features"]

# engine features a model may implement, each switched on by a
# ServingConfig field (see `require_features`)
FEATURES = ("int8_weights", "int8_kv", "adapters", "speculation", "mesh",
            "prefill_chunk")


class CacheSpec(NamedTuple):
    """The per-layer cache state of one token, as the arena stores it:
    the block arena is `(layers, 1, num_blocks, heads, block_size,
    row_width)`, blocks on axis 2 (swap payloads and migration tickets
    index it) and heads on axis 3 (a mesh plan shards it)."""
    layers: int
    heads: int
    row_width: int

    def arena_shape(self, num_blocks: int, block_size: int):
        return (self.layers, 1, num_blocks, self.heads, block_size,
                self.row_width)


class ServingModel:
    """The interface. A model subclasses it and hands ONE instance to
    the engine through its config's `serving_model()`.

    Array contracts (S slots, P pages a slot, B a prefill bucket):
      prefill(params, cfg, tokens (1, B), pfx_len, real_len, arena,
              pages (P,), adapters=None, adapter_id=None)
          -> (logits (1, V) f32 of position pfx_len + real_len - 1,
              arena, counters)
      prefill_chunk: the same with an arbitrary start position
          (feature "prefill_chunk")
      decode_chunk(params, cfg, tokens, arena, pt (S, P), ts, keys,
                   temps, done, remaining, eos_ids, chunk, sample_fn=,
                   speculate_k=, spec_state=, arena_constraint=,
                   adapters=, adapter_ids=)
          -> (block, tokens, arena, ts, keys, done, remaining, counters)
             and, speculating, (block, counts, tokens, arena, ts, keys,
             done, remaining, spec_state, counters)
    `counters` is None or a dict of small int32 arrays the program
    accumulated in-graph (a routed model's tokens per expert); the
    scheduler fetches them WITH the chunk's token block or the first
    token, adds them up on the host and `engine.stats()` reports them.
    """

    name = "model"
    features = frozenset()

    def max_positions(self, cfg) -> int:
        raise NotImplementedError

    def cache_spec(self, cfg) -> CacheSpec:
        raise NotImplementedError

    def activation_dtype(self, params):
        """bfloat16 for a bfloat16 parameter tree, else float32: the
        full-precision arena's type and the type the kernels run in."""
        raise NotImplementedError

    def decode_attention_path(self, arena, arena_constraint=None) -> str:
        """Which attention the decode chunk runs on this arena, for
        `engine.stats()["decode_attention"]`."""
        raise NotImplementedError

    def counter_names(self, cfg):
        """{name: shape} of the counters the programs return."""
        return {}

    def prefill(self, params, cfg, tokens, pfx_len, real_len, arena,
                pages, adapters=None, adapter_id=None):
        raise NotImplementedError

    def prefill_chunk(self, params, cfg, tokens, start_pos, real_len,
                      arena, pages, adapters=None, adapter_id=None):
        raise NotImplementedError

    def decode_chunk(self, params, cfg, tokens, arena, pt, ts, keys,
                     temps, done, remaining, eos_ids, chunk, **kw):
        raise NotImplementedError

    def quantize_params(self, params, cfg):
        """Weight-only int8 (feature "int8_weights")."""
        raise NotImplementedError

    def spec_ngram_seed(self, table, slot, tokens, real_len):
        """Seed a slot's drafter table (feature "speculation")."""
        raise NotImplementedError


def serving_model(cfg) -> ServingModel:
    """The model a config is served by."""
    named = getattr(cfg, "serving_model", None)
    if named is not None:
        return named()
    from ..models.gpt_decode import GPT_SERVING_MODEL
    return GPT_SERVING_MODEL


def require_features(model: ServingModel, serving) -> None:
    """Refuse, at engine construction, every ServingConfig option that
    asks for a feature `model` does not declare: an engine must never
    serve base-model tokens, read quantized rows as values or run one
    chip's program on a mesh because a model lacked the path."""
    asked = {
        "int8_weights": (serving.weight_dtype == "int8",
                         "weight_dtype='int8'"),
        "int8_kv": (serving.kv_dtype == "int8", "kv_dtype='int8'"),
        "adapters": (serving.max_adapters is not None, "max_adapters"),
        "speculation": (serving.speculate_k > 0,
                        "speculate_k > 0 (the verify pass)"),
        "mesh": (serving.mesh_shape is not None, "mesh_shape"),
        "prefill_chunk": (serving.prefill_chunk is not None,
                          "prefill_chunk"),
    }
    missing = [f"{option} needs {feature!r}"
               for feature, (on, option) in asked.items()
               if on and feature not in model.features]
    if missing:
        raise ValueError(
            f"the serving model {model.name!r} does not implement: "
            + "; ".join(missing)
            + f" (it declares {sorted(model.features)}) — refusing at "
            "construction rather than serving through a path that "
            "ignores the option")
