"""From command-a-plus-05-2026's configuration file (the published `cohere2_moe` keys,
cut to one chip's share of its deployment) to the program's objects:
`paddle_tpu.models.command_a`'s config and weights made on the device from the seed. A
checkout whose program has no such model fails here, at the import, at once."""

from . import model

# what the served block is written for; any other value is refused, not ignored
WRITTEN_FOR = (("attention_bias", False), ("hidden_act", "silu"), ("norm_topk_prob", True),
               ("tie_word_embeddings", True), ("use_parallel_block", True),
               ("use_qk_norm", False), ("use_gated_activation", True),
               ("expert_selection_fn", "sigmoid"), ("first_k_dense_replace", 0),
               ("shared_expert_combination_strategy", "average"),
               ("position_embedding_type", "rope_gptj"), ("rotary_pct", 1),
               ("order_of_interleaved_layers", "local_attn_first"), ("rms_norm_eps", None))


def command_a_config(cfg):
    from paddle_tpu.models.command_a import CommandAConfig

    for key, want in WRITTEN_FOR:
        if cfg[key] != want:
            raise ValueError(f"the served block is written for {key} = {want!r}, "
                             f"the configuration says {cfg[key]!r}")
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types names num_hidden_layers layers")
    rope = cfg["rope_parameters"]
    if rope["rope_type"] != "default" or rope["rope_theta"] != cfg["rope_theta"]:
        raise ValueError("rope_parameters: the block is written for plain rotary positions "
                         "in the sliding layers at the config's one theta")
    published = cfg["published"]
    return CommandAConfig(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        moe_intermediate=cfg["intermediate_size"], n_routed_experts=published["num_experts"],
        n_shared_experts=cfg["num_shared_experts"],
        experts_per_tok=cfg["num_experts_per_tok"],
        experts_held=(cfg["experts_held_first"], cfg["num_experts"]),
        vocab_slice=(cfg["vocab_first_row"], cfg["vocab_size"], published["vocab_size"]),
        layer_types=cfg["layer_types"], sliding_window=cfg["sliding_window"],
        layer_norm_eps=cfg["layer_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        logit_scale=float(cfg["logit_scale"]), max_pos=cfg["max_position_embeddings"],
        init_range=cfg["assumed"]["initializer_range"])


def serving_params(cfg, seed, dtype):
    """The served weights, made on the device from the seed in the type they are
    served in (see the configuration's `assumed.weights`)."""
    import jax
    from paddle_tpu.models.command_a import init_params

    return init_params(command_a_config(cfg), jax.random.PRNGKey(model.fold_seed(seed)), dtype)
