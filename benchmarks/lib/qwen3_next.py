"""From Qwen3-Next-80B-A3B-Instruct's configuration file (the published `qwen3_next` keys, cut
to one chip's share of its deployment) to the program's objects:
`paddle_tpu.models.qwen3_next`'s config and weights made on the device from the seed. A
checkout whose program has no such model fails here, at the import, at once."""

from paddle_tpu.models import qwen3_next as program

from . import model

# what the served block is written for; any other value is refused, not ignored
WRITTEN_FOR = (("hidden_act", "silu"), ("rope_scaling", None), ("norm_topk_prob", True),
               ("decoder_sparse_step", 1), ("mlp_only_layers", []),
               ("use_sliding_window", False), ("tie_word_embeddings", False))


def qwen3_next_config(cfg):
    for key, want in WRITTEN_FOR:
        if cfg[key] != want:
            raise ValueError(f"the served block is written for {key} = {want!r}, "
                             f"the configuration says {cfg[key]!r}")
    published, assumed = cfg["published"], cfg["assumed"]
    return program.Qwen3NextConfig(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        full_attention_interval=cfg["full_attention_interval"],
        partial_rotary_factor=cfg["partial_rotary_factor"], rope_theta=cfg["rope_theta"],
        gdn_key_heads=cfg["linear_num_key_heads"], gdn_value_heads=cfg["linear_num_value_heads"],
        gdn_key_dim=cfg["linear_key_head_dim"], gdn_value_dim=cfg["linear_value_head_dim"],
        gdn_conv=cfg["linear_conv_kernel_dim"], moe_intermediate=cfg["moe_intermediate_size"],
        shared_intermediate=cfg["shared_expert_intermediate_size"],
        n_routed_experts=published["num_experts"], experts_per_tok=cfg["num_experts_per_tok"],
        rms_eps=cfg["rms_norm_eps"],
        experts_held=(cfg["experts_held_first"], cfg["num_experts"]),
        vocab_slice=(cfg["vocab_first_row"], cfg["vocab_size"], published["vocab_size"]),
        l2_eps=assumed["gdn_l2_eps"], state_dtype=assumed["gdn_state_dtype"],
        max_pos=cfg["max_position_embeddings"], init_range=assumed["initializer_range"])


def serving_params(cfg, seed, dtype):
    """The served weights, made on the device from the seed in the type they are served
    in (see the configuration's `assumed.weights`)."""
    import jax

    return program.init_params(qwen3_next_config(cfg),
                               jax.random.PRNGKey(model.fold_seed(seed)), dtype)
