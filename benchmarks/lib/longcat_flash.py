"""From LongCat-Flash-Omni's configuration file (the published language-model keys, cut to
one chip's share of its deployment) to the program's objects:
`paddle_tpu.models.longcat_flash`'s config and weights made on the device from the seed. A
checkout whose program has no such model fails here, at the import, at once."""

from paddle_tpu.models import longcat_flash as program

from . import model

# what the served block is written for; any other value is refused, not ignored
WRITTEN_FOR = (("attention_method", "MLA"), ("attention_bias", False),
               ("zero_expert_type", "identity"))


def longcat_flash_config(cfg):
    for key, want in WRITTEN_FOR:
        if cfg[key] != want:
            raise ValueError(f"the served block is written for {key} = {want!r}, "
                             f"the configuration says {cfg[key]!r}")
    if cfg.get("rope_scaling") is not None:
        raise ValueError("the served block rotates at rope_theta alone: no rope_scaling")
    published, assumed = cfg["published"], cfg["assumed"]
    return program.LongcatFlashConfig(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"], layers=cfg["num_layers"],
        heads=cfg["num_attention_heads"], q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"], qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        intermediate=cfg["ffn_hidden_size"], moe_intermediate=cfg["expert_ffn_hidden_size"],
        n_routed_experts=published["n_routed_experts"], zero_expert_num=cfg["zero_expert_num"],
        experts_per_tok=cfg["moe_topk"], routed_scaling_factor=cfg["routed_scaling_factor"],
        rms_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        mla_scale_q_lora=cfg["mla_scale_q_lora"], mla_scale_kv_lora=cfg["mla_scale_kv_lora"],
        experts_held=(cfg["experts_held_first"], cfg["n_routed_experts"]),
        vocab_slice=(cfg["vocab_first_row"], cfg["vocab_size"], published["vocab_size"]),
        max_pos=cfg["max_position_embeddings"], init_range=assumed["initializer_range"],
        router_bias_std=assumed["router_bias_std"])


def serving_params(cfg, seed, dtype):
    """The served weights, made on the device from the seed in the type they are served
    in (see the configuration's `assumed.weights`)."""
    import jax

    return program.init_params(longcat_flash_config(cfg),
                               jax.random.PRNGKey(model.fold_seed(seed)), dtype)
