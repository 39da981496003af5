"""From SDAR-30B-A3B-Chat's configuration file (the published `sdar_moe` keys and, under
`assumed.generation`, the parameters of generation by diffusion over blocks) to the
program's objects: `paddle_tpu.models.sdar`'s config and weights made on the device from
the seed. A checkout whose program has no such model fails here, at the import, at once."""

from paddle_tpu.models import sdar as program

from . import model

# what the served block is written for; any other value is refused, not ignored
WRITTEN_FOR = (("attention_bias", False), ("hidden_act", "silu"), ("norm_topk_prob", True),
               ("tie_word_embeddings", False), ("use_sliding_window", False),
               ("decoder_sparse_step", 1), ("mlp_only_layers", []), ("rope_scaling", None),
               ("sliding_window", None))


def sdar_config(cfg):
    for key, want in WRITTEN_FOR:
        if cfg[key] != want:
            raise ValueError(f"the served block is written for {key} = {want!r}, "
                             f"the configuration says {cfg[key]!r}")
    gen = cfg["assumed"]["generation"]
    return program.SdarConfig(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        moe_intermediate=cfg["moe_intermediate_size"], n_routed_experts=cfg["num_experts"],
        experts_per_tok=cfg["num_experts_per_tok"], rms_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]), max_pos=cfg["max_position_embeddings"],
        block_length=gen["block_length"], denoising_steps=gen["denoising_steps"],
        confidence_threshold=gen["confidence_threshold"], remasking=gen["remasking_strategy"],
        mask_token_id=gen["mask_token_id"], init_range=cfg["assumed"]["initializer_range"])


def serving_params(cfg, seed, dtype):
    """The served weights, made on the device from the seed in the type they are served
    in (see the configuration's `assumed.weights`)."""
    import jax

    return program.init_params(sdar_config(cfg), jax.random.PRNGKey(model.fold_seed(seed)), dtype)
