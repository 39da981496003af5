"""From granite-4.0-h-small's configuration file (the published `granitemoehybrid` keys, cut
to one chip's share of its deployment) to the program's objects:
`paddle_tpu.models.granite_hybrid`'s config and weights made on the device from the seed. A
checkout whose program has no such model fails here, at the import, at once."""

from paddle_tpu.models import granite_hybrid as program

from . import model

# what the served block is written for; any other value is refused, not ignored
WRITTEN_FOR = (("hidden_act", "silu"), ("normalization_function", "rmsnorm"),
               ("position_embedding_type", "nope"), ("rope_scaling", None),
               ("attention_bias", False), ("mamba_proj_bias", False), ("mamba_conv_bias", True),
               ("mamba_n_groups", 1), ("tie_word_embeddings", True))


def granite_hybrid_config(cfg):
    for key, want in WRITTEN_FOR:
        if cfg[key] != want:
            raise ValueError(f"the served block is written for {key} = {want!r}, "
                             f"the configuration says {cfg[key]!r}")
    published, assumed = cfg["published"], cfg["assumed"]
    layers = cfg["num_hidden_layers"]
    # the published list names all 40 layers; a stage holds those up to its depth
    return program.GraniteHybridConfig(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"], layers=layers,
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        layer_types=cfg["layer_types"][:layers], mamba_heads=cfg["mamba_n_heads"],
        mamba_head_dim=cfg["mamba_d_head"], mamba_state=cfg["mamba_d_state"],
        mamba_groups=cfg["mamba_n_groups"], mamba_conv=cfg["mamba_d_conv"],
        mamba_expand=cfg["mamba_expand"], mamba_chunk=cfg["mamba_chunk_size"],
        moe_intermediate=cfg["intermediate_size"],
        shared_intermediate=cfg["shared_intermediate_size"],
        n_routed_experts=published["num_local_experts"],
        experts_per_tok=cfg["num_experts_per_tok"],
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        attention_multiplier=cfg["attention_multiplier"], logits_scaling=cfg["logits_scaling"],
        rms_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        experts_held=(cfg["experts_held_first"], cfg["num_local_experts"]),
        vocab_slice=(cfg["vocab_first_row"], cfg["vocab_size"], published["vocab_size"]),
        state_dtype=assumed["mamba_state_dtype"], max_pos=cfg["max_position_embeddings"],
        init_range=assumed["initializer_range"])


def serving_params(cfg, seed, dtype):
    """The served weights, made on the device from the seed in the type they are served
    in (see the configuration's `assumed.weights`)."""
    import jax

    return program.init_params(granite_hybrid_config(cfg),
                               jax.random.PRNGKey(model.fold_seed(seed)), dtype)
