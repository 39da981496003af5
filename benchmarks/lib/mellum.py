"""From Mellum2-12B-A2.5B-Instruct's configuration file (the published `mellum`
keys) to the program's objects: `paddle_tpu.models.mellum`'s config and weights made
on the device from the seed. A checkout whose program has no such model fails here,
at the import, at once."""

from . import model

# what the served block is written for; any other value is refused, not ignored
WRITTEN_FOR = (("attention_bias", False), ("hidden_act", "silu"), ("norm_topk_prob", True),
               ("tie_word_embeddings", False), ("use_sliding_window", True))


def mellum_config(cfg):
    import math

    from paddle_tpu.models.mellum import MellumConfig

    for key, want in WRITTEN_FOR:
        if cfg[key] != want:
            raise ValueError(f"the served block is written for {key} = {want!r}, "
                             f"the configuration says {cfg[key]!r}")
    if set(cfg["mlp_layer_types"]) != {"sparse"}:
        raise ValueError("mlp_layer_types: every layer of the served block is sparse, the "
                         f"configuration says {sorted(set(cfg['mlp_layer_types']))}")
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"] \
            or len(cfg["mlp_layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types and mlp_layer_types name num_hidden_layers layers each")
    full, sliding = (cfg["rope_parameters"][k] for k in ("full_attention", "sliding_attention"))
    if sliding["rope_type"] != "default" or full["rope_type"] != "yarn" \
            or sliding["rope_theta"] != full["rope_theta"]:
        raise ValueError("rope_parameters: the block is written for plain rotary positions in "
                         "the sliding layers, YaRN in the full ones and one theta")
    # the program scales cos and sin by 0.1 ln(factor) + 1; the published number is that
    if abs(full["attention_factor"] - (0.1 * math.log(full["factor"]) + 1.0)) > 1e-12:
        raise ValueError(f"rope_parameters.full_attention.attention_factor "
                         f"{full['attention_factor']} is not 0.1 ln(factor) + 1")
    return MellumConfig(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        moe_intermediate=cfg["moe_intermediate_size"], n_routed_experts=cfg["num_experts"],
        experts_per_tok=cfg["num_experts_per_tok"], layer_types=cfg["layer_types"],
        sliding_window=cfg["sliding_window"], rms_eps=cfg["rms_norm_eps"],
        rope_theta=float(full["rope_theta"]),
        rope_scaling={"type": "yarn", "factor": full["factor"],
                      "original_max_position_embeddings": full["original_max_position_embeddings"],
                      "beta_fast": full["beta_fast"], "beta_slow": full["beta_slow"]},
        max_pos=cfg["max_position_embeddings"],
        init_range=cfg["assumed"]["initializer_range"])


def serving_params(cfg, seed, dtype):
    """The served weights, made on the device from the seed in the type they are
    served in (see the configuration's `assumed.weights`)."""
    import jax
    from paddle_tpu.models.mellum import init_params

    return init_params(mellum_config(cfg), jax.random.PRNGKey(model.fold_seed(seed)), dtype)
