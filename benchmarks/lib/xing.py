"""From Xing4.0-29B-A4B's configuration file (the published `xing4_0` keys) to
the program's objects: the block that serves Moonlight, configured with the
query's low-rank pair, YaRN positions and four residual streams, and weights
made on the device from the seed. `lib/moonlight.py` stays Moonlight's."""

from . import model

# what the served block is written for; any other value is refused, not ignored
WRITTEN_FOR = (("num_nextn_predict_layers", 0), ("n_group", 1), ("topk_group", 1),
               ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
               ("norm_topk_prob", True), ("moe_layer_freq", 1), ("ep_size", 1),
               ("tie_word_embeddings", False), ("attention_bias", False),
               ("hidden_act", "silu"))


def xing_config(cfg):
    import inspect

    from paddle_tpu.models.moonlight import MoonlightConfig

    if "hc_mult" not in inspect.signature(MoonlightConfig.__init__).parameters:
        raise ValueError("this checkout's latent-attention block has no residual streams "
                         "(MoonlightConfig takes no hc_mult): it cannot serve xing4_0")
    for key, want in WRITTEN_FOR:
        if cfg[key] != want:
            raise ValueError(f"the served block is written for {key} = {want!r}, "
                             f"the configuration says {cfg[key]!r}")
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("num_key_value_heads: latent attention has one latent row for all "
                         f"{cfg['num_attention_heads']} heads, not {cfg['num_key_value_heads']}")
    if cfg["mhc_h_res_clamp_min"] != -cfg["mhc_h_res_clamp_max"]:
        raise ValueError("mhc_h_res_clamp_min: the mixer clamps symmetrically, the "
                         f"configuration says {cfg['mhc_h_res_clamp_min']} and "
                         f"{cfg['mhc_h_res_clamp_max']}")
    return MoonlightConfig(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
        kv_lora_rank=cfg["kv_lora_rank"], qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        intermediate=cfg["intermediate_size"], moe_intermediate=cfg["moe_intermediate_size"],
        n_routed_experts=cfg["n_routed_experts"], n_shared_experts=cfg["n_shared_experts"],
        experts_per_tok=cfg["num_experts_per_tok"], first_k_dense=cfg["first_k_dense_replace"],
        routed_scaling_factor=cfg["routed_scaling_factor"], rms_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]), max_pos=cfg["max_position_embeddings"],
        init_range=cfg["assumed"]["initializer_range"], q_lora_rank=cfg["q_lora_rank"],
        rope_scaling=cfg["rope_scaling"], hc_mult=cfg["hc_mult"],
        hc_sinkhorn_iters=cfg["hc_sinkhorn_iters"], hc_eps=cfg["hc_eps"],
        hc_res_clamp=cfg["mhc_h_res_clamp_max"], name="Xing4.0-29B-A4B")


def serving_params(cfg, seed, dtype):
    """The served weights, made on the device from the seed in the type they
    are served in (see the configuration's `assumed.weights`)."""
    import jax
    from paddle_tpu.models.moonlight import init_params

    return init_params(xing_config(cfg), jax.random.PRNGKey(model.fold_seed(seed)), dtype)
