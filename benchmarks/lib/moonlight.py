"""From Moonlight's configuration file (the published DeepSeek-V3 keys) to the
program's objects: its MoonlightConfig and weights made on the device from the
seed. `lib/model.py` stays GPT-2's."""

from . import model


def moonlight_config(cfg):
    from paddle_tpu.models.moonlight import MoonlightConfig

    for key, want in (("q_lora_rank", None), ("n_group", 1), ("topk_group", 1),
                      ("scoring_func", "sigmoid"), ("norm_topk_prob", True),
                      ("moe_layer_freq", 1), ("tie_word_embeddings", False),
                      ("attention_bias", False), ("hidden_act", "silu")):
        if cfg[key] != want:
            raise ValueError(f"the served block is written for {key} = {want!r}, "
                             f"the configuration says {cfg[key]!r}")
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
        init_range=cfg["assumed"]["initializer_range"])


def serving_params(cfg, seed, dtype):
    """The served weights, made on the device from the seed in the type they
    are served in (three small programs: the embedding and head, a dense
    layer, an expert layer; see the configuration's `assumed.weights`)."""
    import jax
    from paddle_tpu.models.moonlight import init_params

    return init_params(moonlight_config(cfg), jax.random.PRNGKey(model.fold_seed(seed)), dtype)
