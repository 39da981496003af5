"""From Kimi-Linear-48B-A3B-Instruct's configuration file (the published `kimi_linear`
keys, cut to one chip's share of its deployment) to the program's objects:
`paddle_tpu.models.kimi_linear`'s config and weights made on the device from the seed. A
checkout whose program has no such model fails here, at the import, at once."""

from paddle_tpu.models import kimi_linear as program

from . import model

# what the served block is written for; any other value is refused, not ignored
WRITTEN_FOR = (("hidden_act", "silu"), ("mla_use_nope", True), ("q_lora_rank", None),
               ("rope_scaling", None), ("moe_layer_freq", 1), ("moe_renormalize", True),
               ("moe_router_activation_func", "sigmoid"), ("num_expert_group", 1),
               ("topk_group", 1), ("num_nextn_predict_layers", 0),
               ("tie_word_embeddings", False))


def kimi_linear_config(cfg):
    for key, want in WRITTEN_FOR:
        if cfg[key] != want:
            raise ValueError(f"the served block is written for {key} = {want!r}, "
                             f"the configuration says {cfg[key]!r}")
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("the latent layers expand one key and one value a query head")
    lin, published, assumed = cfg["linear_attn_config"], cfg["published"], cfg["assumed"]
    layers = cfg["num_hidden_layers"]
    # the published lists name all 27 layers; a stage holds those up to its depth
    kinds = {name: [i for i in lin[name] if i <= layers]
             for name in ("kda_layers", "full_attn_layers")}
    return program.KimiLinearConfig(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"], layers=layers,
        heads=cfg["num_attention_heads"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"], qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        short_conv_kernel_size=lin["short_conv_kernel_size"], **kinds,
        intermediate=cfg["intermediate_size"], moe_intermediate=cfg["moe_intermediate_size"],
        n_routed_experts=published["num_experts"], n_shared_experts=cfg["num_shared_experts"],
        experts_per_tok=cfg["num_experts_per_token"],
        first_k_dense=cfg["first_k_dense_replace"],
        routed_scaling_factor=cfg["routed_scaling_factor"], rms_eps=cfg["rms_norm_eps"],
        experts_held=(cfg["experts_held_first"], cfg["num_experts"]),
        vocab_slice=(cfg["vocab_first_row"], cfg["vocab_size"], published["vocab_size"]),
        kda_decay_rank=assumed["kda_decay_rank"], kda_gate_rank=assumed["kda_gate_rank"],
        l2_eps=assumed["kda_l2_eps"], state_dtype=assumed["kda_state_dtype"],
        max_pos=cfg["model_max_length"], init_range=assumed["initializer_range"])


def serving_params(cfg, seed, dtype):
    """The served weights, made on the device from the seed in the type they are served
    in (see the configuration's `assumed.weights`)."""
    import jax

    return program.init_params(kimi_linear_config(cfg),
                               jax.random.PRNGKey(model.fold_seed(seed)), dtype)
