"""Operations and bytes Moonlight's new layers need, from their shapes. `cfg`
is the configuration file's dict (the published keys). What the algorithm
needs, not what a kernel does: padded lanes, rows of a bucket's padding and
recomputation are not counted. Weights and cache rows are bfloat16."""

BYTES = 2


def expert_layers(cfg):
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def expert_params(cfg):
    """One routed expert: gate, up and down of a SwiGLU."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg):
    return cfg["n_shared_experts"] * expert_params(cfg)


def router_params(cfg):
    return cfg["hidden_size"] * cfg["n_routed_experts"]


def moe_decode_bytes(cfg, experts_touched, passes):
    """Bytes the expert layers of decode steps have to read: each expert that
    had a row, once for each pass in which it had one, and the shared expert
    and the router once a pass (a pass: one expert layer in one step)."""
    return BYTES * (experts_touched * expert_params(cfg)
                    + passes * (shared_params(cfg) + router_params(cfg)))


def moe_flops(cfg, tokens):
    """Routed + shared + router products of `tokens` tokens through every
    expert layer: 2 operations a parameter a token, `num_experts_per_tok`
    experts each."""
    per_token = (cfg["num_experts_per_tok"] * expert_params(cfg) + shared_params(cfg)
                 + router_params(cfg))
    return 2.0 * expert_layers(cfg) * per_token * tokens


def latent_row_bytes(cfg):
    """One token's cache row in one layer: the latent and the rotated key."""
    return BYTES * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def mla_decode_bytes(cfg, live_positions):
    """Bytes the latent attention of one decode step has to read: every live
    position's row in every layer."""
    return cfg["num_hidden_layers"] * latent_row_bytes(cfg) * live_positions


def attention_params(cfg):
    n = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (cfg["hidden_size"] * n * qk
            + cfg["hidden_size"] * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * n * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + n * cfg["v_head_dim"] * cfg["hidden_size"])


def weight_bytes(cfg):
    """Every matrix of the configuration as it is cut (norm vectors left out)."""
    dense = 3 * cfg["hidden_size"] * cfg["intermediate_size"]
    moe = cfg["n_routed_experts"] * expert_params(cfg) + shared_params(cfg) + router_params(cfg)
    return BYTES * (2 * cfg["vocab_size"] * cfg["hidden_size"]
                    + cfg["num_hidden_layers"] * attention_params(cfg)
                    + cfg["first_k_dense_replace"] * dense + expert_layers(cfg) * moe)
