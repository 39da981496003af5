"""Operations and bytes Kimi-Linear-48B-A3B-Instruct's layers need, from their shapes, for
the SHARE of the model this chip holds. `cfg` is the configuration file's dict (the
published `kimi_linear` keys; `num_experts` the experts HELD, `published.num_experts` the
router's width). What the algorithm needs, not what a kernel or a chunked form does:
padded rows of a bucket, padded lanes, a tile's rows that are nobody's and recomputation
are not counted. Weights and latent rows are bfloat16, the recurrent state float32."""

BYTES = 2
STATE_BYTES = 4


def kinds(cfg):
    """(KDA layers, latent layers) among the layers held here, by the published lists."""
    lin, depth = cfg["linear_attn_config"], cfg["num_hidden_layers"]
    return (sum(i <= depth for i in lin["kda_layers"]),
            sum(i <= depth for i in lin["full_attn_layers"]))


def expert_layers(cfg):
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def expert_params(cfg):
    """One routed or one shared expert: gate, up and down of a SwiGLU."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg):
    return cfg["num_shared_experts"] * expert_params(cfg)


def router_params(cfg):
    """The router is as wide as the MODEL has experts, whichever are held."""
    return cfg["hidden_size"] * cfg["published"]["num_experts"]


def held_pick_share(cfg):
    return cfg["num_experts"] / cfg["published"]["num_experts"]


def moe_decode_bytes(cfg, experts_touched, passes):
    """Bytes the expert layers of decode steps have to read: each HELD expert that had a
    row, once for each pass in which it had one, and the shared expert and the router
    once a pass (a pass: one expert layer in one step)."""
    return BYTES * (experts_touched * expert_params(cfg)
                    + passes * (shared_params(cfg) + router_params(cfg)))


def moe_flops(cfg, tokens, held_picks):
    """The expert layers' products of `tokens` tokens through every expert layer, 2
    operations a parameter: the shared expert and the router for every token a layer, a
    routed expert for each of the `held_picks` picks (summed over the layers) that fell
    on an expert held here."""
    per_token = shared_params(cfg) + router_params(cfg)
    return 2.0 * (expert_layers(cfg) * per_token * tokens + held_picks * expert_params(cfg))


def kda_state_bytes(cfg):
    """A slot's recurrent state of one KDA layer: heads x key x value, float32."""
    lin = cfg["linear_attn_config"]
    return STATE_BYTES * lin["num_heads"] * lin["head_dim"] ** 2


def kda_history_bytes(cfg):
    """A slot's convolution history of one KDA layer: the last K - 1 pre-activation rows
    of q|k|v, bfloat16. It moves under `kda/conv`, so no roofline of `kda/recur` counts
    it."""
    lin = cfg["linear_attn_config"]
    return BYTES * (lin["short_conv_kernel_size"] - 1) * 3 * lin["num_heads"] * lin["head_dim"]


def kda_decode_bytes(cfg, state_steps):
    """Bytes the recurrence of decode steps has to move UNDER `kda/recur` for
    `state_steps` (live slot, KDA layer) steps: the state block read once and written
    once (the history's gather and scatter run under `kda/conv`: its time is not in the
    denominator, so its bytes are not in here)."""
    return 2 * state_steps * kda_state_bytes(cfg)


def kda_prefill_flops(cfg, rows):
    """The recurrence's own three products for `rows` (real row, KDA layer) pairs, every
    head: S'^T k, the outer product into S and S^T q, each 2 d^2 operations: a floor
    under what any chunked form does."""
    lin = cfg["linear_attn_config"]
    return rows * lin["num_heads"] * 6.0 * lin["head_dim"] ** 2


def latent_row_bytes(cfg):
    """One token's cache row in one latent layer: the latent and the shared key."""
    return BYTES * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def mla_decode_bytes(cfg, rows):
    """Bytes the latent attention of decode steps has to read for `rows` attended rows
    (a row: one live position of one latent layer)."""
    return rows * latent_row_bytes(cfg)


def kda_params(cfg):
    lin, h = cfg["linear_attn_config"], cfg["hidden_size"]
    width, ranks = lin["num_heads"] * lin["head_dim"], cfg["assumed"]
    low_rank = h * (ranks["kda_decay_rank"] + ranks["kda_gate_rank"]) \
        + (ranks["kda_decay_rank"] + ranks["kda_gate_rank"]) * width
    return 3 * h * width + width * h + low_rank + h * lin["num_heads"]


def latent_params(cfg):
    n, h = cfg["num_attention_heads"], cfg["hidden_size"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (h * n * qk + h * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * n * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + n * cfg["v_head_dim"] * h)


def weight_bytes(cfg):
    """Every matrix this chip holds (norm vectors, filters and the decay's vectors left
    out): embedding and untied head, the mixers, the dense layer, the held experts."""
    n_kda, n_latent = kinds(cfg)
    dense = 3 * cfg["hidden_size"] * cfg["intermediate_size"]
    moe = cfg["num_experts"] * expert_params(cfg) + shared_params(cfg) + router_params(cfg)
    return BYTES * (2 * cfg["vocab_size"] * cfg["hidden_size"] + n_kda * kda_params(cfg)
                    + n_latent * latent_params(cfg) + cfg["first_k_dense_replace"] * dense
                    + expert_layers(cfg) * moe)
