"""Operations and bytes Qwen3-Next-80B-A3B-Instruct's layers need, from their shapes, for the
SHARE of the model this chip holds. `cfg` is the configuration file's dict (the published
`qwen3_next` keys; `num_experts` the experts HELD, `published.num_experts` the router's
width; a layer's kind from `full_attention_interval` over the `num_hidden_layers` held). What
the algorithm needs, not what a kernel or a chunked form does: padded rows of a bucket, a
tile's rows that are nobody's, a key head's operands handed twice to its value heads and
recomputation are not counted. Weights and attention rows are bfloat16, the recurrent state
float32."""

BYTES = 2
STATE_BYTES = 4


def kinds(cfg):
    """(Gated-DeltaNet layers, attention layers) among the layers held here."""
    layers = cfg["num_hidden_layers"]
    attention = layers // cfg["full_attention_interval"]
    return layers - attention, attention


def expert_layers(cfg):
    return cfg["num_hidden_layers"]


def expert_params(cfg):
    """One routed expert: gate, up and down of a SwiGLU of moe_intermediate_size."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg):
    """The shared expert and the vector of its token gate."""
    return 3 * cfg["hidden_size"] * cfg["shared_expert_intermediate_size"] + cfg["hidden_size"]


def router_params(cfg):
    """The router is as wide as the MODEL has experts, whichever are held."""
    return cfg["hidden_size"] * cfg["published"]["num_experts"]


def held_pick_share(cfg):
    return cfg["num_experts"] / cfg["published"]["num_experts"]


def moe_decode_bytes(cfg, experts_touched, passes):
    """Bytes the expert layers of decode steps have to read: each HELD expert that had a
    row, once for each pass in which it had one, and the shared expert and the router once a
    pass (a pass: one expert layer in one step)."""
    return BYTES * (experts_touched * expert_params(cfg)
                    + passes * (shared_params(cfg) + router_params(cfg)))


def moe_flops(cfg, tokens, held_picks):
    """The expert layers' products of `tokens` tokens through every expert layer, 2
    operations a parameter: the shared expert and the router for every token a layer, a
    routed expert for each of the `held_picks` picks (summed over the layers) that fell on
    an expert held here."""
    per_token = shared_params(cfg) + router_params(cfg)
    return 2.0 * (expert_layers(cfg) * per_token * tokens + held_picks * expert_params(cfg))


def gdn_state_bytes(cfg):
    """A slot's recurrent state of one Gated-DeltaNet layer: value heads x key x value,
    float32 (2,097,152 B at the published widths)."""
    return (STATE_BYTES * cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"]
            * cfg["linear_value_head_dim"])


def gdn_history_bytes(cfg):
    """A slot's convolution history of one Gated-DeltaNet layer: the last K - 1
    pre-activation rows of q | k | v, bfloat16. It moves under `gdn/conv`, so no roofline of
    `gdn/recur` counts it."""
    width = (2 * cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
             + cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"])
    return BYTES * (cfg["linear_conv_kernel_dim"] - 1) * width


def gdn_decode_bytes(cfg, state_steps):
    """Bytes the recurrence of decode steps has to move UNDER `gdn/recur` for `state_steps`
    (live slot, Gated-DeltaNet layer) steps: the state block read once and written once."""
    return 2 * state_steps * gdn_state_bytes(cfg)


def gdn_prefill_flops(cfg, rows):
    """The recurrence's own products for `rows` (real row, Gated-DeltaNet layer) pairs, every
    value head: S^T k, the outer product into S and S^T q, each 2 dk dv operations (6 dk dv a
    head a row): a floor under what any chunked form does."""
    return (rows * cfg["linear_num_value_heads"] * 6.0 * cfg["linear_key_head_dim"]
            * cfg["linear_value_head_dim"])


def kv_row_bytes(cfg):
    """One token's cache row in one attention layer: K and V of every KV head (2,048 B)."""
    return BYTES * 2 * cfg["num_key_value_heads"] * cfg["head_dim"]


def decode_rows_bytes(cfg, rows):
    """Bytes the attention of decode steps has to read for `rows` attended rows (a row: one
    live position of one attention layer)."""
    return rows * kv_row_bytes(cfg)


def attn_prefill_flops(cfg, prompt_lens):
    """The causal triangle of each prompt's own rows (below `real_len`; a bucket's padding is
    not counted) in every attention layer: q k^T and p v, 2 operations a multiply, T (T + 1)
    / 2 pairs a head at head_dim."""
    _, attention = kinds(cfg)
    pairs = sum(t * (t + 1) / 2.0 for t in prompt_lens)
    return attention * cfg["num_attention_heads"] * 4.0 * cfg["head_dim"] * pairs


def gdn_params(cfg):
    h = cfg["hidden_size"]
    keys = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    values = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    return h * (2 * keys + 2 * values + 2 * cfg["linear_num_value_heads"]) + values * h


def attention_params(cfg):
    h, d = cfg["hidden_size"], cfg["head_dim"]
    return (h * cfg["num_attention_heads"] * 2 * d + 2 * h * cfg["num_key_value_heads"] * d
            + cfg["num_attention_heads"] * d * h)


def weight_bytes(cfg):
    """Every matrix this chip holds (norm vectors, filters and the decay's vectors left
    out): the embedding and the untied head, the mixers, the held experts, the shared
    expert and the router of every layer."""
    n_gdn, n_attention = kinds(cfg)
    moe = cfg["num_experts"] * expert_params(cfg) + shared_params(cfg) + router_params(cfg)
    return BYTES * (2 * cfg["vocab_size"] * cfg["hidden_size"] + n_gdn * gdn_params(cfg)
                    + n_attention * attention_params(cfg) + expert_layers(cfg) * moe)
