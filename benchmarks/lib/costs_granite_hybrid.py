"""Operations and bytes granite-4.0-h-small's layers need, from their shapes, for the SHARE
of the model this chip holds. `cfg` is the configuration file's dict (the published
`granitemoehybrid` keys; `num_local_experts` the experts HELD, `published.num_local_experts`
the router's width; a layer's kind from `layer_types`, the first `num_hidden_layers` of
them). What the algorithm needs, not what a kernel or a chunked form does: padded rows of a
bucket, padded lanes, a tile's rows that are nobody's and recomputation are not counted.
Weights and attention rows are bfloat16, the recurrent state float32."""

BYTES = 2
STATE_BYTES = 4


def kinds(cfg):
    """(mamba layers, attention layers) among the layers held here."""
    held = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return held.count("mamba"), held.count("attention")


def expert_layers(cfg):
    return cfg["num_hidden_layers"]


def expert_params(cfg):
    """One routed expert: gate, up and down of a SwiGLU of intermediate_size."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def shared_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def router_params(cfg):
    """The router is as wide as the MODEL has experts, whichever are held."""
    return cfg["hidden_size"] * cfg["published"]["num_local_experts"]


def held_pick_share(cfg):
    return cfg["num_local_experts"] / cfg["published"]["num_local_experts"]


def moe_decode_bytes(cfg, experts_touched, passes):
    """Bytes the expert layers of decode steps have to read: each HELD expert that had a
    row, once for each pass in which it had one, and the shared feed-forward and the router
    once a pass (a pass: one expert layer in one step)."""
    return BYTES * (experts_touched * expert_params(cfg)
                    + passes * (shared_params(cfg) + router_params(cfg)))


def moe_flops(cfg, tokens, held_picks):
    """The expert layers' products of `tokens` tokens through every expert layer, 2
    operations a parameter: the shared feed-forward and the router for every token a layer,
    a routed expert for each of the `held_picks` picks (summed over the layers) that fell on
    an expert held here."""
    per_token = shared_params(cfg) + router_params(cfg)
    return 2.0 * (expert_layers(cfg) * per_token * tokens + held_picks * expert_params(cfg))


def ssd_state_bytes(cfg):
    """A slot's recurrent state of one mamba layer: heads x head channels x state,
    float32."""
    return STATE_BYTES * cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]


def ssd_history_bytes(cfg):
    """A slot's convolution history of one mamba layer: the last K - 1 pre-activation rows
    of x|B|C, bfloat16. It moves under `ssd/conv`, so no roofline of `ssd/step` counts it."""
    width = cfg["mamba_n_heads"] * cfg["mamba_d_head"] + 2 * cfg["mamba_d_state"]
    return BYTES * (cfg["mamba_d_conv"] - 1) * width


def ssd_decode_bytes(cfg, state_steps):
    """Bytes the recurrence of decode steps has to move UNDER `ssd/step` for `state_steps`
    (live slot, mamba layer) steps: the state block read once and written once (the
    history's gather and scatter run under `ssd/conv`: its time is not in the denominator,
    so its bytes are not in here)."""
    return 2 * state_steps * ssd_state_bytes(cfg)


def ssd_prefill_flops(cfg, rows):
    """The recurrence's own two products for `rows` (real row, mamba layer) pairs, every
    head: the outer product into S and S C, each 2 P N operations (the decay's multiply of S
    is a third pass over it, counted with them: 6 P N a head a row): a floor under what any
    chunked form does."""
    return rows * cfg["mamba_n_heads"] * 6.0 * cfg["mamba_d_head"] * cfg["mamba_d_state"]


def kv_row_bytes(cfg):
    """One token's cache row in the attention layer: K and V of every KV head."""
    head_dim = cfg["hidden_size"] // cfg["num_attention_heads"]
    return BYTES * 2 * cfg["num_key_value_heads"] * head_dim


def decode_rows_bytes(cfg, rows):
    """Bytes the attention of decode steps has to read for `rows` attended rows (a row: one
    live position of one attention layer)."""
    return rows * kv_row_bytes(cfg)


def mamba_params(cfg):
    inner, h = cfg["mamba_n_heads"] * cfg["mamba_d_head"], cfg["hidden_size"]
    return h * (2 * inner + 2 * cfg["mamba_d_state"] + cfg["mamba_n_heads"]) + inner * h


def attention_params(cfg):
    h = cfg["hidden_size"]
    head_dim = h // cfg["num_attention_heads"]
    return 2 * h * h + 2 * h * cfg["num_key_value_heads"] * head_dim


def weight_bytes(cfg):
    """Every matrix this chip holds (norm vectors, filters and the decay's vectors left
    out): the tied embedding, the mixers, the held experts, the shared feed-forward and
    the router of every layer."""
    n_mamba, n_attention = kinds(cfg)
    moe = cfg["num_local_experts"] * expert_params(cfg) + shared_params(cfg) + router_params(cfg)
    return BYTES * (cfg["vocab_size"] * cfg["hidden_size"] + n_mamba * mamba_params(cfg)
                    + n_attention * attention_params(cfg) + expert_layers(cfg) * moe)
