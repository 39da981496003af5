"""Operations and bytes LongCat-Flash's layers need, from their shapes, for the SHARE of
the model this chip holds. `cfg` is the configuration file's dict (the published keys;
`n_routed_experts` the experts HELD, `published.n_routed_experts` + `zero_expert_num` the
router's width). What the algorithm needs, not what a kernel does: padded rows of a
bucket, padded lanes, a tile's rows that are nobody's and recomputation are not counted.
An identity expert has no parameter, no operation and no byte. Weights and latent rows are
bfloat16."""

BYTES = 2


def cache_layers(cfg):
    """Two latent attentions a layer."""
    return 2 * cfg["num_layers"]


def router_width(cfg):
    return cfg["published"]["n_routed_experts"] + cfg["zero_expert_num"]


def expert_params(cfg):
    """One routed expert: gate, up and down of a SwiGLU."""
    return 3 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"]


def router_params(cfg):
    """The router is as wide as the MODEL has outputs, whichever experts are held."""
    return cfg["hidden_size"] * router_width(cfg)


def dense_params(cfg):
    """One dense feed-forward: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["ffn_hidden_size"]


def latent_params(cfg):
    """One latent attention: the low-rank query pair, W_kva, W_kvb, W_o."""
    n, h = cfg["num_attention_heads"], cfg["hidden_size"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (h * cfg["q_lora_rank"] + cfg["q_lora_rank"] * n * qk
            + h * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * n * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + n * cfg["v_head_dim"] * h)


def moe_decode_bytes(cfg, experts_touched, passes):
    """Bytes the expert layers of decode steps have to read: each HELD expert that had a
    row, once for each pass in which it had one (the experts a step's picks made LIVE),
    and the router once a pass (a pass: one expert layer in one step)."""
    return BYTES * (experts_touched * expert_params(cfg) + passes * router_params(cfg))


def moe_flops(cfg, tokens, held_picks):
    """The expert layers' products of `tokens` tokens through every expert layer, 2
    operations a parameter: the router for every token a layer, a routed expert for each
    of the `held_picks` picks (summed over the layers) that fell on an expert held here;
    an identity pick is one multiply-add a value and is not counted."""
    return 2.0 * (cfg["num_layers"] * router_params(cfg) * tokens
                  + held_picks * expert_params(cfg))


def dense_decode_bytes(cfg, passes):
    """Bytes the dense feed-forwards of decode steps have to read: both of a layer, whole,
    once a pass (a pass: one layer in one step)."""
    return BYTES * passes * 2 * dense_params(cfg)


def latent_row_bytes(cfg):
    """One token's cache row in one cache layer: the latent and the shared key."""
    return BYTES * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def mla_decode_bytes(cfg, rows):
    """Bytes the latent attention of decode steps has to read for `rows` attended rows (a
    row: one live position of one cache layer)."""
    return rows * latent_row_bytes(cfg)


def weight_bytes(cfg):
    """Every matrix this chip holds (norm vectors and the router's bias left out)."""
    layer = 2 * latent_params(cfg) + 2 * dense_params(cfg) + router_params(cfg) \
        + cfg["n_routed_experts"] * expert_params(cfg)
    return BYTES * (2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["num_layers"] * layer)
