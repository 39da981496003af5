"""Operations and bytes command-a-plus-05-2026's layers need, from their shapes, for the
SHARE of the model this chip holds. `cfg` is the configuration file's dict (the
published `cohere2_moe` keys; `num_experts` the experts HELD, `published.num_experts`
the router's width). What the algorithm needs, not what a kernel does: padded rows of a
bucket, a page's rows outside the window, a tile's rows that are nobody's and
recomputation are not counted. Weights and cache rows are bfloat16."""

BYTES = 2


def layers_of(cfg, kind):
    """How many layers are "full_attention" or "sliding_attention"."""
    return sum(t == kind for t in cfg["layer_types"])


def expert_params(cfg):
    """One routed or one shared expert: gate, up and down of a SwiGLU."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def shared_params(cfg):
    return cfg["num_shared_experts"] * expert_params(cfg)


def router_params(cfg):
    """The router is as wide as the MODEL has experts, whichever are held."""
    return cfg["hidden_size"] * cfg["published"]["num_experts"]


def attention_params(cfg):
    """W_q, W_k, W_v, W_o."""
    d, h = cfg["head_dim"], cfg["hidden_size"]
    n, n_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h * (n + 2 * n_kv) * d + n * d * h


def weight_bytes(cfg):
    """Every matrix this chip holds (norm vectors left out); the embedding is the
    head."""
    layer = (attention_params(cfg) + router_params(cfg) + shared_params(cfg)
             + cfg["num_experts"] * expert_params(cfg))
    return BYTES * (cfg["vocab_size"] * cfg["hidden_size"] + cfg["num_hidden_layers"] * layer)


def cache_row_bytes(cfg):
    """One token's K and V in one layer, every KV head."""
    return BYTES * cfg["num_key_value_heads"] * 2 * cfg["head_dim"]


def held_pick_share(cfg):
    """The share of a token's picks that fall on an expert held here, were the router
    even over the published experts."""
    return cfg["num_experts"] / cfg["published"]["num_experts"]


def moe_decode_bytes(cfg, experts_touched, passes):
    """Bytes the expert layers of decode steps have to read: each HELD expert that had
    a row, once for each pass in which it had one, and the shared experts and the
    router once a pass (a pass: one layer in one step)."""
    return BYTES * (experts_touched * expert_params(cfg)
                    + passes * (shared_params(cfg) + router_params(cfg)))


def moe_flops(cfg, tokens, held_picks):
    """The expert layers' products of `tokens` tokens through every layer, 2 operations
    a parameter: the shared experts and the router for every token a layer, a routed
    expert for each of the `held_picks` picks (summed over the layers) that fell on an
    expert held here."""
    per_token = shared_params(cfg) + router_params(cfg)
    return 2.0 * (cfg["num_hidden_layers"] * per_token * tokens
                  + held_picks * expert_params(cfg))


def attended_pairs(length, window=None):
    """(query, key) pairs of one sequence of `length` rows: the causal triangle, or
    with a window the band (row i attends min(i + 1, window) keys: the rectangle
    length x window less its corner)."""
    if window is None or window >= length:
        return length * (length + 1) // 2
    return length * window - window * (window - 1) // 2


def attention_prefill_flops(cfg, length):
    """Scores and contexts of one prompt of `length` rows in every layer: 4 operations
    a pair a value of a head (q . k and p v, a multiply and an add each), every query
    head; the triangle in full layers, the band in sliding ones."""
    per_pair = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
    return per_pair * (
        layers_of(cfg, "full_attention") * attended_pairs(length)
        + layers_of(cfg, "sliding_attention") * attended_pairs(length, cfg["sliding_window"]))


def decode_rows_bytes(cfg, rows):
    """Bytes the decode steps' attention has to read for `rows` attended rows (a row:
    one position of one layer, every KV head's K and V)."""
    return rows * cache_row_bytes(cfg)
