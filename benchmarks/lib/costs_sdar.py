"""Operations and bytes SDAR-30B-A3B-Chat's layers need, from their shapes. `cfg` is the
configuration file's dict (the published `sdar_moe` keys; the block length under
`assumed.generation`). What the algorithm needs, not what a kernel does: padded rows of a
bucket, a tile's rows that are nobody's and recomputation are not counted. Weights and
cache rows are bfloat16."""

BYTES = 2


def block_length(cfg):
    return cfg["assumed"]["generation"]["block_length"]


def expert_params(cfg):
    """One routed expert: gate, up and down of a SwiGLU."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg):
    return cfg["hidden_size"] * cfg["num_experts"]


def attention_params(cfg):
    """W_q, W_k, W_v, W_o."""
    d, h = cfg["head_dim"], cfg["hidden_size"]
    n, n_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h * (n + 2 * n_kv) * d + n * d * h


def weight_bytes(cfg):
    """Every matrix (norm vectors left out): the layers, the embedding and the head."""
    layer = attention_params(cfg) + router_params(cfg) + cfg["num_experts"] * expert_params(cfg)
    return BYTES * (2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["num_hidden_layers"] * layer)


def cache_row_bytes(cfg):
    """One token's K and V in one layer, every KV head."""
    return BYTES * cfg["num_key_value_heads"] * 2 * cfg["head_dim"]


def moe_decode_bytes(cfg, experts_touched, passes):
    """Bytes the expert layers of block passes have to read: each expert that had a row,
    once for each pass of a layer in which it had one, and the router once such a pass."""
    return BYTES * (experts_touched * expert_params(cfg) + passes * router_params(cfg))


def moe_flops(cfg, tokens):
    """The expert layers' products of `tokens` tokens through every layer, 2 operations a
    parameter: the router and `num_experts_per_tok` experts a token a layer."""
    per_token = router_params(cfg) + cfg["num_experts_per_tok"] * expert_params(cfg)
    return 2.0 * cfg["num_hidden_layers"] * per_token * tokens


def whole_blocks(cfg, length):
    """The rows of a prompt that its prefill attends and writes: its whole blocks."""
    B = block_length(cfg)
    return length // B * B


def attended_pairs(length, block):
    """(query, key) pairs of `length` rows (a multiple of `block`) under the block-causal
    mask: row i attends the (i // block + 1) * block rows through its own block's end."""
    blocks = length // block
    return block * block * blocks * (blocks + 1) // 2


def attention_prefill_flops(cfg, length):
    """Scores and contexts of one prompt of `length` tokens in every layer: 4 operations a
    pair a value of a head (q . k and p v, a multiply and an add each), every query head,
    over the block-causal triangle of its whole blocks."""
    per_pair = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
    return per_pair * cfg["num_hidden_layers"] * attended_pairs(
        whole_blocks(cfg, length), block_length(cfg))


def decode_rows_bytes(cfg, rows):
    """Bytes the block passes' attention has to read for `rows` attended rows (a row: one
    position of one layer, every KV head's K and V; a pass's B queries share it)."""
    return rows * cache_row_bytes(cfg)
