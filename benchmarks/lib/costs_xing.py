"""Operations and bytes of what Xing4.0-29B-A4B adds to the latent-attention +
expert block, from its shapes: the attention's parameters with the query's
low-rank pair, every matrix of the configuration as it is cut, and the residual
mixer's least traffic. `cfg` is the configuration file's dict (the published
keys). What the algorithm needs, not what a program does. The experts', the
router's and the latent rows' counts are `costs_moonlight`'s: they read the
same keys."""

from . import costs_moonlight as moonlight

BYTES = 2


def attention_params(cfg):
    """W_qa, W_qb, W_kva, W_kvb, W_o."""
    n, h = cfg["num_attention_heads"], cfg["hidden_size"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (h * cfg["q_lora_rank"] + cfg["q_lora_rank"] * n * qk
            + h * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * n * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + n * cfg["v_head_dim"] * h)


def mixer_params(cfg):
    """One sublayer's `phi`: n x C rows onto H_pre (n), H_post (n), H_res (n x n)."""
    n = cfg["hc_mult"]
    return n * cfg["hidden_size"] * (2 * n + n * n)


def weight_bytes(cfg):
    """Every matrix of the configuration as it is cut (norm vectors, the mixers'
    biases and gates left out)."""
    dense = 3 * cfg["hidden_size"] * cfg["intermediate_size"]
    moe = (cfg["n_routed_experts"] * moonlight.expert_params(cfg)
           + moonlight.shared_params(cfg) + moonlight.router_params(cfg))
    return BYTES * (2 * cfg["vocab_size"] * cfg["hidden_size"]
                    + cfg["num_hidden_layers"] * (attention_params(cfg) + 2 * mixer_params(cfg))
                    + cfg["first_k_dense_replace"] * dense
                    + moonlight.expert_layers(cfg) * moe)


def sublayers(cfg):
    return 2 * cfg["num_hidden_layers"]


def mixer_bytes(cfg, tokens):
    """Bytes the mixers of `tokens` tokens have to move through every sublayer,
    the streams bfloat16: X (n x C) read once for the coefficients and the
    sublayer's input, then X and the sublayer's output (C) read and X' written."""
    n, h = cfg["hc_mult"], cfg["hidden_size"]
    return BYTES * (n * h + (n * h + h) + n * h) * sublayers(cfg) * tokens


def mixer_flops(cfg, tokens):
    """The projection onto `phi`: 2 operations a parameter a token a sublayer."""
    return 2.0 * mixer_params(cfg) * sublayers(cfg) * tokens
