"""Operations and bytes a GPT-2 step needs, from its shapes. `cfg` is the
configuration file's dict (Hugging Face key names). Recomputed and padded work
is not counted: these are what the algorithm needs, not what a kernel does."""


def widths(cfg):
    h, layers = cfg["n_embd"], cfg["n_layer"]
    inner = cfg.get("n_inner") or 4 * h
    return h, layers, inner, cfg["vocab_size"]


def matmul_params(cfg):
    """Parameters that take part in a matrix product for every token: the four
    attention projections and the two feed-forward matrices of each layer, and
    the tied output head. Position and token look-ups are not products."""
    h, layers, inner, vocab = widths(cfg)
    return layers * (4 * h * h + 2 * h * inner) + h * vocab


def train_flops_per_token(cfg, seq):
    """6 N (forward and backward of every product) plus causal attention: a
    token at position t scores t keys and mixes t values, 4 h t operations a
    layer forward, so 2 h seq on average over a sequence, three times that with
    the backward pass."""
    h, layers, _, _ = widths(cfg)
    return 6.0 * matmul_params(cfg) + 3.0 * layers * 2.0 * h * seq


def prefill_flops(cfg, prompt_len):
    """Forward pass over a prompt of `prompt_len` real tokens, the head on its
    last position only (the engine samples one token from a prefill)."""
    h, layers, inner, vocab = widths(cfg)
    body = layers * (4 * h * h + 2 * h * inner)
    return (2.0 * body * prompt_len + 2.0 * h * vocab
            + layers * 2.0 * h * prompt_len * prompt_len)


def kv_bytes_per_token(cfg, bytes_per_value=2):
    h, layers, _, _ = widths(cfg)
    return 2 * layers * h * bytes_per_value


def weight_bytes(cfg, bytes_per_value=2):
    return matmul_params(cfg) * bytes_per_value


def decode_step_bytes(cfg, live_positions):
    """Bytes one decode step has to read: every weight once (bf16) and the
    cached keys and values of the positions that are live in the batch."""
    return weight_bytes(cfg) + kv_bytes_per_token(cfg) * live_positions
