"""Decoder-only GPT language model (beyond the Fluid-era reference, which
predates GPT-style LMs — built to exercise the causal flash-attention and
long-context paths at model scale; architecture per GPT-2: pre-LN blocks,
learned positions, tied LM head).

TPU-first choices mirror models/bert.py: (b, s, n, d) layout with separate
q/k/v projections (no relayout traffic), causal attention through the
fused_attention op (flash kernel at s>=256, masked-einsum reference below —
the same shape dispatch), next-token loss computed in-graph over shifted
slices."""

from __future__ import annotations

import math

import paddle_tpu as pt
from ..framework.layer_helper import ParamAttr
from ._common import attr as _attr, check_max_pos, ffn as _shared_ffn, \
    layer_norm as _ln

__all__ = ["GPTConfig", "gpt_lm_program", "flops_per_step", "tp_shardings"]


class GPTConfig:
    def __init__(self, vocab_size=50257, hidden=768, layers=12, heads=12,
                 ffn=None, max_pos=1024, dropout=0.1, init_range=0.02,
                 attn_impl="fused", cp_axis="", seq_parallel="ring"):
        self.vocab_size = vocab_size
        self.hidden = hidden
        self.layers = layers
        self.heads = heads
        self.ffn = ffn if ffn is not None else 4 * hidden
        self.max_pos = max_pos
        self.dropout = dropout
        self.init_range = init_range
        self.attn_impl = attn_impl
        self.cp_axis = cp_axis
        self.seq_parallel = seq_parallel

    def serving_model(self):
        """What serving.ServingEngine serves this config through."""
        from .gpt_decode import GPT_SERVING_MODEL
        return GPT_SERVING_MODEL


def _causal_attention(x, cfg: GPTConfig, prefix: str, seq: int):
    h, nh = cfg.hidden, cfg.heads
    hd = h // nh

    def proj(name):
        p = pt.layers.fc(x, h, num_flatten_dims=2,
                         param_attr=_attr(f"{prefix}/{name}.w", cfg),
                         bias_attr=ParamAttr(name=f"{prefix}/{name}.b"))
        return pt.layers.reshape(p, [0, seq, nh, hd])

    q, k, v = proj("q"), proj("k"), proj("v")
    ctx = pt.layers.fused_attention(
        q, k, v, causal=True, sm_scale=1.0 / math.sqrt(hd),
        impl=cfg.attn_impl if cfg.attn_impl != "fused" else "",
        cp_axis=cfg.cp_axis, seq_parallel=cfg.seq_parallel)
    ctx = pt.layers.reshape(ctx, [0, seq, h])
    return pt.layers.fc(ctx, h, num_flatten_dims=2,
                        param_attr=_attr(f"{prefix}/out.w", cfg),
                        bias_attr=ParamAttr(name=f"{prefix}/out.b"))


def _mlp(x, cfg: GPTConfig, prefix: str):
    return _shared_ffn(x, cfg, prefix, names=("mlp1", "mlp2"))


def gpt_decoder(tokens, cfg: GPTConfig, is_test=False, prefix="gpt",
                cut_vars=None):
    """tokens: int64 (-1, seq) -> hidden states (-1, seq, h), pre-LN
    residual stack with a final LN (GPT-2). cut_vars (list) collects the
    per-layer residual var names — recompute/pipeline boundaries.

    Every op is built under a `pt.name_scope` that names its stage in a
    device trace, as the serving programs of models/gpt_decode.py name
    theirs: `embed`, `norm`, `attn` (projections, fused_attention and the
    residual add), `ffn`, and `head` for the final norm (gpt_lm_program
    puts the product over the tied table there too)."""
    seq = int(tokens.shape[1])
    check_max_pos(seq, cfg)
    with pt.name_scope("embed"):
        wte = pt.layers.embedding(
            tokens, size=[cfg.vocab_size, cfg.hidden],
            param_attr=_attr(f"{prefix}/wte", cfg))
        pos_ids = pt.layers.arange(0, seq, dtype="int64")
        wpe = pt.layers.embedding(
            pos_ids, size=[cfg.max_pos, cfg.hidden],
            param_attr=_attr(f"{prefix}/wpe", cfg))
        x = wte + wpe
        if cfg.dropout > 0:
            x = pt.layers.dropout(
                x, cfg.dropout, is_test=is_test,
                dropout_implementation="upscale_in_train")
    def _resid_drop(t):
        # GPT-2 resid_pdrop on every sublayer output; attn-prob dropout
        # stays absent on the fused path (standard for flash kernels,
        # same documented limitation as models/bert.py attn_impl="fused")
        if cfg.dropout > 0 and not is_test:
            return pt.layers.dropout(
                t, cfg.dropout, is_test=is_test,
                dropout_implementation="upscale_in_train")
        return t

    for i in range(cfg.layers):
        p = f"{prefix}/l{i}"
        with pt.name_scope("norm"):
            h = _ln(x, f"{p}/ln1")
        with pt.name_scope("attn"):
            x = x + _resid_drop(_causal_attention(h, cfg, p, seq))
        with pt.name_scope("norm"):
            h = _ln(x, f"{p}/ln2")
        with pt.name_scope("ffn"):
            x = x + _resid_drop(_mlp(h, cfg, p))
        if cut_vars is not None:
            cut_vars.append(x.name)
    with pt.name_scope("head"):
        return _ln(x, f"{prefix}/lnf")


def gpt_lm_program(cfg: GPTConfig, seq_len: int, is_test=False,
                   learning_rate=1e-4, optimizer="adam", amp=False,
                   recompute=False):
    """(main, startup, fetches) for a causal-LM step: next-token CE with
    the tied wte head, loss over positions 0..seq-2 predicting 1..seq-1.
    The shift happens where a tensor is one column wide: the loss op sees
    the head's (b, seq, V) logits as they lie, against the tokens rolled
    left by one (the last position meets token 0, a valid id whose loss
    nobody reads), and the per-position LOSS is cut to seq - 1 before the
    mean: the same sum over the same b x (seq - 1) positions, and a
    gradient of exactly zero on the last position's logits. XLA folded
    the older slice of the logits into the head's product and its pad
    into the backward products, so no copy is saved; what the pad did
    was stand between the loss gradient's expression and those products.
    Without it the gradient is no buffer at all: the two backward
    products read the logits and rebuild it in their operands (PERF.md
    section 6, PR 51: 2.5 ms of a 66.8 ms step for 1.1).
    The shift is a slice of the (b, seq, V) logits in the IR and no copy
    on the device: XLA computes the head's product for the seq - 1 rows
    the loss reads and folds the gradient's pad into the head's backward
    products (seen in the step compiled for a v5e and in the cells'
    traces, PR 51), so the loss op meets (b, seq - 1, V) logits against
    tokens[:, 1:] and the last position's logits get a gradient of zero.
    recompute=True checkpoints the per-layer residuals and remats the
    segments in the backward (transpiler/recompute.py)."""
    main, startup = pt.Program(), pt.Program()
    cuts = [] if recompute else None
    with pt.program_guard(main, startup):
        tokens = pt.layers.data("tokens", [seq_len], dtype="int64")
        h = gpt_decoder(tokens, cfg, is_test=is_test, cut_vars=cuts)
        wte = main.global_block.var("gpt/wte")
        with pt.name_scope("head"):
            logits = pt.layers.matmul(h, wte, transpose_y=True)
        with pt.name_scope("loss"):
            # shift: logits[:, t] predicts tokens[:, t + 1]; the last
            # position's loss is dropped, not its vocabulary-wide logits
            labels = pt.layers.roll(tokens, -1, 1)
            labels = pt.layers.reshape(labels, [0, seq_len, 1])
            loss = pt.layers.softmax_with_cross_entropy(logits, labels)
            loss = pt.layers.slice(loss, [1], [0], [seq_len - 1])
            mean_loss = pt.layers.mean(loss)

        if optimizer == "adam":
            opt = pt.optimizer.Adam(learning_rate)
        elif optimizer == "lamb":
            opt = pt.optimizer.Lamb(learning_rate)
        else:
            opt = pt.optimizer.SGD(learning_rate)
        if amp:
            from ..contrib import mixed_precision
            opt = mixed_precision.decorate(opt)
        if not is_test:
            opt.minimize(mean_loss)
    if cuts is not None:
        main._recompute_checkpoints = list(cuts)
        if not is_test:
            from ..transpiler.recompute import apply_recompute
            apply_recompute(main, cuts)
    return main, startup, {"loss": mean_loss, "logits": logits}


def flops_per_step(cfg: GPTConfig, batch: int, seq: int) -> float:
    """Standard 6*N*tokens + attention-score terms (train = fwd + 2x bwd)."""
    h, L, ffn, v = cfg.hidden, cfg.layers, cfg.ffn, cfg.vocab_size
    per_tok = L * (4 * h * h + 2 * h * ffn) * 2   # qkvo + mlp matmuls, fwd
    attn = L * 2 * 2 * h * seq                    # scores + ctx per token
    head = 2 * h * v
    fwd = batch * seq * (per_tok + attn + head)
    return 3.0 * fwd


def tp_shardings(cfg: GPTConfig, prefix="gpt"):
    """Megatron-style tensor-parallel param shardings over the 'mp' axis
    (column-parallel q/k/v + mlp1, row-parallel out + mlp2)."""
    sh = {f"{prefix}/wte": ("mp", None)}
    for i in range(cfg.layers):
        p = f"{prefix}/l{i}"
        for nm in ("q", "k", "v"):
            sh[f"{p}/{nm}.w"] = (None, "mp")
            sh[f"{p}/{nm}.b"] = ("mp",)
        sh[f"{p}/out.w"] = ("mp", None)
        sh[f"{p}/mlp1.w"] = (None, "mp")
        sh[f"{p}/mlp1.b"] = ("mp",)
        sh[f"{p}/mlp2.w"] = ("mp", None)
    return sh
