"""NN ops: conv, pool, normalization, dropout, losses, metrics.

Reference: paddle/fluid/operators/ conv_op.cc + conv_cudnn_op.cu.cc,
pool_op.cc, batch_norm_op.cc, layer_norm_op.cc, dropout_op.cc,
softmax_with_cross_entropy_op.cc, cross_entropy_op.cc. Convs map onto
lax.conv_general_dilated (MXU); normalizations are jnp reductions that XLA
fuses; dropout carries an explicit Mask output so its gradient is exact
(custom grad rule — the one place the generic vjp path can't be used because
of RNG).
"""

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.registry import register_op, get_op_def


# ---------------------------------------------------------------------------
# convolution (reference: conv_op.cc; cudnn variant conv_cudnn_op.cu.cc)
# ---------------------------------------------------------------------------

def _conv_padding(paddings, algo, ksize, dilations):
    if algo == "SAME":
        return "SAME"
    if algo == "VALID":
        return "VALID"
    if len(paddings) == 2:
        return [(paddings[0], paddings[0]), (paddings[1], paddings[1])]
    return [(paddings[0], paddings[1]), (paddings[2], paddings[3])]


@register_op("conv2d")
def _conv2d(ctx, ins, attrs):
    """Filter params are ALWAYS stored OIHW (layout-independent
    checkpoints); with data_format NHWC — the layout the TPU's conv
    engine prefers, no relayout copies around each conv — the filter
    transposes to HWIO at trace time (free: folded into the conv)."""
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = tuple(attrs.get("strides", [1, 1]))
    dil = tuple(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", 1)
    fmt = attrs.get("data_format", "NCHW")
    pad = _conv_padding(attrs.get("paddings", [0, 0]),
                        attrs.get("padding_algorithm", "EXPLICIT"),
                        w.shape[2:], dil)
    if fmt == "NHWC":
        dn = ("NHWC", "HWIO", "NHWC")
        w = jnp.transpose(w, (2, 3, 1, 0))
    else:
        dn = ("NCHW", "OIHW", "NCHW")
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=strides, padding=pad, rhs_dilation=dil,
        feature_group_count=groups,
        dimension_numbers=dn,
        preferred_element_type=jnp.float32 if x.dtype == jnp.float32 else None)
    return {"Output": [out.astype(x.dtype)]}


@register_op("depthwise_conv2d")
def _depthwise_conv2d(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    attrs = dict(attrs)
    attrs["groups"] = x.shape[
        3 if attrs.get("data_format", "NCHW") == "NHWC" else 1]
    return _conv2d(ctx, {"Input": [x], "Filter": [w]}, attrs)


@register_op("conv2d_transpose")
def _conv2d_transpose(ctx, ins, attrs):
    """reference: conv_transpose_op.cc. Filter layout [C_in, C_out/g, kh, kw];
    implemented as the gradient-of-conv: input-dilated conv with a flipped,
    IO-swapped kernel."""
    x, w = ins["Input"][0], ins["Filter"][0]
    s = tuple(attrs.get("strides", [1, 1]))
    p = attrs.get("paddings", [0, 0])
    dil = tuple(attrs.get("dilations", [1, 1]))
    groups = int(attrs.get("groups", 1))
    kh, kw = w.shape[2], w.shape[3]
    wf = jnp.flip(w, axis=(2, 3))                        # [C_in, C_out/g,...]
    if groups == 1:
        wf = wf.transpose(1, 0, 2, 3)                    # -> OIHW
    else:
        # per-group IO swap: [g, C_in/g, C_out/g, kh, kw] -> concat over
        # groups of [C_out/g, C_in/g, kh, kw] gives OIHW with
        # O = C_out (group-major), I = C_in/g — the layout
        # feature_group_count expects
        cin = wf.shape[0]
        wg = wf.reshape(groups, cin // groups, *wf.shape[1:])
        wf = wg.transpose(0, 2, 1, 3, 4).reshape(
            groups * wf.shape[1], cin // groups, kh, kw)
    eh = dil[0] * (kh - 1)
    ew = dil[1] * (kw - 1)
    pad = [(eh - p[0], eh - p[0]), (ew - p[1], ew - p[1])]
    out = jax.lax.conv_general_dilated(
        x, wf, window_strides=(1, 1), padding=pad, lhs_dilation=s,
        rhs_dilation=dil, feature_group_count=groups,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return {"Output": [out]}


@register_op("conv3d")
def _conv3d(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = tuple(attrs.get("strides", [1, 1, 1]))
    dil = tuple(attrs.get("dilations", [1, 1, 1]))
    p = attrs.get("paddings", [0, 0, 0])
    pad = [(pi, pi) for pi in p] if len(p) == 3 else \
        [(p[0], p[1]), (p[2], p[3]), (p[4], p[5])]
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=strides, padding=pad, rhs_dilation=dil,
        feature_group_count=attrs.get("groups", 1),
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"))
    return {"Output": [out]}


# ---------------------------------------------------------------------------
# pooling (reference: pool_op.cc)
# ---------------------------------------------------------------------------

@register_op("pool2d")
def _pool2d(ctx, ins, attrs):
    x = ins["X"][0]
    ptype = attrs.get("pooling_type", "max")
    fmt = attrs.get("data_format", "NCHW")
    sp_axes = (1, 2) if fmt == "NHWC" else (2, 3)
    if attrs.get("global_pooling", False) or attrs.get("adaptive", False) \
            and tuple(attrs.get("ksize")) == (1, 1):
        if ptype == "max":
            out = jnp.max(x, axis=sp_axes, keepdims=True)
        else:
            out = jnp.mean(x, axis=sp_axes, keepdims=True)
        return {"Out": [out]}
    if attrs.get("adaptive", False):
        if fmt == "NHWC":
            xt = jnp.transpose(x, (0, 3, 1, 2))
            out = _adaptive_pool2d(ctx, {"X": [xt]},
                                   {"pooling_size": attrs["ksize"],
                                    "pooling_type": ptype})["Out"][0]
            return {"Out": [jnp.transpose(out, (0, 2, 3, 1))]}
        return _adaptive_pool2d(ctx, {"X": [x]},
                                {"pooling_size": attrs["ksize"],
                                 "pooling_type": ptype})
    ksize = tuple(attrs["ksize"])
    strides = tuple(attrs.get("strides", ksize))
    p = attrs.get("paddings", [0, 0])

    def _mk4(hpair, wpair):
        if fmt == "NHWC":
            return [(0, 0), hpair, wpair, (0, 0)]
        return [(0, 0), (0, 0), hpair, wpair]

    pads = _mk4((p[0], p[0]), (p[1], p[1]))
    sp_dims = (x.shape[1], x.shape[2]) if fmt == "NHWC" \
        else (x.shape[2], x.shape[3])
    if attrs.get("ceil_mode", False):
        extra = []
        for i, (dim, k, s, pp) in enumerate(
                zip(sp_dims, ksize, strides, p)):
            rem = (dim + 2 * pp - k) % s
            extra.append((s - rem) % s if rem else 0)
        pads = _mk4((p[0], p[0] + extra[0]), (p[1], p[1] + extra[1]))
    if fmt == "NHWC":
        window = (1,) + ksize + (1,)
        strides4 = (1,) + strides + (1,)
    else:
        window = (1, 1) + ksize
        strides4 = (1, 1) + strides
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else \
            jnp.iinfo(x.dtype).min
        out = jax.lax.reduce_window(x, init, jax.lax.max, window, strides4,
                                    pads)
    else:
        ssum = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides4,
                                     pads)
        if attrs.get("exclusive", True):
            ones = jnp.ones(x.shape, x.dtype)
            cnt = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window,
                                        strides4, pads)
            out = ssum / cnt
        else:
            out = ssum / float(np.prod(ksize))
    return {"Out": [out]}


@register_op("adaptive_pool2d")
def _adaptive_pool2d(ctx, ins, attrs):
    """reference: pool_op.cc adaptive=True — output bin i covers input
    range [floor(i*H/oh), ceil((i+1)*H/oh)). Divisible sizes reduce to a
    reshape; otherwise avg pools through two small (static) membership
    matmuls and max through per-bin slice maxima (bins are trace-time
    constants, so XLA sees a fixed fused graph either way)."""
    x = ins["X"][0]
    oh, ow = (int(d) for d in attrs["pooling_size"])
    n, c, h, w = x.shape
    ptype = attrs.get("pooling_type", "avg")
    if h % oh == 0 and w % ow == 0:
        xr = x.reshape(n, c, oh, h // oh, ow, w // ow)
        if ptype == "max":
            out = jnp.max(xr, axis=(3, 5))
        else:
            out = jnp.mean(xr, axis=(3, 5))
        return {"Out": [out]}

    def bins(in_dim, out_dim):
        lo = [(i * in_dim) // out_dim for i in range(out_dim)]
        hi = [-(-((i + 1) * in_dim) // out_dim) for i in range(out_dim)]
        return lo, hi

    hlo, hhi = bins(h, oh)
    wlo, whi = bins(w, ow)
    if ptype == "max":
        rows = [jnp.max(x[:, :, a:bq], axis=2) for a, bq in zip(hlo, hhi)]
        xh = jnp.stack(rows, axis=2)                     # [n, c, oh, w]
        cols = [jnp.max(xh[:, :, :, a:bq], axis=3)
                for a, bq in zip(wlo, whi)]
        return {"Out": [jnp.stack(cols, axis=3)]}
    mh = np.zeros((oh, h), np.float32)
    for i, (a, bq) in enumerate(zip(hlo, hhi)):
        mh[i, a:bq] = 1.0 / (bq - a)
    mw = np.zeros((ow, w), np.float32)
    for i, (a, bq) in enumerate(zip(wlo, whi)):
        mw[i, a:bq] = 1.0 / (bq - a)
    out = jnp.einsum("oh,nchw,pw->ncop", jnp.asarray(mh, x.dtype), x,
                     jnp.asarray(mw, x.dtype))
    return {"Out": [out]}


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

@register_op("batch_norm",
             non_diff_outputs={"MeanOut", "VarianceOut", "SavedMean",
                               "SavedVariance"},
             no_grad_inputs={"Mean", "Variance"})
def _batch_norm(ctx, ins, attrs):
    """reference: batch_norm_op.cc. Train mode normalizes with batch stats
    and emits updated running stats (MeanOut/VarianceOut alias the Mean/
    Variance persistables in the IR, like the reference's in-place outputs)."""
    x = ins["X"][0]
    scale, bias = ins["Scale"][0], ins["Bias"][0]
    mean, var = ins["Mean"][0], ins["Variance"][0]
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    layout = attrs.get("data_layout", "NCHW")
    if x.ndim == 2:
        axes, shape = (0,), (1, -1)
    elif layout == "NCHW":
        axes, shape = (0, 2, 3), (1, -1, 1, 1)
    else:
        axes, shape = (0, 1, 2), (1, 1, 1, -1)

    # stats in float32 even for bf16 activations (AMP-safe, like
    # layer_norm below) — this is what lets batch_norm sit on the AMP
    # white list so conv+bn chains stay bf16 end to end
    x32 = x.astype(jnp.float32)
    if attrs.get("is_test", False) or attrs.get("use_global_stats", False):
        use_mean, use_var = mean, var
        mean_out, var_out = mean, var
        saved_mean = jnp.zeros_like(mean)
        saved_var = jnp.zeros_like(var)
    else:
        use_mean = jnp.mean(x32, axis=axes)
        use_var = jnp.var(x32, axis=axes)
        mean_out = momentum * mean + (1.0 - momentum) * use_mean
        var_out = momentum * var + (1.0 - momentum) * use_var
        saved_mean = use_mean
        saved_var = 1.0 / jnp.sqrt(use_var + eps)

    inv = 1.0 / jnp.sqrt(use_var + eps)
    y = (x32 - use_mean.reshape(shape)) * (inv * scale).reshape(shape) \
        + bias.reshape(shape)
    return {"Y": [y.astype(x.dtype)], "MeanOut": [mean_out],
            "VarianceOut": [var_out],
            "SavedMean": [saved_mean], "SavedVariance": [saved_var]}


@register_op("layer_norm", non_diff_outputs={"Mean", "Variance"})
def _layer_norm(ctx, ins, attrs):
    """reference: layer_norm_op.cc; normalizes over dims >= begin_norm_axis.
    Stats are computed in f32 even for bf16 activations (AMP-safe)."""
    x = ins["X"][0]
    eps = attrs.get("epsilon", 1e-5)
    axis = attrs.get("begin_norm_axis", 1)
    axes = tuple(range(axis, x.ndim))
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=axes, keepdims=True)
    var = jnp.var(x32, axis=axes, keepdims=True)
    y = ((x32 - mean) / jnp.sqrt(var + eps))
    nshape = (1,) * axis + x.shape[axis:]
    if "Scale" in ins:
        y = y * ins["Scale"][0].astype(jnp.float32).reshape(nshape)
    if "Bias" in ins:
        y = y + ins["Bias"][0].astype(jnp.float32).reshape(nshape)
    return {"Y": [y.astype(x.dtype)], "Mean": [jnp.squeeze(mean)],
            "Variance": [jnp.squeeze(var)]}


@register_op("group_norm", non_diff_outputs={"Mean", "Variance"})
def _group_norm(ctx, ins, attrs):
    x = ins["X"][0]
    g = attrs["groups"]
    eps = attrs.get("epsilon", 1e-5)
    n, c, h, w = x.shape
    xr = x.reshape(n, g, c // g, h, w)
    mean = jnp.mean(xr, axis=(2, 3, 4), keepdims=True)
    var = jnp.var(xr, axis=(2, 3, 4), keepdims=True)
    y = ((xr - mean) / jnp.sqrt(var + eps)).reshape(n, c, h, w)
    if "Scale" in ins:
        y = y * ins["Scale"][0].reshape(1, -1, 1, 1)
    if "Bias" in ins:
        y = y + ins["Bias"][0].reshape(1, -1, 1, 1)
    return {"Y": [y], "Mean": [mean.reshape(n, g)],
            "Variance": [var.reshape(n, g)]}


@register_op("instance_norm", non_diff_outputs={"SavedMean", "SavedVariance"})
def _instance_norm(ctx, ins, attrs):
    x = ins["X"][0]
    eps = attrs.get("epsilon", 1e-5)
    mean = jnp.mean(x, axis=(2, 3), keepdims=True)
    var = jnp.var(x, axis=(2, 3), keepdims=True)
    y = (x - mean) / jnp.sqrt(var + eps)
    if "Scale" in ins:
        y = y * ins["Scale"][0].reshape(1, -1, 1, 1)
    if "Bias" in ins:
        y = y + ins["Bias"][0].reshape(1, -1, 1, 1)
    return {"Y": [y], "SavedMean": [mean.reshape(x.shape[:2])],
            "SavedVariance": [var.reshape(x.shape[:2])]}


@register_op("lrn", non_diff_outputs={"MidOut"})
def _lrn(ctx, ins, attrs):
    x = ins["X"][0]
    n = attrs.get("n", 5)
    k = attrs.get("k", 2.0)
    alpha = attrs.get("alpha", 1e-4)
    beta = attrs.get("beta", 0.75)
    sq = jnp.square(x)
    pad = n // 2
    sqp = jnp.pad(sq, [(0, 0), (pad, n - 1 - pad), (0, 0), (0, 0)])
    acc = sum(sqp[:, i:i + x.shape[1]] for i in range(n))
    mid = k + alpha * acc
    return {"Out": [x / mid ** beta], "MidOut": [mid]}


@register_op("prelu")
def _prelu(ctx, ins, attrs):
    x, alpha = ins["X"][0], ins["Alpha"][0]
    mode = attrs.get("mode", "all")
    if mode == "channel":
        alpha = alpha.reshape(1, -1, *([1] * (x.ndim - 2)))
    return {"Out": [jnp.where(x > 0, x, alpha * x)]}


# ---------------------------------------------------------------------------
# dropout — custom grad (RNG mask must match between fwd and bwd)
# ---------------------------------------------------------------------------

def _dropout_grad_maker(op, block, no_grad_set):
    from ..framework.core import grad_var_name
    return [{
        "type": "dropout_grad",
        "inputs": {"Mask": op.output("Mask"),
                   "Out@GRAD": [grad_var_name(op.output("Out")[0])]},
        "outputs": {"X@GRAD": [grad_var_name(op.input("X")[0])]},
        "attrs": dict(op.attrs),
    }]


def _dropout_grad_lower(ctx, ins, attrs):
    mask = ins["Mask"][0]
    dout = ins["Out@GRAD"][0]
    p = attrs.get("dropout_prob", 0.5)
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    if attrs.get("is_test", False):
        g = dout if impl == "upscale_in_train" else dout * (1.0 - p)
    elif impl == "upscale_in_train":
        scale = 0.0 if p >= 1.0 else 1.0 / (1.0 - p)
        g = dout * mask.astype(dout.dtype) * scale
    else:
        g = dout * mask.astype(dout.dtype)
    return {"X@GRAD": [g]}


@register_op("dropout_mask_apply", not_differentiable=True, grad_free=True)
def _dropout_mask_apply(ctx, ins, attrs):
    """Recompute-region replay of a dropout whose Mask was saved: same
    math as the dropout forward, but with the GIVEN mask — recompute must
    never re-draw RNG (transpiler/recompute.py). Inserted after backward
    construction, so it needs no gradient."""
    x, mask = ins["X"][0], ins["Mask"][0]
    p = attrs.get("dropout_prob", 0.5)
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    if attrs.get("is_test", False):  # frozen dropout replays as identity
        out = x if impl == "upscale_in_train" else x * (1.0 - p)
    elif impl == "upscale_in_train":
        scale = 0.0 if p >= 1.0 else 1.0 / (1.0 - p)
        out = x * mask.astype(x.dtype) * scale
    else:
        out = x * mask.astype(x.dtype)
    return {"Out": [out]}


@register_op("dropout", stateful=True, non_diff_outputs={"Mask"},
             grad_maker=_dropout_grad_maker, grad_lower=_dropout_grad_lower)
def _dropout(ctx, ins, attrs):
    """reference: dropout_op.cc. Mask is a real output (uint8), as in the
    reference, so the grad op replays the same mask."""
    x = ins["X"][0]
    p = attrs.get("dropout_prob", 0.5)
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    if attrs.get("is_test", False):
        out = x if impl == "upscale_in_train" else x * (1.0 - p)
        return {"Out": [out], "Mask": [jnp.ones(x.shape, jnp.uint8)]}
    keep = jax.random.bernoulli(ctx.rng(), 1.0 - p, x.shape)
    if impl == "upscale_in_train":
        scale = 0.0 if p >= 1.0 else 1.0 / (1.0 - p)
        out = x * keep.astype(x.dtype) * scale
    else:
        out = x * keep.astype(x.dtype)
    return {"Out": [out], "Mask": [keep.astype(jnp.uint8)]}


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def _squeeze_label(label):
    if label.ndim > 1 and label.shape[-1] == 1:
        return jnp.squeeze(label, -1)
    return label


def _softmax_xent_grad_maker(op, block, no_grad_set):
    """The grad op reads the forward's INPUT logits and the saved row
    log-sum-exp (`Lse`), never the `Softmax` output. A forward op built
    without an `Lse` output (by hand, or a program saved before PR 51)
    keeps the older desc: the saved `Softmax` and no logits."""
    from ..framework.core import grad_var_name
    softmax = op.output("Softmax")
    ins = {"Label": op.input("Label"),
           "Loss@GRAD": [grad_var_name(op.output("Loss")[0])]}
    attrs = dict(op.attrs)
    if op.output("Lse"):
        ins.update(Logits=op.input("Logits"), Lse=op.output("Lse"))
        # another forward op reading Softmax keeps its write alive; the
        # gradient still rebuilds the probabilities, the counter says so
        attrs["softmax_read"] = any(
            set(softmax) & set(other.input_names())
            for other in block.ops if other is not op)
    else:
        ins["Softmax"] = softmax
    if softmax:
        # present only when an aux loss consumed the Softmax output
        # (entropy penalty, distillation) — the accum resolves it to ""
        # otherwise and grad_lower skips it
        ins["Softmax@GRAD"] = [grad_var_name(softmax[0])]
    return [{
        "type": "softmax_with_cross_entropy_grad",
        "inputs": ins,
        "outputs": {"Logits@GRAD": [grad_var_name(op.input("Logits")[0])]},
        "attrs": attrs,
    }]


_XENT_LOWERINGS = {
    False: ("loss_lse_lowerings_total",
            "softmax_with_cross_entropy gradients lowered from the logits "
            "and the saved row log-sum-exp, with nothing but the op's "
            "contract asking for Softmax"),
    True: ("loss_softmax_kept_lowerings_total",
           "softmax_with_cross_entropy gradients lowered in a program that "
           "keeps a vocabulary-wide softmax: another op reads it, "
           "Softmax@GRAD arrives, or the forward op saved no log-sum-exp"),
}


def _softmax_xent_grad_lower(ctx, ins, attrs):
    """d_logits = (softmax - onehot(label)) * d_loss, with the softmax
    REBUILT as exp(logits - Lse) in float32 from the logits the forward
    read (under AMP the head's bfloat16 output, live anyway for the head's
    own gradient) and the row's saved float32 log-sum-exp, and rounded once
    to the logits' type.

    What stands between the forward and the backward is a scalar a row and
    nothing vocabulary-wide, as fused_attention saves `Lse` and not the
    probabilities (ops/attention_ops.py). The older design (the reference
    grad kernel's, softmax_with_cross_entropy_op.h) handed this op the
    saved Softmax and took the label's log-probability out of
    log_softmax's float32 result. At GPT-2's vocabulary XLA recomputed the
    former but WROTE the latter: 8 x 1,023 x 50,257 float32 = 1.65 GB a
    step beside the 0.82 GB of gradient, 5.1 ms of a 64.1 ms step in ONE
    fusion of a stage that is pure HBM traffic (`loss` 6.3 ms,
    `loss_time_share` 27.06 / 23.77 with the head; ledger, PR 50; the
    fusions: PERF.md section 5, PR 51). Now the gradient is an expression
    of the logits and two values a row, which XLA may fold into the
    operands of the head's backward products (it does for gpt_lm_program:
    the gradient is no buffer at all, `loss` 1.2 ms).

    The aux-loss path (`Softmax@GRAD` present) rebuilds `sm` the same way
    and keeps its formula. A grad op that carries a saved `Softmax` and no
    `Lse` (see the maker) traces the older expressions.
    `loss_lse_lowerings_total` / `loss_softmax_kept_lowerings_total` count
    which kind of program a lowering belonged to."""
    label = ins["Label"][0]
    g = ins["Loss@GRAD"][0]
    g_sm = ins.get("Softmax@GRAD", [None])[0]
    if "Lse" in ins:
        logits = ins["Logits"][0]
        out_dtype = logits.dtype
        sm = jnp.exp(logits.astype(jnp.float32) - ins["Lse"][0])
        kept = g_sm is not None or bool(attrs.get("softmax_read", False))
    else:
        out_dtype = ins["Softmax"][0].dtype
        sm = ins["Softmax"][0].astype(jnp.float32)
        kept = True
    if not ctx.abstract:
        from ..observability.metrics import get_registry
        get_registry().counter(*_XENT_LOWERINGS[kept]).inc()
    axis = attrs.get("axis", -1) % sm.ndim
    if attrs.get("soft_label", False):
        d = sm - label.astype(jnp.float32)
    else:
        lab = label
        if lab.ndim == sm.ndim and lab.shape[axis] == 1:
            lab = jnp.squeeze(lab, axis)
        idx = jnp.expand_dims(lab.astype(jnp.int32), axis)
        # onehot as iota==label: fuses to a select, no (.., V) materialize
        iota = jax.lax.broadcasted_iota(jnp.int32, sm.shape, axis)
        d = sm - (iota == idx).astype(jnp.float32)
        ignore = attrs.get("ignore_index", -100)
        d = jnp.where(jnp.expand_dims(lab == ignore, axis), 0.0, d)
    dl = d * g.astype(jnp.float32)
    if g_sm is not None:
        # aux-loss path through the Softmax output: softmax vjp
        # dL/dlogits += (g_sm - sum(g_sm * sm)) * sm
        gs = g_sm.astype(jnp.float32)
        dl = dl + (gs - jnp.sum(gs * sm, axis=axis, keepdims=True)) * sm
    return {"Logits@GRAD": [dl.astype(out_dtype)]}


@register_op("softmax_with_cross_entropy", no_grad_inputs={"Label"},
             non_diff_outputs={"Lse"},
             grad_maker=_softmax_xent_grad_maker,
             grad_lower=_softmax_xent_grad_lower)
def _softmax_xent(ctx, ins, attrs):
    """reference: softmax_with_cross_entropy_op.cc — the numerically stable
    fused path (log-softmax + NLL in one), float32 inside whatever the
    logits' type (bf16 logits only halve HBM traffic: AMP-safe).

    What the op SAVES for its gradient is `Lse`, the row's float32
    log-sum-exp in the shape of `Loss` (max + log of the shifted sum, the
    two reductions log_softmax makes anyway), not the probabilities: the
    grad op rebuilds them from the logits (see _softmax_xent_grad_lower).
    `Softmax` stays in the op's contract, computed by the same expression
    as before (exp(logp) in the logits' type), but nothing of the gradient
    reads it: where no other op does either, XLA removes it.
    The hard-label loss takes the label's logit from the INPUT (a gather of
    one element a row out of a tensor that lies in HBM already) and shifts
    it as log_softmax shifts the row: -((x[label] - max) - log_sum), the
    bits of -log_softmax(x)[label]; gathered out of `logp`, as before PR
    51, it made XLA write `logp` whole in float32 (1.65 GB a step at
    GPT-2's 8 x 1,023 x 50,257). Gradients do not flow through `Lse`;
    they do through `Softmax` (an aux loss's `Softmax@GRAD`)."""
    logits, label = ins["Logits"][0], ins["Label"][0]
    axis = attrs.get("axis", -1) % logits.ndim
    x = logits.astype(jnp.float32)
    # jax.nn.log_softmax written out, so that its two reductions are also
    # the saved output's
    row_max = jax.lax.stop_gradient(jnp.max(x, axis=axis, keepdims=True))
    shifted = x - row_max
    log_sum = jnp.log(jnp.sum(jnp.exp(shifted), axis=axis, keepdims=True))
    logp = shifted - log_sum
    softmax = jnp.exp(logp).astype(logits.dtype)
    lse = row_max + log_sum
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * logp, axis=axis, keepdims=True)
    else:
        lab = label
        if lab.ndim == logits.ndim and lab.shape[axis] == 1:
            lab = jnp.squeeze(lab, axis)
        idx = jnp.expand_dims(lab.astype(jnp.int32), axis)
        picked = jnp.take_along_axis(logits, idx, axis=axis)
        nll = -((picked.astype(jnp.float32) - row_max) - log_sum)
        ignore = attrs.get("ignore_index", -100)
        nll = jnp.where(jnp.expand_dims(lab == ignore, axis), 0.0, nll)
        loss = nll
    return {"Softmax": [softmax], "Loss": [loss], "Lse": [lse]}


@register_op("cross_entropy", no_grad_inputs={"Label"})
def _cross_entropy(ctx, ins, attrs):
    """reference: cross_entropy_op.cc — takes probabilities (post-softmax)."""
    x, label = ins["X"][0], ins["Label"][0]
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * jnp.log(jnp.maximum(x, 1e-20)), axis=-1,
                        keepdims=True)
    else:
        lab = _squeeze_label(label)
        p = jnp.take_along_axis(x, lab[..., None].astype(jnp.int32), axis=-1)
        loss = -jnp.log(jnp.maximum(p, 1e-20))
        ignore = attrs.get("ignore_index", -100)
        loss = jnp.where((lab == ignore)[..., None], 0.0, loss)
    return {"Y": [loss]}


@register_op("sigmoid_cross_entropy_with_logits", no_grad_inputs={"Label"})
def _sigmoid_xent(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    loss = jnp.maximum(x, 0.0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ignore = attrs.get("ignore_index", -100)
    loss = jnp.where(label == ignore, 0.0, loss)
    if attrs.get("normalize", False):
        n = jnp.maximum(jnp.sum((label != ignore).astype(loss.dtype)), 1.0)
        loss = loss / n
    return {"Out": [loss]}


@register_op("huber_loss", non_diff_outputs={"Residual"},
             no_grad_inputs={"Y"})
def _huber_loss(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    d = attrs.get("delta", 1.0)
    r = y - x
    loss = jnp.where(jnp.abs(r) <= d, 0.5 * r * r,
                     d * (jnp.abs(r) - 0.5 * d))
    return {"Out": [loss], "Residual": [r]}


@register_op("smooth_l1_loss", non_diff_outputs={"Diff"},
             no_grad_inputs={"Y", "InsideWeight", "OutsideWeight"})
def _smooth_l1(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    sigma = attrs.get("sigma", 1.0)
    s2 = sigma * sigma
    diff = x - y
    if "InsideWeight" in ins:
        diff = diff * ins["InsideWeight"][0]
    ad = jnp.abs(diff)
    loss = jnp.where(ad < 1.0 / s2, 0.5 * s2 * diff * diff, ad - 0.5 / s2)
    if "OutsideWeight" in ins:
        loss = loss * ins["OutsideWeight"][0]
    return {"Out": [jnp.sum(loss, axis=tuple(range(1, x.ndim)),
                            keepdims=False)[..., None]],
            "Diff": [diff]}


@register_op("square_error_cost", no_grad_inputs={"Label"})
def _square_error_cost(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Label"][0]
    return {"Out": [jnp.square(x - y)]}


@register_op("kldiv_loss", no_grad_inputs={"Target"})
def _kldiv_loss(ctx, ins, attrs):
    x, t = ins["X"][0], ins["Target"][0]
    loss = t * (jnp.log(jnp.maximum(t, 1e-20)) - x)
    red = attrs.get("reduction", "mean")
    if red == "mean":
        loss = jnp.mean(loss).reshape((1,))
    elif red == "sum":
        loss = jnp.sum(loss).reshape((1,))
    elif red == "batchmean":
        loss = (jnp.sum(loss) / x.shape[0]).reshape((1,))
    return {"Loss": [loss]}


# ---------------------------------------------------------------------------
# metrics (reference: operators/metrics/)
# ---------------------------------------------------------------------------

@register_op("accuracy", not_differentiable=True, grad_free=True)
def _accuracy(ctx, ins, attrs):
    """reference: metrics/accuracy_op.cc — takes top-k Indices + Label."""
    idx = ins["Indices"][0]
    label = _squeeze_label(ins["Label"][0])
    correct = jnp.any(idx == label[:, None], axis=1)
    n = idx.shape[0]
    num_correct = jnp.sum(correct.astype(jnp.float32))
    return {"Accuracy": [(num_correct / n).reshape((1,))],
            "Correct": [num_correct.astype(jnp.int32).reshape((1,))],
            "Total": [jnp.asarray([n], jnp.int32)]}


# ---------------------------------------------------------------------------
# resize / interpolate
# ---------------------------------------------------------------------------

def _interp_out_hw(x, attrs):
    oh = attrs.get("out_h", 0)
    ow = attrs.get("out_w", 0)
    if (not oh or not ow) and attrs.get("scale", 0.0):
        oh = int(x.shape[2] * attrs["scale"])
        ow = int(x.shape[3] * attrs["scale"])
    if not oh or not ow:
        raise ValueError("interp op needs out_h/out_w or scale")
    return oh, ow


def _interp_coords(in_dim, out_dim, align_corners, align_mode=1):
    """Source coordinates per output index, matching the reference
    interpolate_op: align_corners=True -> ratio (in-1)/(out-1) (index 0 for
    out_dim==1); else align_mode 1 (the reference default) -> src =
    ratio*dst; align_mode 0 -> half-pixel centers."""
    if align_corners:
        if out_dim <= 1:
            return jnp.zeros((out_dim,))
        return jnp.linspace(0.0, in_dim - 1.0, out_dim)
    if align_mode == 0:  # half-pixel
        return jnp.clip(
            (jnp.arange(out_dim) + 0.5) * (in_dim / out_dim) - 0.5,
            0, in_dim - 1)
    return jnp.clip(jnp.arange(out_dim) * (in_dim / out_dim),
                    0, in_dim - 1)


@register_op("nearest_interp")
def _nearest_interp(ctx, ins, attrs):
    x = ins["X"][0]
    oh, ow = _interp_out_hw(x, attrs)
    ac = attrs.get("align_corners", True)
    am = attrs.get("align_mode", 1)
    # reference nearest kernel: round only with align_corners; else floor
    # (static_cast<int>(ratio * dst))
    snap = jnp.round if ac else jnp.floor
    ih = snap(_interp_coords(x.shape[2], oh, ac, am)).astype(jnp.int32)
    iw = snap(_interp_coords(x.shape[3], ow, ac, am)).astype(jnp.int32)
    return {"Out": [x[:, :, ih][:, :, :, iw]]}


@register_op("bilinear_interp")
def _bilinear_interp(ctx, ins, attrs):
    x = ins["X"][0]
    oh, ow = _interp_out_hw(x, attrs)
    ac = attrs.get("align_corners", True)
    am = attrs.get("align_mode", 1)
    h, w = x.shape[2], x.shape[3]
    ys = _interp_coords(h, oh, ac, am)
    xs = _interp_coords(w, ow, ac, am)
    y0 = jnp.floor(ys).astype(jnp.int32)
    x0 = jnp.floor(xs).astype(jnp.int32)
    y1 = jnp.minimum(y0 + 1, h - 1)
    x1 = jnp.minimum(x0 + 1, w - 1)
    ly = (ys - y0)[None, None, :, None]
    lx = (xs - x0)[None, None, None, :]
    v00 = x[:, :, y0][:, :, :, x0]
    v01 = x[:, :, y0][:, :, :, x1]
    v10 = x[:, :, y1][:, :, :, x0]
    v11 = x[:, :, y1][:, :, :, x1]
    out = (v00 * (1 - ly) * (1 - lx) + v01 * (1 - ly) * lx
           + v10 * ly * (1 - lx) + v11 * ly * lx)
    return {"Out": [out.astype(x.dtype)]}


# ---------------------------------------------------------------------------
# 3-D convolution family (reference: conv3d in conv_op.cc, pool3d in
# pool_op.cc) — video/volumetric models; NCDHW layout
# ---------------------------------------------------------------------------

@register_op("conv3d_transpose")
def _conv3d_transpose(ctx, ins, attrs):
    """Grad-of-conv formulation like conv2d_transpose above: input-dilated
    conv with a flipped, IO-swapped kernel (Paddle output-shape
    semantics: out = (in-1)*stride - 2*pad + dilation*(k-1) + 1)."""
    x, w = ins["Input"][0], ins["Filter"][0]
    s3 = tuple(attrs.get("strides", [1, 1, 1]))
    p = attrs.get("paddings", [0, 0, 0])
    dil = tuple(attrs.get("dilations", [1, 1, 1]))
    groups = int(attrs.get("groups", 1))
    wf = jnp.flip(w, axis=(2, 3, 4))
    if groups == 1:
        wf = wf.transpose(1, 0, 2, 3, 4)  # -> OIDHW
    else:
        # same per-group IO swap as conv2d_transpose
        cin = wf.shape[0]
        wg = wf.reshape(groups, cin // groups, *wf.shape[1:])
        wf = wg.transpose(0, 2, 1, 3, 4, 5).reshape(
            groups * wf.shape[1], cin // groups, *wf.shape[2:])
    pad = []
    for i in range(3):
        e = dil[i] * (w.shape[2 + i] - 1)
        pad.append((e - p[i], e - p[i]))
    out = jax.lax.conv_general_dilated(
        x, wf, window_strides=(1, 1, 1), padding=pad, lhs_dilation=s3,
        rhs_dilation=dil, feature_group_count=groups,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"))
    return {"Output": [out]}


@register_op("pool3d")
def _pool3d(ctx, ins, attrs):
    x = ins["X"][0]
    ptype = attrs.get("pooling_type", "max")
    if attrs.get("global_pooling", False):
        fn = jnp.max if ptype == "max" else jnp.mean
        return {"Out": [fn(x, axis=(2, 3, 4), keepdims=True)]}
    ksize = tuple(attrs["ksize"])
    strides = tuple(attrs.get("strides", ksize))
    p = attrs.get("paddings", [0, 0, 0])
    extra = [0, 0, 0]
    if attrs.get("ceil_mode", False):
        for i, (dim, k, st, pp) in enumerate(
                zip(x.shape[2:], ksize, strides, p)):
            rem = (dim + 2 * pp - k) % st
            extra[i] = (st - rem) % st if rem else 0
    pads = [(0, 0), (0, 0), (p[0], p[0] + extra[0]),
            (p[1], p[1] + extra[1]), (p[2], p[2] + extra[2])]
    window = (1, 1) + ksize
    strides5 = (1, 1) + strides
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else \
            jnp.iinfo(x.dtype).min
        out = jax.lax.reduce_window(x, init, jax.lax.max, window, strides5,
                                    pads)
    else:
        ssum = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides5,
                                     pads)
        if attrs.get("exclusive", True):
            cnt = jax.lax.reduce_window(jnp.ones(x.shape, x.dtype), 0.0,
                                        jax.lax.add, window, strides5, pads)
            out = ssum / cnt
        else:
            out = ssum / float(np.prod(ksize))
    return {"Out": [out]}


@register_op("spectral_norm", non_diff_outputs={"UOut", "VOut"})
def _spectral_norm(ctx, ins, attrs):
    """reference spectral_norm_op.cc: weight / sigma_max, sigma estimated
    by power iteration with persistent U/V state (updated in place)."""
    w = ins["Weight"][0]
    u = ins["U"][0].reshape(-1)
    v = ins["V"][0].reshape(-1)
    dim = attrs.get("dim", 0)
    power_iters = attrs.get("power_iters", 1)
    eps = attrs.get("eps", 1e-12)
    mat = jnp.moveaxis(w, dim, 0).reshape(w.shape[dim], -1)

    def normalize(x):
        return x / (jnp.linalg.norm(x) + eps)

    for _ in range(max(power_iters, 0)):
        v = normalize(mat.T @ u)
        u = normalize(mat @ v)
    u = jax.lax.stop_gradient(u)
    v = jax.lax.stop_gradient(v)
    sigma = u @ (mat @ v)
    return {"Out": [w / sigma], "UOut": [u], "VOut": [v]}


@register_op("trilinear_interp")
def _trilinear_interp(ctx, ins, attrs):
    """reference: interpolate_op.cc trilinear mode — [n, c, D, H, W] resize
    via jax.image (matches align_corners=False half-pixel; align_corners
    uses the linear endpoint grid)."""
    import jax
    x = ins["X"][0]
    od = int(attrs["out_d"])
    oh = int(attrs["out_h"])
    ow = int(attrs["out_w"])
    n, c = x.shape[0], x.shape[1]
    method = "trilinear"
    if attrs.get("align_corners", True):
        # endpoint-aligned grid: gather with explicit coords per axis
        def coords(src, dst):
            if dst == 1:
                return jnp.zeros((1,))
            return jnp.linspace(0.0, src - 1.0, dst)
        d, h, w = x.shape[2:]
        zs, ys, xs = coords(d, od), coords(h, oh), coords(w, ow)

        def axis_lerp(arr, cs, axis):
            lo = jnp.floor(cs).astype(jnp.int32)
            hi = jnp.minimum(lo + 1, arr.shape[axis] - 1)
            t = (cs - lo).reshape([-1 if i == axis else 1
                                   for i in range(arr.ndim)])
            a = jnp.take(arr, lo, axis=axis)
            b = jnp.take(arr, hi, axis=axis)
            return a * (1 - t) + b * t

        out = axis_lerp(axis_lerp(axis_lerp(x, zs, 2), ys, 3), xs, 4)
        return {"Out": [out]}
    out = jax.image.resize(x, (n, c, od, oh, ow), method=method)
    return {"Out": [out]}
