"""Long-tail ops from the reference's top-level operator list: vision
rearrangement, linalg helpers, ranking/similarity losses, beam-search
decode utilities.

Reference: paddle/fluid/operators/ *_op.cc (interpolate_op.cc,
pixel_shuffle_op.cc, shuffle_channel_op.cc, space_to_depth_op.cc,
temporal_shift_op.cc, cos_sim_op.cc, multiplex_op.cc, rank_loss_op.cc,
margin_rank_loss_op.cc, bpr_loss_op.cc, log_loss_op.cc, hinge_loss_op.cc,
bilinear_tensor_product_op.cc, im2sequence_op.cc, unfold_op.cc,
add_position_encoding_op.cc, gather_tree_op.cc, linspace_op.cc,
shard_index_op.cc, sampling_id_op.cc, dist_op.cc, trace/diag/meshgrid/
kron/cross…).

Ops whose OUTPUT SIZE depends on data (masked_select, unique, where_index,
the LoD beam_search step op) are deliberately absent: XLA requires static
shapes; the padded/top-k formulations elsewhere (topk + gather_tree for
beam decode, boolean-mask multiply for selection) are the TPU-native
equivalents.
"""

import jax
import jax.numpy as jnp

from ..framework.registry import register_op


# ---------------------------------------------------------------------------
# spatial rearrangement (interp ops live in nn_ops.py via jax.image.resize
# — registering them here too would silently shadow those rules)
# ---------------------------------------------------------------------------

@register_op("pixel_shuffle")
def _pixel_shuffle(ctx, ins, attrs):
    x = ins["X"][0]
    r = int(attrs["upscale_factor"])
    n, c, h, w = x.shape
    y = x.reshape(n, c // (r * r), r, r, h, w)
    y = y.transpose(0, 1, 4, 2, 5, 3)
    return {"Out": [y.reshape(n, c // (r * r), h * r, w * r)]}


@register_op("shuffle_channel")
def _shuffle_channel(ctx, ins, attrs):
    x = ins["X"][0]
    g = int(attrs["group"])
    n, c, h, w = x.shape
    y = x.reshape(n, g, c // g, h, w).transpose(0, 2, 1, 3, 4)
    return {"Out": [y.reshape(n, c, h, w)]}


@register_op("space_to_depth")
def _space_to_depth(ctx, ins, attrs):
    x = ins["X"][0]
    b = int(attrs["blocksize"])
    n, c, h, w = x.shape
    y = x.reshape(n, c, h // b, b, w // b, b)
    y = y.transpose(0, 3, 5, 1, 2, 4)
    return {"Out": [y.reshape(n, c * b * b, h // b, w // b)]}


@register_op("temporal_shift")
def _temporal_shift(ctx, ins, attrs):
    """reference temporal_shift_op.cc: shift 1/shift_ratio of channels one
    frame back/forward across the fold of N = nt/seg batches."""
    x = ins["X"][0]
    seg = int(attrs["seg_num"])
    ratio = attrs.get("shift_ratio", 0.25)
    nt, c, h, w = x.shape
    n = nt // seg
    c1 = int(c * ratio)
    c2 = int(c * 2 * ratio)
    y = x.reshape(n, seg, c, h, w)
    fwd = jnp.concatenate(
        [y[:, 1:, :c1], jnp.zeros_like(y[:, :1, :c1])], axis=1)
    bwd = jnp.concatenate(
        [jnp.zeros_like(y[:, :1, c1:c2]), y[:, :-1, c1:c2]], axis=1)
    out = jnp.concatenate([fwd, bwd, y[:, :, c2:]], axis=2)
    return {"Out": [out.reshape(nt, c, h, w)]}


def _patches(x, ksize, strides, pad_pairs, dilations):
    patches = jax.lax.conv_general_dilated_patches(
        x, tuple(ksize), tuple(strides), list(pad_pairs),
        rhs_dilation=tuple(dilations),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    n, ckk, oh, ow = patches.shape
    return patches.reshape(n, ckk, oh * ow)


@register_op("unfold")
def _unfold(ctx, ins, attrs):
    """im2col (reference unfold_op.cc): [n,c,h,w] ->
    [n, c*kh*kw, out_h*out_w]. paddings: [ph, pw] symmetric, or the
    reference's 4-element [up, left, down, right]."""
    x = ins["X"][0]
    p = list(attrs.get("paddings", [0, 0]))
    if len(p) == 4:
        pad_pairs = [(p[0], p[2]), (p[1], p[3])]
    else:
        pad_pairs = [(p[0], p[0]), (p[1], p[1])]
    return {"Y": [_patches(x, attrs["kernel_sizes"],
                           attrs.get("strides", [1, 1]), pad_pairs,
                           attrs.get("dilations", [1, 1]))]}


@register_op("im2sequence")
def _im2sequence(ctx, ins, attrs):
    """reference im2sequence_op.cc: sliding patches flattened to a
    sequence [n, out_h*out_w, c*kh*kw]; paddings order matches unfold's
    [up, left, down, right]."""
    p = list(attrs.get("paddings", [0, 0, 0, 0]))
    pad_pairs = [(p[0], p[2]), (p[1], p[3])]
    y = _patches(ins["X"][0], attrs["kernels"],
                 attrs.get("strides", [1, 1]), pad_pairs, [1, 1])
    return {"Out": [jnp.swapaxes(y, 1, 2)]}


@register_op("add_position_encoding")
def _add_position_encoding(ctx, ins, attrs):
    """reference add_position_encoding_op.cc: sinusoidal PE added to
    [b, s, d]."""
    x = ins["X"][0]
    alpha = attrs.get("alpha", 1.0)
    beta = attrs.get("beta", 1.0)
    b, s, d = x.shape
    pos = jnp.arange(s, dtype=jnp.float32)[:, None]
    half_sin = (d + 1) // 2            # odd d: sin half gets the extra col
    i_sin = jnp.arange(half_sin, dtype=jnp.float32)[None, :]
    i_cos = jnp.arange(d - half_sin, dtype=jnp.float32)[None, :]
    pe = jnp.concatenate(
        [jnp.sin(pos / jnp.power(10000.0, 2 * i_sin / d)),
         jnp.cos(pos / jnp.power(10000.0, 2 * i_cos / d))], axis=1)
    return {"Out": [alpha * x + beta * pe[None, :, :].astype(x.dtype)]}


# ---------------------------------------------------------------------------
# linalg helpers
# ---------------------------------------------------------------------------

@register_op("linspace", not_differentiable=True, grad_free=True)
def _linspace(ctx, ins, attrs):
    """`num` must be a static attr: a tensor Num would be a dynamic output
    shape, which XLA cannot express (reject at build, not mid-trace)."""
    if "num" not in attrs:
        raise ValueError("linspace requires the static attr 'num' "
                         "(tensor Num means a dynamic shape under XLA)")
    start = ins["Start"][0].reshape(())
    stop = ins["Stop"][0].reshape(())
    return {"Out": [jnp.linspace(start, stop, int(attrs["num"]))]}


@register_op("shard_index", not_differentiable=True, grad_free=True)
def _shard_index(ctx, ins, attrs):
    """reference shard_index_op.cc: map global ids to shard-local ids
    (ignore_value outside this shard)."""
    x = ins["X"][0]
    index_num = attrs["index_num"]
    nshards = attrs["nshards"]
    shard_id = attrs["shard_id"]
    ignore = attrs.get("ignore_value", -1)
    per = (index_num + nshards - 1) // nshards
    local = x - shard_id * per
    return {"Out": [jnp.where((x // per) == shard_id, local, ignore)]}


@register_op("norm")
def _norm(ctx, ins, attrs):
    """l2-normalize along axis (reference norm_op.cc); Norm output is the
    per-slice norm."""
    x = ins["X"][0]
    axis = attrs.get("axis", -1)
    eps = attrs.get("epsilon", 1e-10)
    n = jnp.sqrt(jnp.sum(x * x, axis=axis, keepdims=True) + eps)
    return {"Out": [x / n], "Norm": [n]}


@register_op("dist")
def _dist(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    p = attrs.get("p", 2.0)
    d = jnp.abs(x - y)
    if p == 0:
        out = jnp.sum((d != 0).astype(x.dtype))
    elif p == float("inf"):
        out = jnp.max(d)
    else:
        out = jnp.sum(d ** p) ** (1.0 / p)
    return {"Out": [out.reshape((1,))]}


@register_op("cross", no_grad_inputs=set())
def _cross(ctx, ins, attrs):
    axis = attrs.get("dim", -1)
    return {"Out": [jnp.cross(ins["X"][0], ins["Y"][0], axis=axis)]}


@register_op("kron")
def _kron(ctx, ins, attrs):
    return {"Out": [jnp.kron(ins["X"][0], ins["Y"][0])]}


@register_op("trace")
def _trace(ctx, ins, attrs):
    return {"Out": [jnp.trace(ins["Input"][0],
                              offset=attrs.get("offset", 0),
                              axis1=attrs.get("axis1", 0),
                              axis2=attrs.get("axis2", 1))]}


@register_op("diag", not_differentiable=True, grad_free=True)
def _diag(ctx, ins, attrs):
    return {"Out": [jnp.diag(ins["Diagonal"][0])]}


@register_op("meshgrid", not_differentiable=True, grad_free=True)
def _meshgrid(ctx, ins, attrs):
    outs = jnp.meshgrid(*ins["X"], indexing="ij")
    return {"Out": list(outs)}


@register_op("bilinear_tensor_product")
def _bilinear_tensor_product(ctx, ins, attrs):
    """reference bilinear_tensor_product_op.cc: out[b,k] =
    x[b,:] @ W[k] @ y[b,:] + bias."""
    x, y, w = ins["X"][0], ins["Y"][0], ins["Weight"][0]
    out = jnp.einsum("bi,kij,bj->bk", x, w, y)
    if ins.get("Bias"):
        out = out + ins["Bias"][0]
    return {"Out": [out]}


# ---------------------------------------------------------------------------
# similarity / ranking losses
# ---------------------------------------------------------------------------

@register_op("cos_sim")
def _cos_sim(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    xn = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))
    yn = jnp.sqrt(jnp.sum(y * y, axis=-1, keepdims=True))
    out = jnp.sum(x * y, axis=-1, keepdims=True) / (xn * yn + 1e-12)
    return {"Out": [out], "XNorm": [xn], "YNorm": [yn]}


@register_op("rank_loss", no_grad_inputs={"Label"})
def _rank_loss(ctx, ins, attrs):
    """reference rank_loss_op.cc (RankNet)."""
    label = ins["Label"][0]
    left, right = ins["Left"][0], ins["Right"][0]
    d = left - right
    return {"Out": [jnp.logaddexp(0.0, d) - label * d]}


@register_op("margin_rank_loss", no_grad_inputs={"Label"})
def _margin_rank_loss(ctx, ins, attrs):
    label = ins["Label"][0]
    x1, x2 = ins["X1"][0], ins["X2"][0]
    margin = attrs.get("margin", 0.0)
    out = jnp.maximum(0.0, -label * (x1 - x2) + margin)
    return {"Out": [out], "Activated": [(out > 0).astype(x1.dtype)]}


@register_op("bpr_loss", no_grad_inputs={"Label"})
def _bpr_loss(ctx, ins, attrs):
    """Bayesian personalized ranking (reference bpr_loss_op.cc)."""
    x = ins["X"][0]                       # [b, c] scores
    label = ins["Label"][0].reshape(-1)   # positive item per row
    c = x.shape[1]
    pos = jnp.take_along_axis(x, label[:, None], axis=1)
    lsm = jax.nn.log_sigmoid(pos - x)
    # exclude the positive column itself; average over the c-1 negatives
    mask = jnp.arange(c)[None, :] != label[:, None]
    loss = -jnp.sum(lsm * mask, axis=1, keepdims=True) / float(c - 1)
    return {"Y": [loss]}


@register_op("log_loss", no_grad_inputs={"Labels"})
def _log_loss(ctx, ins, attrs):
    p = ins["Predicted"][0]
    y = ins["Labels"][0]
    eps = attrs.get("epsilon", 1e-7)
    return {"Loss": [-y * jnp.log(p + eps)
                     - (1 - y) * jnp.log(1 - p + eps)]}


@register_op("hinge_loss", no_grad_inputs={"Labels"})
def _hinge_loss(ctx, ins, attrs):
    logits = ins["Logits"][0]
    y = ins["Labels"][0]
    return {"Loss": [jnp.maximum(0.0, 1.0 - (2.0 * y - 1.0) * logits)]}


@register_op("modified_huber_loss", no_grad_inputs={"Y"})
def _modified_huber_loss(ctx, ins, attrs):
    x = ins["X"][0]
    y = 2.0 * ins["Y"][0] - 1.0
    z = x * y
    loss = jnp.where(z >= 1.0, 0.0,
                     jnp.where(z >= -1.0, (1.0 - z) ** 2, -4.0 * z))
    return {"Out": [loss], "IntermediateVal": [z]}


@register_op("teacher_student_sigmoid_loss", no_grad_inputs={"Label"})
def _ts_sigmoid_loss(ctx, ins, attrs):
    """reference teacher_student_sigmoid_loss_op.cc (CTR distillation)."""
    x = ins["X"][0]
    label = ins["Label"][0]
    soft_max_up = attrs.get("soft_max_up_bound", 15.0)
    soft_max_lo = attrs.get("soft_max_lower_bound", -15.0)
    z = jnp.clip(x, soft_max_lo, soft_max_up)
    # teacher part: sigmoid CE vs soft label; student part vs hard 0/1
    hard = (label > 0.5).astype(x.dtype)
    ce = jnp.logaddexp(0.0, z) - hard * z
    soft = jnp.logaddexp(0.0, z) - label * z
    return {"Y": [ce + soft]}


# ---------------------------------------------------------------------------
# decode utilities
# ---------------------------------------------------------------------------

@register_op("gather_tree", not_differentiable=True, grad_free=True)
def _gather_tree(ctx, ins, attrs):
    """Backtrace beam-search parent pointers (reference
    gather_tree_op.cc): Ids/Parents [t, b, beam] -> full sequences."""
    ids, parents = ins["Ids"][0], ins["Parents"][0]
    t = ids.shape[0]

    def scan_fn(beam_idx, ti):
        out = jnp.take_along_axis(ids[ti], beam_idx, axis=-1)
        nxt = jnp.take_along_axis(parents[ti], beam_idx, axis=-1)
        return nxt, out

    b, beam = ids.shape[1], ids.shape[2]
    init = jnp.broadcast_to(jnp.arange(beam)[None, :], (b, beam))
    _, outs = jax.lax.scan(scan_fn, init, jnp.arange(t - 1, -1, -1))
    return {"Out": [jnp.flip(outs, axis=0)]}


@register_op("sampling_id", not_differentiable=True, grad_free=True, stateful=True)
def _sampling_id(ctx, ins, attrs):
    """Sample a column index per row from probabilities (reference
    sampling_id_op.cc)."""
    x = ins["X"][0]
    key = ctx.rng()
    return {"Out": [jax.random.categorical(
        key, jnp.log(jnp.maximum(x, 1e-20))).astype(jnp.int64)]}


# ---------------------------------------------------------------------------
# infra ops: Print (debug passthrough via host callback), isnan/isinf
# (reference print_op.cc, isfinite_op.cc)
# ---------------------------------------------------------------------------

@register_op("print")
def _print(ctx, ins, attrs):
    """Debug print: passes X through unchanged and emits a host-side print
    of stats/values (reference print_op.cc) via jax.debug.print."""
    x = ins["X"][0]
    if ctx.abstract:
        return {"Out": [x]}
    msg = attrs.get("message", "")
    summarize = int(attrs.get("summarize", 20))
    if x.size == 0:
        jax.debug.print(msg + " shape={s} (empty)", s=str(x.shape))
    elif attrs.get("print_tensor_stats", True):
        jax.debug.print(
            msg + " shape={s} mean={m} min={mn} max={mx} first={f}",
            s=str(x.shape), m=jnp.mean(x.astype(jnp.float32)),
            mn=jnp.min(x), mx=jnp.max(x),
            f=x.reshape(-1)[:summarize])
    else:
        jax.debug.print(msg + " {v}", v=x.reshape(-1)[:summarize])
    return {"Out": [x]}


@register_op("isnan", not_differentiable=True, grad_free=True)
def _isnan(ctx, ins, attrs):
    return {"Out": [jnp.any(jnp.isnan(ins["X"][0])).reshape((1,))]}


@register_op("isinf", not_differentiable=True, grad_free=True)
def _isinf(ctx, ins, attrs):
    return {"Out": [jnp.any(jnp.isinf(ins["X"][0])).reshape((1,))]}


@register_op("sign")
def _sign(ctx, ins, attrs):
    """reference: sign_op.cc (grad is zero — jnp.sign's vjp handles it)."""
    return {"Out": [jnp.sign(ins["X"][0])]}
