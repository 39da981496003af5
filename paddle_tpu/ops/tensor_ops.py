"""Tensor manipulation ops: shape, indexing, fill, cast, random.

Reference: paddle/fluid/operators/ reshape_op.cc, transpose_op.cc,
concat_op.cc, split_op.cc, slice_op.cc, gather_op.cc, one_hot_op.cc,
fill_constant_op.cc, uniform_random_op.cc, lookup_table_op.cc, top_k_op.cc…
Random ops draw keys from the LowerContext's functional RNG stream so a block
stays a pure function of (scope, feed, rng_key).
"""

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.registry import register_op


def _prod(t):
    p = 1
    for v in t:
        p *= int(v)
    return p


# ---------------------------------------------------------------------------
# reshape family: fluid emits reshape2/transpose2 with an XShape side output
# that records the input shape for the grad op; with vjp-based grads we only
# keep it for IR compatibility (non-diff, zero-size semantics).
# ---------------------------------------------------------------------------

def _resolve_shape(shape, x):
    """fluid reshape semantics: 0 -> copy input dim, -1 -> infer."""
    shape = list(shape)
    for i, s in enumerate(shape):
        if s == 0:
            shape[i] = x.shape[i]
    if -1 in shape:
        known = _prod([s for s in shape if s != -1])
        shape[shape.index(-1)] = _prod(x.shape) // max(known, 1)
    return tuple(shape)


@register_op("reshape2", non_diff_outputs={"XShape"})
def _reshape2(ctx, ins, attrs):
    x = ins["X"][0]
    out = jnp.reshape(x, _resolve_shape(attrs["shape"], x))
    return {"Out": [out], "XShape": [jnp.zeros((0,) + x.shape, x.dtype)]}


@register_op("reshape")
def _reshape(ctx, ins, attrs):
    x = ins["X"][0]
    return {"Out": [jnp.reshape(x, _resolve_shape(attrs["shape"], x))]}


@register_op("transpose2", non_diff_outputs={"XShape"})
def _transpose2(ctx, ins, attrs):
    x = ins["X"][0]
    out = jnp.transpose(x, attrs["axis"])
    return {"Out": [out], "XShape": [jnp.zeros((0,) + x.shape, x.dtype)]}


@register_op("transpose")
def _transpose(ctx, ins, attrs):
    return {"Out": [jnp.transpose(ins["X"][0], attrs["axis"])]}


@register_op("squeeze2", non_diff_outputs={"XShape"})
def _squeeze2(ctx, ins, attrs):
    x = ins["X"][0]
    axes = attrs.get("axes", [])
    if axes:
        axes = tuple(a % x.ndim for a in axes if x.shape[a % x.ndim] == 1)
        out = jnp.squeeze(x, axis=axes) if axes else x
    else:
        out = jnp.squeeze(x)
    return {"Out": [out], "XShape": [jnp.zeros((0,) + x.shape, x.dtype)]}


@register_op("unsqueeze2", non_diff_outputs={"XShape"})
def _unsqueeze2(ctx, ins, attrs):
    x = ins["X"][0]
    out = x
    for a in sorted(attrs["axes"]):
        out = jnp.expand_dims(out, a)
    return {"Out": [out], "XShape": [jnp.zeros((0,) + x.shape, x.dtype)]}


@register_op("flatten2", non_diff_outputs={"XShape"})
def _flatten2(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", 1)
    out = x.reshape((_prod(x.shape[:axis]), _prod(x.shape[axis:])))
    return {"Out": [out], "XShape": [jnp.zeros((0,) + x.shape, x.dtype)]}


@register_op("flatten_contiguous_range")
def _flatten_range(ctx, ins, attrs):
    x = ins["X"][0]
    start = attrs.get("start_axis", 1) % x.ndim
    stop = attrs.get("stop_axis", -1) % x.ndim
    shape = x.shape[:start] + (_prod(x.shape[start:stop + 1]),) \
        + x.shape[stop + 1:]
    return {"Out": [x.reshape(shape)]}


# ---------------------------------------------------------------------------
# concat / split / stack / slice / pad / expand
# ---------------------------------------------------------------------------

@register_op("concat")
def _concat(ctx, ins, attrs):
    return {"Out": [jnp.concatenate(ins["X"], axis=attrs.get("axis", 0))]}


@register_op("split")
def _split(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", 0)
    sections = attrs.get("sections", [])
    if sections:
        idx = np.cumsum(sections[:-1]).tolist()
        outs = jnp.split(x, idx, axis=axis)
    else:
        outs = jnp.split(x, attrs["num"], axis=axis)
    return {"Out": list(outs)}


@register_op("stack")
def _stack(ctx, ins, attrs):
    return {"Y": [jnp.stack(ins["X"], axis=attrs.get("axis", 0))]}


@register_op("unstack")
def _unstack(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", 0)
    return {"Y": [jnp.squeeze(s, axis=axis)
                  for s in jnp.split(x, x.shape[axis], axis=axis)]}


@register_op("slice")
def _slice(ctx, ins, attrs):
    x = ins["Input"][0]
    axes = attrs["axes"]
    starts = attrs["starts"]
    ends = attrs["ends"]
    idx = [slice(None)] * x.ndim
    for a, s, e in zip(axes, starts, ends):
        dim = x.shape[a]
        s = max(s + dim, 0) if s < 0 else min(s, dim)
        e = max(e + dim, 0) if e < 0 else min(e, dim)
        idx[a] = slice(s, e)
    out = x[tuple(idx)]
    for a in sorted(attrs.get("decrease_axis", []), reverse=True):
        out = jnp.squeeze(out, axis=a)
    return {"Out": [out]}


@register_op("strided_slice")
def _strided_slice(ctx, ins, attrs):
    x = ins["Input"][0]
    idx = [slice(None)] * x.ndim
    for a, s, e, st in zip(attrs["axes"], attrs["starts"], attrs["ends"],
                           attrs["strides"]):
        idx[a] = slice(s, e, st)
    return {"Out": [x[tuple(idx)]]}


@register_op("pad")
def _pad(ctx, ins, attrs):
    x = ins["X"][0]
    p = attrs["paddings"]
    pairs = [(p[2 * i], p[2 * i + 1]) for i in range(x.ndim)]
    return {"Out": [jnp.pad(x, pairs, constant_values=attrs.get(
        "pad_value", 0.0))]}


@register_op("pad2d")
def _pad2d(ctx, ins, attrs):
    x = ins["X"][0]
    t, b, l, r = attrs["paddings"]
    mode = attrs.get("mode", "constant")
    pairs = [(0, 0), (0, 0), (t, b), (l, r)]
    if mode == "constant":
        out = jnp.pad(x, pairs, constant_values=attrs.get("pad_value", 0.0))
    elif mode == "reflect":
        out = jnp.pad(x, pairs, mode="reflect")
    else:
        out = jnp.pad(x, pairs, mode="edge")
    return {"Out": [out]}


@register_op("expand")
def _expand(ctx, ins, attrs):
    x = ins["X"][0]
    times = attrs["expand_times"]
    return {"Out": [jnp.tile(x, times)]}


@register_op("expand_as")
def _expand_as(ctx, ins, attrs, ):
    x, tgt = ins["X"][0], ins["target_tensor"][0]
    times = [t // s for t, s in zip(tgt.shape, x.shape)]
    return {"Out": [jnp.tile(x, times)]}


@register_op("tile")
def _tile(ctx, ins, attrs):
    return {"Out": [jnp.tile(ins["X"][0], attrs["repeat_times"])]}


@register_op("roll")
def _roll(ctx, ins, attrs):
    return {"Out": [jnp.roll(ins["X"][0], attrs["shifts"],
                             axis=tuple(attrs["axis"]))]}


@register_op("flip")
def _flip(ctx, ins, attrs):
    return {"Out": [jnp.flip(ins["X"][0], axis=tuple(attrs["axis"]))]}


# ---------------------------------------------------------------------------
# gather / scatter / embedding
# ---------------------------------------------------------------------------

@register_op("gather", no_grad_inputs={"Index"})
def _gather(ctx, ins, attrs):
    x, idx = ins["X"][0], ins["Index"][0]
    return {"Out": [jnp.take(x, idx.reshape(-1), axis=0)]}


@register_op("gather_nd", no_grad_inputs={"Index"})
def _gather_nd(ctx, ins, attrs):
    x, idx = ins["X"][0], ins["Index"][0]
    return {"Out": [x[tuple(jnp.moveaxis(idx, -1, 0))]]}


@register_op("scatter", no_grad_inputs={"Ids"})
def _scatter(ctx, ins, attrs):
    x, ids, upd = ins["X"][0], ins["Ids"][0], ins["Updates"][0]
    ids = ids.reshape(-1)
    if attrs.get("overwrite", True):
        out = x.at[ids].set(upd)
    else:
        out = x.at[ids].add(upd)
    return {"Out": [out]}


@register_op("scatter_nd_add", no_grad_inputs={"Index"})
def _scatter_nd_add(ctx, ins, attrs):
    x, idx, upd = ins["X"][0], ins["Index"][0], ins["Updates"][0]
    return {"Out": [x.at[tuple(jnp.moveaxis(idx, -1, 0))].add(upd)]}


def _lookup_sparse_slots(op):
    return {"W"} if op.attrs.get("is_sparse", False) else set()


def _lookup_table_grad(ctx, ins, attrs, squeeze_trailing):
    """Custom grad: dense scatter-add, or — with is_sparse=True — a
    SelectedRows of (ids, out-grad rows), the reference's sparse-embedding
    gradient (operators/lookup_table_op.h LookupTableGradKernel SelectedRows
    branch). The sparse form is what the PS path ships over the wire."""
    from ..framework.selected_rows import SelectedRows

    w, ids, og = ins["W"][0], ins["Ids"][0], ins["Out@GRAD"][0]
    if squeeze_trailing and ids.ndim > 1 and ids.shape[-1] == 1:
        ids = jnp.squeeze(ids, -1)
    pad = attrs.get("padding_idx", -1)
    rows = ids.reshape(-1)
    vals = og.reshape(-1, og.shape[-1])
    if pad is not None and pad >= 0:
        vals = jnp.where((rows != pad)[:, None], vals, 0.0)
    if attrs.get("is_sparse", False):
        return {"W@GRAD": [SelectedRows(rows, vals, w.shape[0])]}
    dense = jnp.zeros_like(w).at[rows].add(vals.astype(w.dtype))
    return {"W@GRAD": [dense]}


@register_op("lookup_table", no_grad_inputs={"Ids"},
             sparse_grad_slots=_lookup_sparse_slots,
             grad_lower=lambda ctx, ins, attrs:
             _lookup_table_grad(ctx, ins, attrs, squeeze_trailing=True))
def _lookup_table(ctx, ins, attrs):
    """Embedding (reference: operators/lookup_table_op.cc). Ids carry a
    trailing 1 dim in fluid. With is_sparse=False the gradient is a dense
    scatter-add (XLA lowers it efficiently); is_sparse=True produces a
    SelectedRows grad consumed by sparse optimizer kernels / the PS path."""
    w, ids = ins["W"][0], ins["Ids"][0]
    squeeze = ids.ndim > 1 and ids.shape[-1] == 1
    if squeeze:
        ids = jnp.squeeze(ids, -1)
    out = jnp.take(w, ids, axis=0)
    pad = attrs.get("padding_idx", -1)
    if pad is not None and pad >= 0:
        mask = (ids != pad)[..., None]
        out = jnp.where(mask, out, 0.0)
    return {"Out": [out]}


@register_op("lookup_table_v2", no_grad_inputs={"Ids"},
             sparse_grad_slots=_lookup_sparse_slots,
             grad_lower=lambda ctx, ins, attrs:
             _lookup_table_grad(ctx, ins, attrs, squeeze_trailing=False))
def _lookup_table_v2(ctx, ins, attrs):
    w, ids = ins["W"][0], ins["Ids"][0]
    out = jnp.take(w, ids, axis=0)
    pad = attrs.get("padding_idx", -1)
    if pad is not None and pad >= 0:
        out = jnp.where((ids != pad)[..., None], out, 0.0)
    return {"Out": [out]}


@register_op("one_hot", not_differentiable=True, grad_free=True)
def _one_hot(ctx, ins, attrs):
    x = ins["X"][0]
    if x.ndim > 1 and x.shape[-1] == 1:
        x = jnp.squeeze(x, -1)
    return {"Out": [jax.nn.one_hot(x, attrs["depth"], dtype=jnp.float32)]}


@register_op("index_select", no_grad_inputs={"Index"})
def _index_select(ctx, ins, attrs):
    x, idx = ins["X"][0], ins["Index"][0]
    return {"Out": [jnp.take(x, idx, axis=attrs.get("dim", 0))]}


@register_op("where", no_grad_inputs={"Condition"})
def _where(ctx, ins, attrs):
    c, x, y = ins["Condition"][0], ins["X"][0], ins["Y"][0]
    return {"Out": [jnp.where(c, x, y)]}


@register_op("where_index", not_differentiable=True, grad_free=True)
def _where_index(ctx, ins, attrs):
    # dynamic-shape op; returns padded indices (static-shape TPU variant)
    c = ins["Condition"][0]
    idx = jnp.nonzero(c.reshape(-1), size=c.size, fill_value=-1)[0]
    return {"Out": [idx[:, None]]}


# ---------------------------------------------------------------------------
# fill / init / cast / assign
# ---------------------------------------------------------------------------

@register_op("fill_constant", not_differentiable=True, grad_free=True)
def _fill_constant(ctx, ins, attrs):
    shape = tuple(attrs["shape"])
    dtype = attrs.get("dtype", "float32")
    return {"Out": [jnp.full(shape, attrs["value"], dtype=dtype)]}


@register_op("fill_constant_batch_size_like", not_differentiable=True, grad_free=True)
def _fill_cbsl(ctx, ins, attrs):
    ref = ins["Input"][0]
    shape = list(attrs["shape"])
    in_idx = attrs.get("input_dim_idx", 0)
    out_idx = attrs.get("output_dim_idx", 0)
    shape[out_idx] = ref.shape[in_idx]
    return {"Out": [jnp.full(tuple(shape), attrs["value"],
                             dtype=attrs.get("dtype", "float32"))]}


@register_op("fill_zeros_like", not_differentiable=True, grad_free=True)
def _fill_zeros_like(ctx, ins, attrs):
    return {"Out": [jnp.zeros_like(ins["X"][0])]}


@register_op("fill_any_like", not_differentiable=True, grad_free=True)
def _fill_any_like(ctx, ins, attrs):
    x = ins["X"][0]
    dtype = attrs.get("dtype") or x.dtype
    return {"Out": [jnp.full_like(x, attrs["value"], dtype=dtype)]}


@register_op("assign")
def _assign(ctx, ins, attrs):
    return {"Out": [ins["X"][0]]}


@register_op("assign_value", not_differentiable=True, grad_free=True)
def _assign_value(ctx, ins, attrs):
    vals = np.asarray(attrs["values"], dtype=attrs.get("dtype", "float32"))
    return {"Out": [jnp.asarray(vals.reshape(attrs["shape"]))]}


@register_op("cast")
def _cast(ctx, ins, attrs):
    return {"Out": [ins["X"][0].astype(attrs["out_dtype"])]}


@register_op("shape", not_differentiable=True, grad_free=True)
def _shape(ctx, ins, attrs):
    x = ins["Input"][0]
    return {"Out": [jnp.asarray(x.shape, dtype=jnp.int32)]}


@register_op("size", not_differentiable=True, grad_free=True)
def _size(ctx, ins, attrs):
    return {"Out": [jnp.asarray([ins["Input"][0].size], dtype=jnp.int64)]}


@register_op("range", not_differentiable=True, grad_free=True)
def _range(ctx, ins, attrs):
    s = ins["Start"][0].reshape(())
    e = ins["End"][0].reshape(())
    st = ins["Step"][0].reshape(())
    # shapes must be static: compute length from python values at trace time
    raise NotImplementedError(
        "dynamic range op is not supported under jit; use layers.arange with "
        "static bounds")


@register_op("increment")
def _increment(ctx, ins, attrs):
    """reference: increment_op.cc — step keeps X's dtype (a python-float
    step must not promote an int64 loop counter to float32, which would
    re-type a While carry mid-loop)."""
    x = ins["X"][0]
    return {"Out": [x + jnp.asarray(attrs.get("step", 1.0), x.dtype)]}


# ---------------------------------------------------------------------------
# random ops — functional keys from ctx.rng()
# ---------------------------------------------------------------------------

def _rng_key(ctx, attrs):
    seed = attrs.get("seed", 0)
    if seed:
        return jax.random.PRNGKey(seed)
    return ctx.rng()


@register_op("uniform_random", not_differentiable=True, grad_free=True, stateful=True)
def _uniform_random(ctx, ins, attrs):
    shape = tuple(attrs["shape"])
    dtype = attrs.get("dtype", "float32")
    out = jax.random.uniform(_rng_key(ctx, attrs), shape,
                             minval=attrs.get("min", -1.0),
                             maxval=attrs.get("max", 1.0),
                             dtype=jnp.float32).astype(dtype)
    return {"Out": [out]}


@register_op("gaussian_random", not_differentiable=True, grad_free=True, stateful=True)
def _gaussian_random(ctx, ins, attrs):
    shape = tuple(attrs["shape"])
    dtype = attrs.get("dtype", "float32")
    out = (attrs.get("mean", 0.0) + attrs.get("std", 1.0)
           * jax.random.normal(_rng_key(ctx, attrs), shape, dtype=jnp.float32))
    return {"Out": [out.astype(dtype)]}


@register_op("truncated_gaussian_random", not_differentiable=True, grad_free=True,
             stateful=True)
def _truncated_gaussian_random(ctx, ins, attrs):
    shape = tuple(attrs["shape"])
    out = (attrs.get("mean", 0.0) + attrs.get("std", 1.0)
           * jax.random.truncated_normal(_rng_key(ctx, attrs), -2.0, 2.0,
                                         shape, dtype=jnp.float32))
    return {"Out": [out.astype(attrs.get("dtype", "float32"))]}


@register_op("randint", not_differentiable=True, grad_free=True, stateful=True)
def _randint(ctx, ins, attrs):
    return {"Out": [jax.random.randint(
        _rng_key(ctx, attrs), tuple(attrs["shape"]), attrs.get("low", 0),
        attrs.get("high"), dtype=attrs.get("dtype", "int64"))]}


@register_op("shuffle_batch", not_differentiable=True, grad_free=True, stateful=True)
def _shuffle_batch(ctx, ins, attrs):
    x = ins["X"][0]
    perm = jax.random.permutation(_rng_key(ctx, attrs), x.shape[0])
    return {"Out": [jnp.take(x, perm, axis=0)], "ShuffleIdx": [perm]}


# ---------------------------------------------------------------------------
# top-k / argsort / argmax / cumsum / unique
# ---------------------------------------------------------------------------

@register_op("top_k", non_diff_outputs={"Indices"})
def _top_k(ctx, ins, attrs):
    x = ins["X"][0]
    v, i = jax.lax.top_k(x, attrs["k"])
    return {"Out": [v], "Indices": [i.astype(jnp.int64)]}


@register_op("arg_max", not_differentiable=True, grad_free=True)
def _arg_max(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", -1)
    out = jnp.argmax(x, axis=axis).astype(attrs.get("dtype", "int64"))
    if attrs.get("keepdims", False):
        out = jnp.expand_dims(out, axis)
    return {"Out": [out]}


@register_op("arg_min", not_differentiable=True, grad_free=True)
def _arg_min(ctx, ins, attrs):
    x = ins["X"][0]
    return {"Out": [jnp.argmin(x, axis=attrs.get("axis", -1))
                    .astype(attrs.get("dtype", "int64"))]}


@register_op("argsort", non_diff_outputs={"Indices"})
def _argsort(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", -1)
    desc = attrs.get("descending", False)
    idx = jnp.argsort(-x if desc else x, axis=axis)
    out = jnp.take_along_axis(x, idx, axis=axis)
    return {"Out": [out], "Indices": [idx.astype(jnp.int64)]}


@register_op("cumsum")
def _cumsum(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", -1)
    if attrs.get("flatten", False):
        x = x.reshape(-1)
        axis = 0
    xa = jnp.flip(x, axis) if attrs.get("reverse", False) else x
    out = jnp.cumsum(xa, axis=axis)
    if attrs.get("exclusive", False):
        out = out - xa
    if attrs.get("reverse", False):
        out = jnp.flip(out, axis)
    return {"Out": [out]}


@register_op("cumprod")
def _cumprod(ctx, ins, attrs):
    return {"Out": [jnp.cumprod(ins["X"][0], axis=attrs.get("dim", -1))]}


# py_func: host-Python callback inside the graph
# (reference: operators/py_func_op.cc + layers py_func). The callable table
# lives host-side; the op lowers to jax.pure_callback, which XLA schedules
# as a host call — same mechanics as the reference's GIL-grabbing op.
_PY_FUNCS = {}


def register_py_func(fn) -> int:
    fid = len(_PY_FUNCS)
    _PY_FUNCS[fid] = fn
    return fid


@register_op("py_func", not_differentiable=True)
def _py_func(ctx, ins, attrs):
    import numpy as _np

    fn = _PY_FUNCS[attrs["func_id"]]
    out_shapes = attrs["out_shapes"]
    out_dtypes = attrs["out_dtypes"]
    xs = ins.get("X", [])
    results = [jax.ShapeDtypeStruct(tuple(s), jnp.dtype(d))
               for s, d in zip(out_shapes, out_dtypes)]

    def host_fn(*arrays):
        out = fn(*arrays)
        if not isinstance(out, (list, tuple)):
            out = [out]
        return [_np.asarray(o, dtype=d)
                for o, d in zip(out, out_dtypes)]

    outs = jax.pure_callback(host_fn, results, *xs)
    if not isinstance(outs, (list, tuple)):
        outs = [outs]
    return {"Out": list(outs)}


@register_op("optimization_barrier", not_differentiable=True,
             grad_free=True)
def _optimization_barrier(ctx, ins, attrs):
    """XLA opt-barrier: values pass through unchanged, but the compiler
    cannot CSE computations across it. The recompute transpiler feeds the
    cloned segments' inputs through one of these so the clones stay
    distinct from the original forward ops (exactly how jax.checkpoint
    keeps its rematerialized HLO from being deduplicated)."""
    xs = tuple(ins["X"])
    outs = jax.lax.optimization_barrier(xs)
    return {"Out": list(outs)}
