"""Op registry: one JAX lowering rule per op type.

Replaces the reference's OpRegistry / OpInfoMap / REGISTER_OPERATOR machinery
(paddle/fluid/framework/op_registry.h:68,199; op_info.h). Key design change
for TPU: an op is *defined by its JAX lowering rule*. That single rule gives

  * build-time shape/dtype inference  — via jax.eval_shape (replaces the
    reference's per-op InferShape, operator.h:430),
  * runtime lowering                  — traced into the block-level jit
    (replaces per-op CPU/CUDA kernels),
  * gradients                         — via jax.vjp over the rule (replaces
    the reference's hand-written grad kernels + GradOpDescMaker,
    grad_op_desc_maker.h). XLA CSE dedupes the recomputed forward.

Ops can still override the grad-desc maker or the grad lowering when the
generic path is wrong (rng ops like dropout, ops with saved intermediates).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Set

import numpy as np

from .core import Block, Operator, GRAD_SUFFIX, NAMESCOPE_ATTR

__all__ = ["OpDef", "register_op", "get_op_def", "has_op_def",
           "infer_op_shapes", "LowerContext", "lower_op", "DUMMY_BATCH",
           "register_macro_op"]

# Dummy concrete size substituted for -1 (batch) dims during eval_shape-based
# inference; a large prime so a genuine layer dim colliding with it (and
# being wrongly mapped back to -1) is vanishingly unlikely.
DUMMY_BATCH = 8191


def shape_spec(shape, dtype):
    """jax.ShapeDtypeStruct from declared var metadata, -1 (batch) dims
    substituted with DUMMY_BATCH — the one spec convention shared by
    build-time inference here and the static verifier's read-only
    shape walk (analysis/analyzers.py)."""
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct(
        tuple(DUMMY_BATCH if d == -1 else d for d in shape),
        jnp.dtype(dtype))


def concrete_to_batch(shape):
    """Map DUMMY_BATCH dims of an inferred shape back to -1 (apply only
    when some input carried a -1 dim)."""
    return tuple(-1 if d == DUMMY_BATCH else d for d in shape)


@dataclass
class OpDef:
    type: str
    # lower(ctx, ins, attrs) -> {out_slot: [jax arrays]}
    lower: Callable[["LowerContext", Dict[str, List[Any]], Dict[str, Any]],
                    Dict[str, List[Any]]]
    # input slots that never receive gradients (indices, labels, ...)
    no_grad_inputs: Set[str] = field(default_factory=set)
    # output slots that are not differentiable / get zero cotangents
    non_diff_outputs: Set[str] = field(default_factory=set)
    # uses ctx.rng() — requires a custom grad path
    stateful: bool = False
    # in-place update op (optimizer ops): outputs alias inputs by name
    is_optimizer_op: bool = False
    # custom grad-op desc maker: (op, block, no_grad_set) -> list[dict] |None
    grad_maker: Optional[Callable] = None
    # custom grad lowering: (ctx, grad_op, env_getter, attrs) -> {slot: [..]}
    grad_lower: Optional[Callable] = None
    # if True, op has NO gradient (grads of its inputs are zeros / skipped)
    not_differentiable: bool = False
    # for not_differentiable ops: True means a zero/absent gradient is
    # mathematically intended (argmax, comparisons, samplers, box codecs);
    # False means silently dropping the gradient would train wrong, so
    # backward RAISES if the loss depends on this op's output
    grad_free: bool = False
    # fn(op) -> set of forward-input slots whose grads are SelectedRows
    # (e.g. lookup_table with is_sparse=True); backward marks those grad
    # vars' Variable.type = "selected_rows"
    sparse_grad_slots: Optional[Callable] = None


_REGISTRY: Dict[str, OpDef] = {}


def register_op(op_type: str, **kw):
    """Decorator: @register_op("relu") def _(ctx, ins, attrs): ..."""
    def deco(fn):
        _REGISTRY[op_type] = OpDef(type=op_type, lower=fn, **kw)
        return fn
    return deco


def get_op_def(op_type: str) -> OpDef:
    if op_type not in _REGISTRY:
        raise NotImplementedError(f"no lowering registered for op {op_type!r}")
    return _REGISTRY[op_type]


# Macro ops (control flow) lower with full context: fn(ctx, op, env) where
# env is the live name->array binding and op carries sub-block attrs. They
# reach their sub-blocks via op.block.program. The reference analog is
# operators/controlflow/ (while_op.cc runs a sub-block with a nested
# Executor); here the sub-block lowers into lax.while_loop/cond/scan bodies.
_MACROS: Dict[str, Callable] = {}


def register_macro_op(op_type: str, aliases: Sequence[str] = (), **opdef_kw):
    """aliases: extra op-type names sharing this lowering — reference-IR
    compatibility names (e.g. conditional_block_infer is the inference-time
    registration of the same kernel, controlflow/conditional_block_infer_op.cc)."""
    def deco(fn):
        opdef_kw.setdefault("not_differentiable",
                            "grad_maker" not in opdef_kw)
        for name in (op_type,) + tuple(aliases):
            _MACROS[name] = fn
            _REGISTRY[name] = OpDef(type=name, lower=None, **opdef_kw)
        return fn
    return deco


# Host-boundary ops: file IO (save/load), RPC (send/recv/listen_and_serv),
# reader machinery — side effects that cannot live inside the jitted XLA
# computation. The Executor runs them EAGERLY against the scope: ops before
# the first compute op run pre-jit (loads, reads), ops after the last
# compute op run post-jit (saves, barriers). fn(op, scope, feed) mutates
# scope/feed in place. The reference's analog is ops whose kernels do IO
# from inside the C++ interpreter loop (save_op.cc, send_op.cc) — with a
# whole-block jit that interpreter loop no longer exists, so the boundary
# moves to the executor.
_HOST_OPS: Dict[str, Callable] = {}


def register_host_op(op_type: str, aliases: Sequence[str] = (), **opdef_kw):
    def deco(fn):
        opdef_kw.setdefault("not_differentiable", True)
        opdef_kw.setdefault("grad_free", True)
        for name in (op_type,) + tuple(aliases):
            _HOST_OPS[name] = fn
            _REGISTRY[name] = OpDef(type=name, lower=None, **opdef_kw)
        return fn
    return deco


def has_op_def(op_type: str) -> bool:
    return op_type in _REGISTRY


# ---------------------------------------------------------------------------
# Lowering context
# ---------------------------------------------------------------------------

class LowerContext:
    """Per-trace state handed to lowering rules.

    Functional RNG: rules call ctx.rng() for a fresh PRNG key; keys are
    fold_in(base_key, counter) so the whole block stays a pure function of
    (scope, feed, base_key).
    """

    def __init__(self, rng_key=None, is_test: bool = False,
                 abstract: bool = False, mesh=None, spmd_axes=(),
                 differentiable: bool = False):
        self._rng_key = rng_key
        self._counter = 0
        self.is_test = is_test
        self.abstract = abstract  # True during eval_shape inference
        # True while tracing under jax.vjp (a macro grad op's replay):
        # everything lowered must be reverse-differentiable, so while ops
        # switch from lax.while_loop to their bounded masked-scan form
        self.differentiable = differentiable
        self.mesh = mesh          # jax.sharding.Mesh when running sharded
        # mesh axis names live under an enclosing shard_map (explicit-SPMD
        # execution mode): collective ops (c_allreduce_* ...) lower to named
        # lax collectives over these axes; empty = GSPMD/single-device mode
        self.spmd_axes = tuple(spmd_axes)

    def rng(self):
        import jax
        if self._rng_key is None:
            # abstract inference path — any key works, shapes are identical
            key = jax.random.PRNGKey(0)
        else:
            key = jax.random.fold_in(self._rng_key, self._counter)
        self._counter += 1
        return key


# ---------------------------------------------------------------------------
# Generic op lowering (forward + grad) given an environment
# ---------------------------------------------------------------------------

def lower_op(ctx: LowerContext, op: Operator, env: Dict[str, Any]) -> None:
    """Lower one op: read inputs from env, write outputs into env. Each op
    traces under jax.named_scope so XLA metadata (and profiler traces) carry
    op-level names — the RecordEvent analog at zero runtime cost: the op's
    type, under the name scopes it was built in where `pt.name_scope`
    stamped them (`head/matmul`, `attn/fc_grad`, `optimizer/adam`)."""
    import jax

    namescope = op.attrs.get(NAMESCOPE_ATTR)
    with jax.named_scope(f"{namescope}/{op.type}" if namescope
                         else op.type):
        if op.type in _MACROS:
            _MACROS[op.type](ctx, op, env)
            return
        if op.type.endswith("_grad"):
            _lower_grad_op(ctx, op, env)
            return
        opdef = get_op_def(op.type)
        ins = {slot: [env[n] for n in names]
               for slot, names in op.inputs.items() if names}
        outs = opdef.lower(ctx, ins, op.attrs)
        _bind_outputs(op, outs, env)


def _bind_outputs(op: Operator, outs: Dict[str, List[Any]], env):
    for slot, names in op.outputs.items():
        vals = outs.get(slot)
        if vals is None:
            continue
        if len(vals) != len(names):
            raise RuntimeError(
                f"op {op.type}: slot {slot} produced {len(vals)} values for "
                f"{len(names)} output vars")
        for n, v in zip(names, vals):
            env[n] = v


def _lower_grad_op(ctx: LowerContext, op: Operator, env: Dict[str, Any]):
    import jax
    import jax.numpy as jnp

    fwd_type = op.type[: -len("_grad")]
    opdef = get_op_def(fwd_type)

    if opdef.grad_lower is not None:
        ins = {slot: [env[n] for n in names if n]
               for slot, names in op.inputs.items()
               if any(n for n in names)}
        outs = opdef.grad_lower(ctx, ins, op.attrs)
        _bind_outputs(op, outs, env)
        return

    if opdef.stateful:
        raise RuntimeError(
            f"op {fwd_type} uses rng; it must define a custom grad_lower")

    # Split grad-op inputs into forward inputs, forward outputs, out-grads.
    fwd_in_slots: Dict[str, List[str]] = {}
    out_grad_slots: Dict[str, List[str]] = {}
    fwd_out_slots: Dict[str, List[str]] = {}
    for slot, names in op.inputs.items():
        if not names:
            continue
        if slot.endswith(GRAD_SUFFIX):
            out_grad_slots[slot[: -len(GRAD_SUFFIX)]] = names
        elif slot.startswith("__out__"):
            fwd_out_slots[slot[len("__out__"):]] = names
        else:
            fwd_in_slots[slot] = names

    # Which forward-input slots need grads (appear in grad-op outputs).
    req_slots = [s[: -len(GRAD_SUFFIX)] for s in op.outputs
                 if s.endswith(GRAD_SUFFIX) and op.outputs[s]]
    diff_slots = [s for s in fwd_in_slots
                  if s in req_slots and s not in opdef.no_grad_inputs]

    flat_primals = [env[n] for s in diff_slots for n in fwd_in_slots[s]]
    slot_lens = [len(fwd_in_slots[s]) for s in diff_slots]

    out_index: List = []  # filled during first trace: (slot, idx) per output

    def f(*flat):
        ins: Dict[str, List[Any]] = {}
        it = iter(flat)
        for s, ln in zip(diff_slots, slot_lens):
            ins[s] = [next(it) for _ in range(ln)]
        for s, names in fwd_in_slots.items():
            if s not in ins:
                ins[s] = [env[n] for n in names]
        sub_ctx = LowerContext(is_test=ctx.is_test, abstract=ctx.abstract,
                               mesh=ctx.mesh, spmd_axes=ctx.spmd_axes)
        outs = opdef.lower(sub_ctx, ins, op.attrs)
        out_index.clear()
        flat_outs = []
        for slot in sorted(outs):
            if slot in opdef.non_diff_outputs:
                continue
            for i, v in enumerate(outs[slot]):
                if jnp.issubdtype(jnp.asarray(v).dtype, jnp.inexact):
                    out_index.append((slot, i))
                    flat_outs.append(v)
        return tuple(flat_outs)

    primals_out, vjp_fn = jax.vjp(f, *flat_primals)

    # Cotangents: out-grad from env when present, else zeros.
    cots = []
    for (slot, i), primal in zip(out_index, primals_out):
        names = out_grad_slots.get(slot)
        g = None
        if names is not None and i < len(names) and names[i] in env:
            g = env[names[i]]
        cots.append(jnp.zeros_like(primal) if g is None
                    else jnp.asarray(g, dtype=primal.dtype))

    grads = vjp_fn(tuple(cots))

    it = iter(grads)
    grads_by_slot = {s: [next(it) for _ in range(ln)]
                     for s, ln in zip(diff_slots, slot_lens)}
    for slot, names in op.outputs.items():
        if not slot.endswith(GRAD_SUFFIX):
            continue
        base = slot[: -len(GRAD_SUFFIX)]
        vals = grads_by_slot.get(base)
        if vals is None:
            continue
        for n, v in zip(names, vals):
            if n:  # empty name == grad not needed for this var
                env[n] = v


# ---------------------------------------------------------------------------
# Shape inference by abstract evaluation
# ---------------------------------------------------------------------------

def infer_op_shapes(op: Operator, block: Block) -> None:
    """Set output var shapes/dtypes by abstract-evaluating the lowering rule.

    -1 (batch) dims are substituted with DUMMY_BATCH for tracing and mapped
    back to -1 in the outputs.
    """
    import jax

    if op.type in ("feed", "fetch"):
        return
    if op.type.endswith("_grad"):
        _infer_grad_shapes(op, block)
        return
    opdef = get_op_def(op.type)

    specs: Dict[str, List[Any]] = {}
    saw_dummy = False
    for slot, names in op.inputs.items():
        if not names:
            continue
        lst = []
        for n in names:
            v = block.var(n)
            if v.shape is None:
                raise RuntimeError(f"input var {n!r} of op {op.type} has no "
                                   "shape; declare it first")
            saw_dummy = saw_dummy or (-1 in v.shape)
            lst.append(shape_spec(v.shape, v.dtype))
        specs[slot] = lst

    ctx = LowerContext(abstract=True)

    def f(ins):
        return opdef.lower(ctx, ins, op.attrs)

    try:
        outs = jax.eval_shape(f, specs)
    except Exception as e:
        raise RuntimeError(
            f"shape inference failed for op {op.type} "
            f"(inputs={{{', '.join(f'{s}:{[block.var(n).shape for n in ns]}' for s, ns in op.inputs.items() if ns)}}}, "
            f"attrs={op.attrs}): {e}") from e

    for slot, names in op.outputs.items():
        vals = outs.get(slot)
        if vals is None:
            continue
        for n, sds in zip(names, vals):
            # resolve through the parent chain: writing an outer var from a
            # sub-block must NOT create a shadow in the sub-block
            v = block.var(n) if block.has_var(n) else block.create_var(
                name=n)
            shape = tuple(sds.shape)
            if saw_dummy:
                shape = concrete_to_batch(shape)
            v.shape = shape
            v.dtype = str(np.dtype(sds.dtype))


def _infer_grad_shapes(op: Operator, block: Block) -> None:
    """Grad var shape == forward var shape; no tracing needed."""
    for slot, names in op.outputs.items():
        if not slot.endswith(GRAD_SUFFIX):
            continue
        fwd_names = op.inputs.get(slot[: -len(GRAD_SUFFIX)], [])
        for i, n in enumerate(names):
            if not n:
                continue
            v = block.var(n) if block.has_var(n) else block.create_var(
                name=n)
            if i < len(fwd_names) and block.has_var(fwd_names[i]):
                fv = block.var(fwd_names[i])
                v.shape = fv.shape
                v.dtype = fv.dtype
