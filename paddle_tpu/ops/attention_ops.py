"""fused_attention program op: flash kernel / ring / Ulysses dispatch.

The program-IR face of the attention stack (flash_attention.py + parallel/
ring.py). Replaces the reference's composed attention graphs (nets.py
scaled_dot_product_attention) and the operators/fused/ family with one op
whose lowering picks the right TPU implementation:

  * no cp_axis          -> Pallas flash kernel on TPU, XLA reference on CPU;
                           under a GSPMD mesh the kernel runs per shard of
                           the batch (shard_map), because a Mosaic kernel
                           cannot be partitioned automatically
  * cp_axis + 'ring'    -> ring attention over the mesh axis (ppermute)
  * cp_axis + 'ulysses' -> all-to-all sequence parallelism

Inputs  Q/K/V: (b, s, n, d); BiasK (optional): (b, s_k) per-key additive.
Attrs   causal, sm_scale (0 = 1/sqrt(d)), cp_axis, seq_parallel, impl.
"""

import numpy as np

from ..framework.registry import register_op

__all__ = []


def _cp_active(ctx, attrs):
    cp_axis = attrs.get("cp_axis", "")
    mesh = ctx.mesh
    return (cp_axis and mesh is not None and cp_axis in mesh.axis_names
            and mesh.shape[cp_axis] > 1)


def _on_batch_shards(ctx, attrs, fn, *args):
    """fn(*args) for the flash path. GSPMD cannot partition a Mosaic
    kernel (jax refuses to lower one under a mesh: "wrap the call in a
    shard_map"), so under a GSPMD mesh fn runs once per shard of the
    batch: dim 0 of every argument and result (q/k/v/out are (b, ...),
    lse is (b*n, ...) batch-major) is split over the data-parallel axis
    when it divides the batch, and replicated over every other axis.
    Inside explicit-SPMD execution (ctx.spmd_axes) the op already sees
    one shard."""
    mesh = ctx.mesh
    if mesh is None or ctx.spmd_axes:
        return fn(*args)
    import jax
    from jax.sharding import PartitionSpec as P

    axis = attrs.get("batch_axis", "dp")
    if axis not in mesh.axis_names or args[0].shape[0] % mesh.shape[axis]:
        axis = None
    spec = P(axis)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=tuple(None if a is None else spec for a in args),
        out_specs=spec, check_vma=False)(*args)


def _fused_attention_grad_maker(op, block, no_grad_set):
    from ..framework.core import grad_var_name
    ins = {"Q": op.input("Q"), "K": op.input("K"), "V": op.input("V"),
           "Out": op.output("Out"), "Lse": op.output("Lse"),
           "Out@GRAD": [grad_var_name(op.output("Out")[0])]}
    if op.input("BiasK"):
        ins["BiasK"] = op.input("BiasK")
    return [{
        "type": "fused_attention_grad",
        "inputs": ins,
        "outputs": {"Q@GRAD": [grad_var_name(op.input("Q")[0])],
                    "K@GRAD": [grad_var_name(op.input("K")[0])],
                    "V@GRAD": [grad_var_name(op.input("V")[0])]},
        "attrs": dict(op.attrs),
    }]


def _fused_attention_grad_lower(ctx, ins, attrs):
    """Flash path: drive the Pallas backward kernel from the saved Out +
    Lse — the vjp-replay path re-ran the forward kernel inside the grad
    (custom calls are opaque to XLA CSE; measured +6.3 ms/step on the GPT
    flagship, BASELINE.md r5). The XLA-reference path replays via jax.vjp
    (pure ops, CSE dedupes). The cp paths also replay via jax.vjp; for
    ring that recompute is inherent to the algorithm, but ulysses on TPU
    dispatches to the flash kernel inside shard_map, so its replayed
    forward is still a real second launch — saving lse through shard_map
    is the known follow-up if ulysses shows up on a profile."""
    import jax
    from .flash_attention import attention_bwd_saved, flash_dispatch

    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    bias_k = ins.get("BiasK", [None])[0]
    out, lse = ins["Out"][0], ins["Lse"][0]
    g = ins["Out@GRAD"][0]
    causal = bool(attrs.get("causal", False))
    sm_scale = float(attrs.get("sm_scale", 0.0)) or None
    impl = attrs.get("impl", None) or None
    bias4 = bias_k[:, None, None, :] if bias_k is not None else None

    if not _cp_active(ctx, attrs):
        use_flash, _ = flash_dispatch(q, k, bias4, impl)
        if use_flash:
            def bwd(q_, k_, v_, bias_, out_, lse_, g_):
                return attention_bwd_saved(q_, k_, v_, bias_, out_, lse_,
                                           g_, causal, sm_scale, impl)

            dq, dk, dv = _on_batch_shards(
                ctx, attrs, bwd, q, k, v, bias4, out, lse,
                g.astype(out.dtype))
            return {"Q@GRAD": [dq], "K@GRAD": [dk], "V@GRAD": [dv]}

    def f(q_, k_, v_):
        fwd_ins = {"Q": [q_], "K": [k_], "V": [v_]}
        if bias_k is not None:
            fwd_ins["BiasK"] = [bias_k]
        return _fused_attention(ctx, fwd_ins, attrs)["Out"][0]

    _, vjp_fn = jax.vjp(f, q, k, v)
    dq, dk, dv = vjp_fn(g.astype(out.dtype))
    return {"Q@GRAD": [dq], "K@GRAD": [dk], "V@GRAD": [dv]}


@register_op("fused_attention", no_grad_inputs={"BiasK"},
             non_diff_outputs={"Lse"},
             grad_maker=_fused_attention_grad_maker,
             grad_lower=_fused_attention_grad_lower)
def _fused_attention(ctx, ins, attrs):
    from .flash_attention import attention_fwd_lse, flash_dispatch
    from ..parallel.ring import (ring_attention_sharded,
                                 ulysses_attention_sharded)

    import jax.numpy as jnp

    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    bias_k = ins.get("BiasK", [None])[0]
    causal = bool(attrs.get("causal", False))
    sm_scale = float(attrs.get("sm_scale", 0.0)) or None
    cp_axis = attrs.get("cp_axis", "")
    mode = attrs.get("seq_parallel", "ring")
    impl = attrs.get("impl", None) or None
    dummy_lse = jnp.zeros((1, 1), jnp.float32)

    mesh = ctx.mesh
    if _cp_active(ctx, attrs):
        import functools
        import jax
        from jax.sharding import PartitionSpec as P

        if mode == "ulysses":
            fn = functools.partial(ulysses_attention_sharded,
                                   axis_name=cp_axis, causal=causal,
                                   sm_scale=sm_scale, impl=impl)
        else:
            fn = functools.partial(ring_attention_sharded,
                                   axis_name=cp_axis, causal=causal,
                                   sm_scale=sm_scale)
        # shard batch over the dp axis too (hybrid dp x cp meshes would
        # otherwise all-gather the global batch onto every dp rank)
        batch_axis = attrs.get("batch_axis", "dp")
        ba = batch_axis if (batch_axis in mesh.axis_names
                            and batch_axis != cp_axis
                            and mesh.shape[batch_axis] > 1
                            and q.shape[0] % mesh.shape[batch_axis] == 0) \
            else None
        spec = P(ba, cp_axis, None, None)
        bspec = P(ba, cp_axis) if bias_k is not None else None
        out = jax.shard_map(
            lambda a, b, c, d: fn(a, b, c, d),
            mesh=mesh, in_specs=(spec, spec, spec, bspec),
            out_specs=spec, check_vma=False)(q, k, v, bias_k)
        return {"Out": [out], "Lse": [dummy_lse]}

    bias4 = None
    if bias_k is not None:
        bias4 = bias_k[:, None, None, :]
    def fwd(q_, k_, v_, bias_):
        return attention_fwd_lse(q_, k_, v_, bias_, causal=causal,
                                 sm_scale=sm_scale, impl=impl)

    if flash_dispatch(q, k, bias4, impl)[0]:
        out, lse = _on_batch_shards(ctx, attrs, fwd, q, k, v, bias4)
    else:   # plain XLA ops: GSPMD partitions them itself
        out, lse = fwd(q, k, v, bias4)
    return {"Out": [out], "Lse": [lse if lse is not None else dummy_lse]}
