"""Forward-recompute (activation checkpointing) program rewrite.

The reference exposes this as fleet's `forward_recompute` /
`recompute_checkpoints` strategy knobs (incubate/fleet/collective); the
engine here is the RecomputeOptimizer design: after backward construction,
clone each checkpoint segment's forward ops into the backward region with
renamed vars, and rewire the grad ops to consume the recomputed values —
so the original activations die at the end of the forward pass and XLA's
memory-minimizing scheduler re-materializes them only when the backward
needs them.

TPU specifics:
  * a single `optimization_barrier` op feeds the clones their inputs —
    without it XLA CSE would merge clone and original (the same mechanism
    jax.checkpoint uses for its remat HLO);
  * dropout is replayed via its SAVED Mask (`dropout_mask_apply`), never
    re-drawn, so recompute is bit-identical to the saved-activation run;
  * other stateful (RNG) ops keep their outputs saved;
  * op order does not matter to XLA — scheduling is dataflow-driven — so
    all clones sit at the start of the backward region and the scheduler
    delays each to just before its consumers.
"""

from __future__ import annotations

from typing import List, Sequence

__all__ = ["apply_recompute"]

_SUFFIX = "@RECOMPUTE"
_BAR = "@RCBAR"


def apply_recompute(program, checkpoints: Sequence[str]) -> int:
    """Rewrite `program` (in place) to recompute non-checkpoint forward
    activations in the backward region. `checkpoints` are the var names
    to KEEP (segment boundaries — e.g. the per-layer residual outputs).
    Returns the number of cloned (recomputed) ops; 0 = nothing to do."""
    from ..framework.registry import (get_op_def, has_op_def, _MACROS,
                                      _HOST_OPS)

    blk = program.global_block
    ops = blk.ops
    first_bwd = next(
        (i for i, op in enumerate(ops)
         if op.attrs.get("op_role") in ("backward", "optimize",
                                        "lr_sched")), None)
    if first_bwd is None:
        raise ValueError(
            "apply_recompute needs backward ops — call it after "
            "optimizer.minimize()")
    fwd, rest = ops[:first_bwd], ops[first_bwd:]

    missing = [c for c in checkpoints if not blk.has_var(c)]
    if missing:
        raise ValueError(f"recompute checkpoints not in program: {missing}")

    keep = set(checkpoints)
    produced = {}
    for i, op in enumerate(fwd):
        for n in op.output_names():
            produced.setdefault(n, i)
        # RNG outputs are saved, never re-drawn: dropout's Out is
        # replayable from its Mask; other stateful ops keep everything
        if has_op_def(op.type) and get_op_def(op.type).stateful:
            keep.update(op.output("Mask") if op.type == "dropout"
                        else op.output_names())

    def is_keep(n: str) -> bool:
        if n in keep or n not in produced:
            return True        # checkpoints, feeds, params, pre-existing
        v = blk.vars.get(n)
        return v is not None and getattr(v, "persistable", False)

    # vars the backward consumes that we want recomputed, closed over the
    # forward producers needed to recompute them
    needed = {n for op in rest for n in op.input_names()
              if n and not is_keep(n)}
    clone_idx: set = set()
    work = list(needed)
    while work:
        i = produced[work.pop()]
        if i in clone_idx:
            continue
        clone_idx.add(i)
        for m in fwd[i].input_names():
            if m and not is_keep(m) and m not in needed:
                needed.add(m)
                work.append(m)
    if not clone_idx:
        return 0

    bad = [fwd[i].type for i in clone_idx
           if fwd[i].type in _MACROS or fwd[i].type in _HOST_OPS]
    if bad:
        raise ValueError(
            f"recompute segment contains control-flow/host ops {bad}; "
            "place checkpoints so segments hold only pure compute ops")

    # the barrier: every saved var the clones read goes through it once
    ext = set()
    for i in clone_idx:
        op = fwd[i]
        ext.update(m for m in op.input_names() if m and is_keep(m))
        if op.type == "dropout":
            ext.update(op.output("Mask"))
    ext = sorted(ext)
    bar = {n: n + _BAR for n in ext}
    for n in ext:
        src = blk.var(n)
        blk.create_var(name=bar[n], shape=src.shape, dtype=src.dtype,
                       stop_gradient=True)
    pos = first_bwd
    # infer_shape=True: the barrier's lowering canonicalizes dtypes
    # (int64 ids come out int32 with x64 off), so the declared metadata
    # must come from the rule, not a copy of the source var's — a copied
    # int64 here is stale (verifier: PT-E006)
    blk.insert_op(pos, "optimization_barrier", {"X": ext},
                  {"Out": [bar[n] for n in ext]},
                  {"op_role": "backward"}, infer_shape=True)
    pos += 1

    # clone outputs all get fresh names, but only NON-kept ones are
    # rewired into the backward (a cloned op may also produce a
    # checkpoint/saved var — that copy is dead and DCE'd, the original
    # stays the saved one)
    ren_all, ren = {}, {}
    for i in clone_idx:
        op = fwd[i]
        # a dropout clone is a dropout_mask_apply that replays the saved
        # Mask — it produces only Out; declaring a Mask@RECOMPUTE var
        # nothing ever writes leaves an orphan (verifier: PT-W102)
        out_names = op.output("Out") if op.type == "dropout" \
            else op.output_names()
        for n in out_names:
            if n:
                ren_all[n] = n + _SUFFIX
                if not is_keep(n):
                    ren[n] = n + _SUFFIX
    for n, rn in sorted(ren_all.items()):
        src = blk.vars.get(n)
        blk.create_var(name=rn, shape=getattr(src, "shape", None),
                       dtype=getattr(src, "dtype", "float32"),
                       stop_gradient=True)

    def map_in(n: str) -> str:
        return ren.get(n, bar.get(n, n))

    for i in sorted(clone_idx):
        op = fwd[i]
        outs = {s: [ren_all.get(n, n) for n in ns]
                for s, ns in op.outputs.items()}
        if op.type == "dropout":
            blk.insert_op(
                pos, "dropout_mask_apply",
                {"X": [map_in(op.input("X")[0])],
                 "Mask": [bar[op.output("Mask")[0]]]},
                {"Out": [ren[op.output("Out")[0]]]},
                {**{k: v for k, v in op.attrs.items()
                    if k in ("dropout_prob", "dropout_implementation",
                             "is_test", "op_namescope")},
                 "op_role": "backward"}, infer_shape=False)
        else:
            ins = {s: [map_in(n) for n in ns]
                   for s, ns in op.inputs.items()}
            blk.insert_op(pos, op.type, ins, outs,
                          {**op.attrs, "op_role": "backward"},
                          infer_shape=False)
        pos += 1

    # grad/optimizer/host ops now read the recomputed activations
    for op in rest:
        for s, ns in op.inputs.items():
            op.inputs[s] = [ren.get(n, n) for n in ns]
    program._bump_version()
    return len(clone_idx)
