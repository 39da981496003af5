"""ShardingPlan: mesh construction + sharding placement for the executor.

The TPU-native replacement for the reference's multi-device SSA graph
machinery (parallel_executor.cc:380-606 + ir/multi_devices_graph_pass/):
instead of cloning ops per device and inserting AllReduceOpHandles, we
annotate shardings on a jax.sharding.Mesh and let GSPMD partition the single
XLA computation — collectives ride ICI and are inserted/scheduled by the
compiler.

Default plan = pure data parallel: feed batch sharded on axis 'dp', scope
replicated. With param_shardings, params get PartitionSpecs (tensor
parallelism / sharded optimizer state).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

__all__ = ["ShardingPlan", "CollectiveSpmdPlan", "ServingTPPlan"]


class ShardingPlan:
    def __init__(self, param_shardings: Optional[Dict[str, tuple]] = None,
                 mesh_shape: Optional[Tuple[int, ...]] = None,
                 axis_names: Tuple[str, ...] = ("dp",),
                 places=None, devices=None,
                 feed_shardings: Optional[Dict[str, tuple]] = None):
        import jax
        import numpy as np
        from jax.sharding import Mesh

        self.param_shardings = dict(param_shardings or {})
        self.feed_shardings = dict(feed_shardings or {})
        devs = devices if devices is not None else jax.devices()
        if places is not None and isinstance(places, int):
            devs = devs[:places]
        if mesh_shape is None:
            mesh_shape = (len(devs),)
            axis_names = axis_names[:1]
        self.axis_names = tuple(axis_names)
        self.mesh = Mesh(
            np.asarray(devs).reshape(mesh_shape), self.axis_names)
        self.batch_axis = self.axis_names[0]
        self._shardings: Dict = {}  # PartitionSpec -> NamedSharding on the mesh

    # -- shardings -----------------------------------------------------------
    def _spec(self, *parts):
        from jax.sharding import PartitionSpec
        return PartitionSpec(*parts)

    def _nsh(self, spec):
        """The mesh's NamedSharding of `spec`, built once a spec: a step
        asks for hundreds of them and nearly all are the replicated one."""
        sh = self._shardings.get(spec)
        if sh is None:
            from jax.sharding import NamedSharding
            sh = self._shardings[spec] = NamedSharding(self.mesh, spec)
        return sh

    def feed_sharding(self, shape=None, name=None):
        """Explicit per-feed PartitionSpec when given (e.g. sequence dim on
        a 'cp' axis); else batch-shard when the leading dim divides over the
        dp axis; replicate small/scalar feeds (e.g. a (1,)-shaped lr)."""
        if name is not None and name in self.feed_shardings:
            return self._nsh(self._spec(*self.feed_shardings[name]))
        n = self.mesh.shape[self.batch_axis]
        if shape is not None and (not shape or shape[0] % n != 0):
            return self._nsh(self._spec())
        return self._nsh(self._spec(self.batch_axis))

    def scope_sharding(self, name: str):
        if name in self.param_shardings:
            return self._nsh(self._spec(*self.param_shardings[name]))
        return self._nsh(self._spec())

    # -- executor hooks ------------------------------------------------------
    def _batch_parts(self):
        """(mesh axes the batch dim shards over, total batch shards) —
        the ONE place the batch-sharding rule lives, so shard_feed and the
        jit in_shardings cannot disagree."""
        return (self.batch_axis,), self.mesh.shape[self.batch_axis]

    def _put(self, v, sharding):
        """device_put — or, on a multi-process mesh (jax.distributed: one
        process per host, the reference's launch.py:132 deployment shape),
        assemble the GLOBAL array from this process's local data. A value
        that is already a global (non-addressable) array is resharded via
        device_put, never round-tripped through the host."""
        import jax
        cur = getattr(v, "sharding", None)
        if cur is not None and cur == sharding:
            return v
        if jax.process_count() > 1:
            if isinstance(v, jax.Array) and not v.is_fully_addressable:
                return jax.device_put(v, sharding)   # global -> reshard
            import numpy as np
            return jax.make_array_from_process_local_data(
                sharding, np.asarray(v))
        return jax.device_put(v, sharding)

    def shard_feed(self, feed: Dict):
        """Place feed arrays batch-sharded across the mesh.

        Multi-process contract (each process is a reference trainer):
        every feed is this process's LOCAL batch shard — the global batch
        is their rank-order concatenation. A feed that is NOT per-process
        data (a broadcast lr scalar, a shared table) must be declared via
        feed_shardings={name: ()}; silently replicating per-process data
        would make devices disagree on a "replicated" value, which is the
        one unrecoverable mistake here, so undeclared unshardable feeds
        raise instead."""
        import jax
        out = {}
        multi = jax.process_count() > 1
        for k, v in feed.items():
            shape = tuple(v.shape)
            if multi and shape:
                axes, nb = self._batch_parts()
                local_shards = max(1, nb // jax.process_count())
                if k in self.feed_shardings:
                    spec = self._spec(*self.feed_shardings[k])
                elif shape[0] % local_shards == 0:
                    spec = self._spec(
                        axes[0] if len(axes) == 1 else tuple(axes))
                else:
                    raise ValueError(
                        f"multi-process feed {k!r} with local leading dim "
                        f"{shape[0]} does not divide over this process's "
                        f"{local_shards} batch shard(s); pad the local "
                        "batch, or declare the feed's sharding explicitly "
                        "(feed_shardings={name: ()} for a replicated "
                        "value)")
                out[k] = self._put(v, self._nsh(spec))
            else:
                out[k] = self._put(v, self.feed_sharding(shape, name=k))
        return out

    def place_scope(self, scope_vals: Dict):
        """Scope values under their shardings (`_put` hands back one that
        sits there already). The executor sends only the names it cannot
        see a step under this plan to have left: `jit` below returns every
        mutable and created name under `scope_sharding(name)`, and that is
        what it recognises them by."""
        return {k: self._put(v, self.scope_sharding(k))
                for k, v in scope_vals.items()}

    def constrain(self, op, env) -> None:
        """Re-assert shardings on sharded-param outputs so GSPMD keeps TP
        layouts stable through the step (with_sharding_constraint)."""
        if not self.param_shardings:
            return
        import jax
        for name in op.output_names():
            if name in self.param_shardings:
                env[name] = jax.lax.with_sharding_constraint(
                    env[name], self.scope_sharding(name))

    def jit(self, fn, mutable, created, readonly, feed_shapes):
        import jax

        mut_sh = {n: self.scope_sharding(n) for n in mutable}
        ro_sh = {n: self.scope_sharding(n) for n in readonly}
        feed_sh = {n: self.feed_sharding(s, name=n)
                   for n, s in feed_shapes.items()}
        out_sh = dict(mut_sh)
        for n in created:
            out_sh[n] = self.scope_sharding(n)
        rep = self._nsh(self._spec())

        return jax.jit(
            fn,
            in_shardings=(mut_sh, ro_sh, feed_sh, rep),
            out_shardings=(out_sh, None, rep, None),
            donate_argnums=(0,))


class CollectiveSpmdPlan(ShardingPlan):
    """Explicit-SPMD execution: the whole block runs under shard_map over a
    mesh axis, so each shard executes the program replica-style — the
    TPU-native analog of the reference's one-process-per-device collective
    mode (transpiler/collective.py GradAllReduce + paddle.distributed.launch).

    Unlike the GSPMD ShardingPlan (where the compiler inserts gradient
    reductions), nothing is synchronized implicitly: programs must carry
    explicit c_allreduce_* ops on their gradients (inserted by
    fleet.CollectiveOptimizer or transpiler.collective.GradAllReduce),
    exactly as reference multi-process programs must. The c_* lowering rules
    (ops/collective_ops.py) see `spmd_axes` on the LowerContext and emit
    psum/all_gather/... over the named axis, which XLA maps onto ICI rings.
    """

    def __init__(self, nranks: Optional[int] = None, axis_name: str = "dp",
                 devices=None, inter_nranks: int = 1):
        """inter_nranks > 1 = hierarchical allreduce (reference
        build_strategy.h:133-139): the replica axis splits into
        (axis_inter, axis_intra) mesh axes and collectives reduce over
        both — numerically identical, and on a DCN-spanning mesh the
        intra axis rides ICI while only the inter stage crosses DCN."""
        inter = max(1, int(inter_nranks))
        if inter > 1:
            import jax
            n = nranks if nranks is not None else len(devices or
                                                      jax.devices())
            if n % inter != 0:
                raise ValueError(
                    f"nranks {n} not divisible by "
                    f"hierarchical inter_nranks {inter}")
            super().__init__(
                mesh_shape=(inter, n // inter),
                axis_names=(f"{axis_name}_inter", f"{axis_name}_intra"),
                places=n, devices=devices)
            self.spmd_axes = self.axis_names
        else:
            super().__init__(mesh_shape=None, axis_names=(axis_name,),
                             places=nranks, devices=devices)
            self.spmd_axes = (axis_name,)

    def constrain(self, op, env) -> None:
        pass  # inside shard_map there are no global shardings to assert

    def _batch_parts(self):
        # SPMD feeds shard over ALL replica axes (feed_spec below) —
        # including the (inter, intra) pair in hierarchical mode
        n = 1
        for a in self.spmd_axes:
            n *= self.mesh.shape[a]
        return tuple(self.spmd_axes), n

    def jit(self, fn, mutable, created, readonly, feed_shapes):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        # a single replica axis, or the (inter, intra) hierarchy — lax
        # collectives accept the axis-name tuple directly
        axis = self.spmd_axes[0] if len(self.spmd_axes) == 1 \
            else tuple(self.spmd_axes)
        n = 1
        for a in self.spmd_axes:
            n *= self.mesh.shape[a]

        def feed_spec(shape):
            return P(axis) if shape and shape[0] % n == 0 else P()

        feed_specs = {k: feed_spec(s) for k, s in feed_shapes.items()}
        mut_specs = {k: P() for k in mutable}
        ro_specs = {k: P() for k in readonly}
        out_mut_specs = {k: P() for k in list(mutable) + list(created)}

        def spmd_fn(mut, ro, feed, key):
            # per-shard rng stream (dropout masks differ across replicas,
            # like per-trainer seeds in the reference)
            idx = jax.lax.axis_index(self.spmd_axes[0])
            for a in self.spmd_axes[1:]:
                idx = idx * self.mesh.shape[a] + jax.lax.axis_index(a)
            local_key = jax.random.fold_in(key, idx)
            new_mut, fetches, _, flags = fn(mut, ro, feed, local_key)
            # fetch semantics match single-process training: scalar float
            # fetches (losses/metrics on the sharded batch) are averaged
            # over shards; everything else is gathered along dim 0 so the
            # full batch is reassembled in order — the analog of the
            # reference's FetchOpHandle merging per-device fetch tensors
            # (details/fetch_op_handle.cc)
            outs = []
            for f in fetches:
                f = jnp.asarray(f)
                if f.size == 1 and jnp.issubdtype(f.dtype, jnp.inexact):
                    outs.append(jax.lax.pmean(f, axis))
                elif f.ndim == 0:
                    outs.append(jax.lax.pmax(f, axis))
                else:
                    outs.append(jax.lax.all_gather(f, axis, tiled=True))
            flags = {k: jax.lax.pmin(jnp.asarray(v).astype(jnp.int32), axis)
                     for k, v in flags.items()}
            new_key = jax.random.fold_in(key, 0x5eed)  # from the global key
            return new_mut, outs, new_key, flags

        smapped = jax.shard_map(
            spmd_fn, mesh=self.mesh,
            in_specs=(mut_specs, ro_specs, feed_specs, P()),
            out_specs=(out_mut_specs, P(), P(), P()),
            check_vma=False)
        return jax.jit(smapped, donate_argnums=(0,))


# Megatron-style tensor-parallel layout for the GPT decode parameter
# pytree (gpt_decode.collect_gpt_params): column-parallel into the
# sharded dimension, row-parallel out of it, so each transformer block
# needs exactly ONE cross-chip reduction per matmul pair (GSPMD inserts
# the psum after out/mlp2). Keys are (w spec, b spec) PartitionSpec
# parts per projection; everything not listed (wte, wpe, layer norms)
# replicates — the embedding/head read full logits on every chip, which
# is what keeps the serving sampler a pure per-slot function.
_GPT_TP_SPECS = {
    "q": ((None, "tp"), ("tp",)),      # column: heads split over tp
    "k": ((None, "tp"), ("tp",)),
    "v": ((None, "tp"), ("tp",)),
    "out": (("tp", None), ()),         # row: contraction dim split
    "mlp1": ((None, "tp"), ("tp",)),   # column: ffn width split
    "mlp2": (("tp", None), ()),        # row
}


class ServingTPPlan:
    """Tensor-parallel mesh + partition placement for the serving
    engine's pjit-sharded executable family (prefill, fused decode
    chunk, verify, admit, release, swap) — the ParallelExecutor/
    DeviceWorker multi-device INFERENCE story, reusing the same GSPMD
    discipline the training ShardingPlan rides: annotate shardings on a
    jax.sharding.Mesh, let the compiler partition the single XLA
    computation and schedule the collectives over ICI.

    Layout (mesh_shape=(tp,), one axis "tp"):

      * params — Megatron TP (_GPT_TP_SPECS): q/k/v/mlp1 column-
        parallel, out/mlp2 row-parallel, embeddings + LNs replicated.
      * KV block arena (layers, 2, num_blocks, heads, bs, hd) — sharded
        on the HEADS axis, co-located with the q/k/v shards so paged
        attention never moves K/V across chips; per-chip HBM for the
        arena is pool_bytes / tp (the serve-a-bigger-model win).
      * page table, decode carry, threefry key rows, n-gram drafter
        state — REPLICATED, so every host-side scheduler/allocator path
        (admission, page mapping, prefix hashing, collect, swap) is
        mesh-oblivious and unchanged.

    Divisibility is enforced up front (heads % tp, ffn % tp): GSPMD
    would pad uneven shards, and padded reductions break the
    token-identity discipline the serving tests pin.
    """

    def __init__(self, cfg, mesh_shape: Tuple[int, ...],
                 devices=None, axis_name: str = "tp"):
        import jax
        import numpy as np
        from jax.sharding import Mesh

        mesh_shape = tuple(int(m) for m in mesh_shape)
        if len(mesh_shape) != 1 or mesh_shape[0] < 1:
            raise ValueError(
                f"serving mesh_shape must be a 1-tuple (tp,) with "
                f"tp >= 1, got {mesh_shape}")
        self.tp = mesh_shape[0]
        self.mesh_shape = mesh_shape
        self.axis_name = axis_name
        devs = list(devices if devices is not None else jax.devices())
        if self.tp > len(devs):
            raise ValueError(
                f"mesh_shape {mesh_shape} needs {self.tp} devices but "
                f"only {len(devs)} are visible (on CPU, set XLA_FLAGS="
                f"--xla_force_host_platform_device_count=N)")
        if cfg.heads % self.tp:
            raise ValueError(
                f"cfg.heads {cfg.heads} not divisible by tp {self.tp} "
                "— attention heads shard evenly or not at all")
        if cfg.ffn % self.tp:
            raise ValueError(
                f"cfg.ffn {cfg.ffn} not divisible by tp {self.tp}")
        self.mesh = Mesh(np.asarray(devs[:self.tp]), (axis_name,))

    # -- shardings -----------------------------------------------------------

    def _nsh(self, *parts):
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(self.mesh, PartitionSpec(*parts))

    @property
    def replicated(self):
        return self._nsh()

    @property
    def arena_sharding(self):
        """(layers, 2, num_blocks, heads, bs, hd): heads on tp."""
        return self._nsh(None, None, None, self.axis_name)

    @property
    def payload_sharding(self):
        """Swap-out payload (layers, 2, P, heads, bs, hd): heads on tp
        — BY CONSTRUCTION the same per-head split as the arena it was
        gathered from (aliased so the two layouts can never diverge)."""
        return self.arena_sharding

    def adapter_shardings(self, nm: str):
        """(A, B) NamedShardings for one projection's LoRA pool leaves —
        A (num_adapters, layers, in, rank), B (num_adapters, layers,
        rank, out) — placed so the low-rank path composes with the
        Megatron layout with ZERO extra collectives: column-parallel
        projections (q/k/v/mlp1, out axis split) replicate the tiny A
        and shard B on its out axis, so x@A@B lands pre-split exactly
        like x@W's columns; row-parallel projections (out/mlp2, in axis
        split) shard A on its in axis and replicate B, so each chip's
        partial x@A rides the SAME psum the base matmul already pays.
        The rank axis never shards (no divisibility demand on r); the
        in/out axes inherit the heads%tp / ffn%tp checks from
        construction (hidden = heads*head_dim)."""
        wspec, _ = _GPT_TP_SPECS[nm]
        if wspec == (None, "tp"):               # column-parallel
            return (self._nsh(),
                    self._nsh(None, None, None, "tp"))
        return (self._nsh(None, None, "tp", None),   # row-parallel
                self._nsh())

    # -- placement -----------------------------------------------------------

    def shard_params(self, params):
        """device_put the GPT decode pytree onto the mesh under the
        Megatron TP layout (embeddings/LNs replicated). Weight-only
        int8 projections (gpt_decode.quantize_params: {"w_q", "w_s",
        "b"}) shard w_q exactly as the fp32 w would, and the
        per-output-channel scale vector rides the BIAS spec — scales
        and bias live on the same (output) axis, so column-parallel
        scales split over tp with their channels and row-parallel
        scales replicate."""
        import jax

        def put(v, *parts):
            return jax.device_put(v, self._nsh(*parts))

        out = {"wte": put(params["wte"]), "wpe": put(params["wpe"]),
               "lnf": {k: put(v) for k, v in params["lnf"].items()},
               "blocks": []}
        for blk in params["blocks"]:
            nb = {"ln1": {k: put(v) for k, v in blk["ln1"].items()},
                  "ln2": {k: put(v) for k, v in blk["ln2"].items()}}
            for nm, (wspec, bspec) in _GPT_TP_SPECS.items():
                if "w_q" in blk[nm]:
                    nb[nm] = {"w_q": put(blk[nm]["w_q"], *wspec),
                              "w_s": put(blk[nm]["w_s"], *bspec),
                              "b": put(blk[nm]["b"], *bspec)}
                else:
                    nb[nm] = {"w": put(blk[nm]["w"], *wspec),
                              "b": put(blk[nm]["b"], *bspec)}
            out["blocks"].append(nb)
        return out

    def shard_arena(self, arena):
        """Place the KV block arena heads-sharded over the mesh (a
        quantized pool's (data, scales) pytree shards both leaves —
        device_put broadcasts the single sharding)."""
        import jax
        return jax.device_put(arena, self.arena_sharding)

    def replicate(self, tree):
        """device_put a pytree fully replicated (page table, decode
        carry, sampler keys, drafter state — the host-logic surfaces)."""
        import jax
        rep = self.replicated
        return jax.tree_util.tree_map(
            lambda v: jax.device_put(v, rep), tree)

    # -- in-graph constraints ------------------------------------------------
    #
    # Applied to every jitted entry point's outputs (and, through the
    # kernels' arena_constraint hook, inside the fused chunk scan): the
    # donated buffers must come back with EXACTLY the layout they went
    # in with, or XLA re-lays the arena out mid-pipeline and donation
    # degrades to a copy.

    def constrain_arena(self, arena):
        """with_sharding_constraint(heads on tp) over the arena — the
        bare data array, or the (int8 data, f32 scale plane) pytree of
        a quantized pool (the heads axis is dim 3 in both leaves, so
        one spec pins both)."""
        import jax
        return jax.tree_util.tree_map(
            lambda a: jax.lax.with_sharding_constraint(
                a, self.arena_sharding), arena)

    def constrain_payload(self, payload):
        import jax
        return jax.tree_util.tree_map(
            lambda p: jax.lax.with_sharding_constraint(
                p, self.payload_sharding), payload)

    def constrain_rep(self, tree):
        """with_sharding_constraint(replicated) over a pytree."""
        import jax
        rep = self.replicated
        return jax.tree_util.tree_map(
            lambda v: jax.lax.with_sharding_constraint(v, rep), tree)
