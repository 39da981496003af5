"""Executor: lowers a whole Program block to ONE jitted XLA computation.

Replaces the reference's op-by-op C++ interpreter (paddle/fluid/framework/
executor.cc:172 Executor::Run / :397 RunPreparedContext) with the TPU-idiomatic
model: trace every op's JAX lowering rule into a single function

    (mutable_scope, readonly_scope, feed, rng_key) -> (new_scope, fetches)

jit it with XLA, donate the mutable scope buffers (param updates reuse HBM
in-place — the analog of the reference's in-place optimizer ops + buffer-reuse
passes, ir/memory_optimize_pass/), and cache the executable keyed on
(program version, feed signature). The reference's GarbageCollector
(executor.cc:411) is unnecessary: XLA liveness does it at compile time.

Scope maps var name -> jax.Array and persists across runs
(reference: framework/scope.h:46, python global_scope executor.py:38).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from .core import Program, Variable, default_main_program
from .registry import LowerContext, lower_op, get_op_def
from ..observability.compile_log import compile_log
from ..observability.metrics import get_registry
from ..observability.tracer import get_tracer, trace_span
from ..observability import train_stats as _train_stats

__all__ = ["Scope", "Executor", "global_scope", "scope_guard",
           "as_jax_function"]

_prng_default_set = False


def _ensure_prng_default():
    """Default to the hardware rbg PRNG: threefry key derivation costs real
    step time on TPU (~7% of a BERT-base step for dropout masks); rbg is
    free and still deterministic per key. Respect an explicit user setting
    via JAX_DEFAULT_PRNG_IMPL or FLAGS_prng_impl. Lazy so that importing
    paddle_tpu has no jax side effects."""
    global _prng_default_set
    if _prng_default_set:
        return
    _prng_default_set = True
    import os

    if os.environ.get("JAX_DEFAULT_PRNG_IMPL"):
        return  # jax already honored the user's env var
    import jax

    jax.config.update("jax_default_prng_impl",
                      os.environ.get("FLAGS_prng_impl", "rbg"))


class Scope:
    """name -> device array map; values persist across Executor.run calls."""

    def __init__(self):
        self._vars: Dict[str, Any] = {}
        # name -> the sharding plan under whose layout the value is KNOWN
        # to sit: a step compiled under that plan returned it, or the
        # executor placed it for that plan. Every other write forgets the
        # name, so the next run under a plan places it again. The scope
        # holds the note, not the executor: it dies with the scope and no
        # array is referenced twice.
        self._placed_for: Dict[str, Any] = {}

    def find_var(self, name: str):
        return self._vars.get(name)

    def set_var(self, name: str, value) -> None:
        self._vars[name] = value
        if self._placed_for:
            self._placed_for.pop(name, None)

    def erase(self, name: str) -> None:
        self._vars.pop(name, None)
        self._placed_for.pop(name, None)

    def _set_placed(self, values: Dict[str, Any], plan) -> None:
        """The executor's own write: `values` as a step compiled under
        `plan` returned them (or as placed for it); plan None is a plain
        write of them all."""
        self._vars.update(values)
        if plan is not None:
            self._placed_for.update(dict.fromkeys(values, plan))
        elif self._placed_for:
            for name in values:
                self._placed_for.pop(name, None)

    def var_names(self) -> List[str]:
        return list(self._vars)

    def __contains__(self, name: str) -> bool:
        return name in self._vars

    def get_numpy(self, name: str) -> np.ndarray:
        v = self._vars[name]
        return np.asarray(v)


_global_scope = Scope()
_scope_stack = threading.local()


def global_scope() -> Scope:
    stack = getattr(_scope_stack, "stack", None)
    if stack:
        return stack[-1]
    return _global_scope


class scope_guard:
    def __init__(self, scope: Scope):
        self._scope = scope

    def __enter__(self):
        if not hasattr(_scope_stack, "stack"):
            _scope_stack.stack = []
        _scope_stack.stack.append(self._scope)
        return self

    def __exit__(self, *exc):
        _scope_stack.stack.pop()
        return False


# ---------------------------------------------------------------------------


def classify_persistables(program, feed_names: set, fetch_names):
    """Classify persistable vars for the whole-block jit: a var must come
    IN from the scope only if some op reads it before any op writes it;
    vars defined by earlier ops (e.g. params created by startup init ops)
    are internal. Returns (mutable, created, readonly):
      mutable  — updated in place: donated in, returned out
      created  — produced by this program (startup init): out only
      readonly — read-only constants from the scope
    Shared by Executor.run and inference.export_train_step so the exported
    artifact is the Executor's own step, argument-for-argument."""
    from .registry import _HOST_OPS

    blk = program.global_block

    def _expand(ops):
        # Flatten macro ops' sub-blocks for read/write classification
        # (sub-block reads are reads of the enclosing op). The macro op
        # is yielded BEFORE its sub-block ops: its implicit reads
        # (carry-in / branch pass-through) happen before any write
        # inside it.
        for op in ops:
            yield op
            for key in ("sub_block", "sub_block_t", "sub_block_f"):
                if key in op.attrs:
                    yield from _expand(program.blocks[op.attrs[key]].ops)

    written = set()
    external_reads = set()
    written_so_far = set(feed_names)
    sub_local = set()
    for b in program.blocks[1:]:
        sub_local.update(b.vars)
    macro_attrs = ("sub_block", "sub_block_t", "sub_block_f")
    for op in _expand(blk.ops):
        if op.type in ("feed", "fetch") or op.type in _HOST_OPS:
            continue
        reads = list(op.input_names())
        if any(k in op.attrs for k in macro_attrs):
            # a macro op's outputs are also implicit reads: while carries
            # state in, conditional_block's untaken branch passes values
            # through
            reads += op.output_names()
        for n in reads:
            if n not in written_so_far and n not in sub_local:
                external_reads.add(n)
        outs = [n for n in op.output_names() if n not in sub_local]
        written.update(outs)
        written_so_far.update(op.output_names())
    for n in fetch_names:
        if n not in written_so_far:
            external_reads.add(n)

    persist = {v.name for v in blk.vars.values() if v.persistable}
    mutable = sorted((persist & written & external_reads) - feed_names)
    created = sorted((persist & written) - set(mutable) - feed_names)
    readonly = sorted((persist & external_reads)
                      - set(mutable) - feed_names)
    return mutable, created, readonly


def _as_feed_array(value, var: Optional[Variable], on_host: bool = False):
    """A feed in its variable's dtype. on_host leaves a host value on the
    host, in the dtype the device will hold, for a sharding plan to send
    to its shards in one transfer; a device array is returned as it is."""
    import jax
    import jax.numpy as jnp
    if isinstance(value, jax.Array):
        # device-resident feed: no host round-trip
        if var is not None and var.dtype is not None and \
                str(value.dtype) != var.dtype:
            value = value.astype(var.dtype)
        return value
    arr = np.asarray(value)
    if var is not None and var.dtype is not None:
        arr = arr.astype(var.dtype, copy=False)
    if on_host:
        return arr.astype(jax.dtypes.canonicalize_dtype(arr.dtype),
                          copy=False)
    return jnp.asarray(arr)


class Executor:
    """fluid.Executor analog. `place` is accepted for API compatibility but
    devices are managed by JAX; pass place=None for the default device."""

    def __init__(self, place=None, donate: bool = True,
                 cache_capacity: Optional[int] = None):
        """donate=False keeps input param buffers alive after run — needed
        when callers hold aliases to scope arrays (the dygraph optimizer
        path), at the cost of double-buffered updates.

        cache_capacity bounds the compiled-executable cache (LRU): a
        long-running varied-shape service must not leak executables.
        Default from FLAGS_executor_cache_capacity (64). Pair with
        reader/bucketing.py so a ragged stream converges to <= #buckets
        entries instead of churning the cache."""
        import os as _os
        from collections import OrderedDict, deque
        self.place = place
        self._donate = donate
        self._cache: "OrderedDict[Any, Any]" = OrderedDict()
        self._classify_cache: "OrderedDict[Any, Any]" = OrderedDict()
        self._compile_stats: Dict[Any, Dict[str, Any]] = {}
        self._cache_capacity = int(
            cache_capacity if cache_capacity is not None
            else _os.environ.get("FLAGS_executor_cache_capacity", "64"))
        self.compile_count = 0  # distinct compilations (tests/telemetry)
        self.run_count = 0  # runs started: the ordinal on executor/run
        # run(validate=True) pre-flight reports, keyed like the compile
        # cache (program uid, version, feed set, fetch list); LRU via
        # the shared _memo helper
        self._validated: "OrderedDict[Any, Any]" = OrderedDict()
        self._compiled_uids = set()  # programs ever compiled, cache-
        # residency-independent: a miss for a known uid whose entries
        # were all LRU-evicted is a recompile (cause="evicted"), not a
        # first compile — cache churn is exactly what the counter is for
        # structured "why" records for misses after a program's first
        # compile (recompilation attribution); also mirrored into the
        # process-wide train_stats.recompile_log() for /trainz
        self.recompile_log: "deque[Dict[str, Any]]" = deque(maxlen=64)
        self.last_fetch_names: List[str] = []  # incl. telemetry extras
        _ensure_prng_default()

    def _memo(self, cache, key, build):
        """LRU memoize into `cache` bounded by the shared capacity."""
        hit = cache.get(key)
        if hit is not None:
            cache.move_to_end(key)
            return hit
        val = build()
        cache[key] = val
        while len(cache) > self._cache_capacity:
            cache.popitem(last=False)
        return val

    # -- public API ---------------------------------------------------------
    def run(self, program: Optional[Program] = None,
            feed: Optional[Dict[str, Any]] = None,
            fetch_list: Optional[Sequence[Union[str, Variable]]] = None,
            scope: Optional[Scope] = None,
            return_numpy: bool = True,
            validate: bool = False):
        # Progress heartbeat for the stall watchdog (observability/
        # watchdog.py): inflight goes up while a run is on the device,
        # runs_total advances when it returns. Busy-with-no-progress for
        # longer than the stall threshold triggers a flight record.
        # labels() materializes both series BEFORE the run body — a hang
        # in the very first run must already be visible to the monitor
        # (runs=0, inflight=1), not hidden behind a counter that never
        # got created. Families are re-fetched per run (not cached) so a
        # registry reset can't orphan the heartbeat — the cost is two
        # dict lookups against ms-scale dispatch.
        reg = get_registry()
        runs = reg.counter("executor_runs_total",
                           "Executor.run calls completed").labels()
        inflight = reg.gauge("executor_inflight_runs",
                             "Executor.run calls currently "
                             "executing").labels()
        inflight.inc()
        try:
            # one span per run, the parent of the run's phase spans; it
            # carries this executor's ordinal of the run and, in the
            # ring, the request_id of an ambient serving request scope
            step = self.run_count
            self.run_count += 1
            with trace_span("executor/run", "executor", {"step": step}):
                out = self._run_impl(program, feed, fetch_list, scope,
                                     return_numpy, validate)
            runs.inc()
            return out
        finally:
            inflight.dec()

    def _validate_preflight(self, program, feed, fetch_names):
        """Opt-in static verification before lowering/compiling: a
        malformed program raises ProgramVerificationError with the
        diagnostic (code + op + var), not an XLA/jit traceback. Memoized
        per (program, version, feed set, fetch list) so steady-state runs
        pay two dict lookups; verification itself is read-only, so the
        compile cache and program bytes are untouched either way."""
        from ..analysis import verify_program
        key = (getattr(program, "_uid", id(program)), program.version,
               frozenset(feed), tuple(fetch_names))
        cached = self._memo(
            self._validated, key,
            lambda: verify_program(program, fetch_list=fetch_names,
                                   feed_names=set(feed)))
        if not cached.ok:
            from ..analysis import ProgramVerificationError
            raise ProgramVerificationError(cached, program)

    def _run_impl(self, program, feed, fetch_list, scope, return_numpy,
                  validate=False):
        from ..compiler import CompiledProgram  # lazy import

        reg = get_registry()
        if program is None:
            program = default_main_program()

        dist_plan = None
        if isinstance(program, CompiledProgram):
            dist_plan = program._plan()
            program = program._program

        scope = scope or global_scope()
        feed = feed or {}

        # pre-flight BEFORE any dispatch branch — the PS path below
        # re-enters run() for the jitted half and must not silently
        # bypass a requested validation
        if validate:
            self._validate_preflight(
                program, feed,
                [f.name if isinstance(f, Variable) else f
                 for f in (fetch_list or [])])

        # parameter-server trainer program: jitted step bracketed by host
        # push/pull through the native KV service (transpiler/
        # distribute_transpiler.py)
        ps_plan = getattr(program, "_ps_plan", None)
        if ps_plan is not None and not getattr(self, "_ps_reentry", False):
            return self._run_ps(program, feed, fetch_list, scope,
                                return_numpy, ps_plan)

        # Collective-transpiled programs carry the replica count they were
        # rewritten for; running on a different mesh width silently mis-
        # scales gradients, so refuse.
        transpiled_n = getattr(program, "_collective_nranks", None)
        if transpiled_n is not None:
            spmd_axes = getattr(dist_plan, "spmd_axes", ()) \
                if dist_plan else ()
            mesh_n = 1
            for a in spmd_axes:  # hierarchical mode: product of both axes
                mesh_n *= int(dist_plan.mesh.shape[a])
            if mesh_n != transpiled_n:
                raise ValueError(
                    f"program was collective-transpiled for "
                    f"{transpiled_n} replicas but is running on "
                    f"{mesh_n} mesh shard(s); use CompiledProgram"
                    f".with_collective(nranks={transpiled_n})")
        fetch_names = [f.name if isinstance(f, Variable) else f
                       for f in (fetch_list or [])]

        blk = program.global_block

        # The phases of a run are spans under executor/run, opened where
        # the work is and never overlapping: prepare (everything up to
        # the compile-cache lookup), compile (a miss), place (scope reads
        # and host->device placement), dispatch (the compiled step's
        # call, which returns futures), writeback (the donated inputs
        # let go, the scope's update, host post ops, finite flags), fetch
        # (the wait for the device), release (what the run still holds).
        with trace_span("executor/prepare", "executor"):
            # Host-boundary ops (save/load/send/recv/readers) run eagerly
            # against the scope: the prefix before the first compute op
            # now, the suffix after the jitted computation. A host op
            # sandwiched between compute ops would need the op-by-op
            # interpreter the whole-block-jit design removed — reference
            # programs (save/load programs, transpiler-emitted trainer
            # prologues/epilogues) only use the prefix/suffix forms.
            from .registry import _HOST_OPS
            host_pre, host_post = [], []
            compute_seen = False
            for op in blk.ops:
                if op.type in _HOST_OPS:
                    (host_post if compute_seen else host_pre).append(op)
                elif op.type not in ("feed", "fetch"):
                    compute_seen = True
                    if host_post:
                        raise RuntimeError(
                            f"host-boundary op(s) "
                            f"{[o.type for o in host_post]} appear between "
                            f"compute ops; split the program (the reference "
                            f"emits separate save/load programs too)")
            for op in host_pre:
                with trace_span(f"host/{op.type}", "host"):
                    _HOST_OPS[op.type](op, scope, feed)
            if not compute_seen:
                # host-only program (save/load programs): everything
                # already ran via host_pre above
                return [np.asarray(scope.find_var(f)) if return_numpy
                        else scope.find_var(f) for f in fetch_names]

            # Training telemetry (observability/train_stats.py): a program
            # whose minimize() attached the tap carries the loss/grad-norm/
            # sentinel-flag var names; while a StepLogger is installed
            # those ride along in the SAME fetch tuple — one jitted
            # computation, no extra device->host transfer. No logger =>
            # fetch list is exactly the user's (the no-op path; XLA
            # dead-code-eliminates the unfetched telemetry ops).
            tele = getattr(program, "_train_telemetry", None)
            tele_logger = _train_stats.get_step_logger() if tele else None
            all_fetch = list(fetch_names)
            if tele_logger is not None:
                seen = set(all_fetch)
                for k in ("loss", "grad_norm", "flag", "lr"):
                    n = tele.get(k)
                    if n and n not in seen:
                        all_fetch.append(n)
                        seen.add(n)
            self.last_fetch_names = list(all_fetch)

            # classify_persistables walks every op/var — ~6.5 ms of pure
            # Python at ResNet-50 scale, re-done identically every step
            # (measured: the bulk of the r3 "unexplained 4.6% framework
            # overhead"). Same key ingredients as the compile cache, so
            # memoize alongside it.
            cls_key = (getattr(program, "_uid", id(program)),
                       program.version, frozenset(feed), tuple(all_fetch))
            mutable, created, readonly = self._memo(
                self._classify_cache, cls_key,
                lambda: classify_persistables(program, set(feed),
                                              all_fetch))

            # ensure rng state
            if "@RNG@" not in scope:
                import jax
                scope.set_var("@RNG@",
                              jax.random.PRNGKey(program.random_seed))

            def _sig(v):
                if hasattr(v, "shape") and hasattr(v, "dtype"):
                    return tuple(v.shape), str(v.dtype)
                a = np.asarray(v)
                return tuple(a.shape), str(a.dtype)

            feed_sig = tuple(sorted((k,) + _sig(v) for k, v in feed.items()))
            cache_key = (getattr(program, "_uid", id(program)),
                         program.version, feed_sig,
                         tuple(all_fetch), tuple(mutable), tuple(readonly),
                         id(dist_plan) if dist_plan else None)

            # Compile-cache lookup with hit/miss/eviction counters and, on
            # every miss after a program's first compile, recompilation
            # attribution: which ingredient changed vs. the nearest cached
            # key. Counters are always on (StepLogger or not) — families
            # are re-fetched per run so a registry reset can't orphan them.
            compiled = self._cache.get(cache_key)
            was_miss = compiled is None
            if not was_miss:
                self._cache.move_to_end(cache_key)
                reg.counter("executor_cache_hits_total",
                            "compile-cache hits").inc()
            else:
                reg.counter("executor_cache_misses_total",
                            "compile-cache misses (compilations)").inc()
                cause, detail = self._attribute_recompile(cache_key)
                if cause != "first_compile":
                    reg.counter(
                        "executor_recompiles_total",
                        "compile-cache misses after a program's first "
                        "compile, by cause").labels(cause=cause).inc()
                    rec = {"ts": time.time(), "cause": cause,
                           "detail": detail,
                           "program": str(cache_key[0])[:8],
                           "compile_index": self.compile_count + 1}
                    self.recompile_log.append(rec)
                    _train_stats.record_recompile(rec)
                feed_shapes = {k: _sig(v)[0] for k, v in feed.items()}
                self.compile_count += 1

        if was_miss:
            # the Program lowered to a jitted callable, no more: jax traces,
            # lowers and compiles it (or loads it) inside the first
            # `executor/dispatch` below, under the compile log's
            # `compile/trace`, `compile/lower` and `compile/backend` spans
            with trace_span("executor/compile", "executor",
                            {"ops": len(blk.ops),
                             "fetches": len(all_fetch),
                             "cause": cause}):
                compiled = self._compile(program, feed_shapes, all_fetch,
                                         mutable, created, readonly,
                                         dist_plan, cause)
            self._cache[cache_key] = compiled
            self._compiled_uids.add(cache_key[0])
            while len(self._cache) > self._cache_capacity:
                old_key, _ = self._cache.popitem(last=False)
                self._compile_stats.pop(old_key, None)
                reg.counter("executor_cache_evictions_total",
                            "compile-cache LRU evictions").inc()

        with trace_span("executor/place", "executor"):
            reg.gauge("executor_cache_size",
                      "compiled executables cached").set(len(self._cache))
            # Under a plan, a name goes through the plan's _put only where
            # the scope does not say that a step under this plan left it
            # (Scope._placed_for): the first step after startup, a value
            # the user or another program wrote in between, a second
            # scope. A read-only value placed here is written back, as a
            # step's outputs are, so it is transferred once and a later
            # set_var of it is seen like any other.
            known = scope._placed_for if dist_plan is not None else None
            mut_in: Dict[str, Any] = {}
            # the read-only values and, until it is taken out below, the
            # key: on a multi-process mesh it must be a GLOBAL replicated
            # array (every process holds the same key: startup ran with
            # the same seed everywhere), so it is placed like the rest
            ro_in: Dict[str, Any] = {}
            to_place: Dict[str, Any] = {}
            for names, vals in ((mutable, mut_in),
                                ((*readonly, "@RNG@"), ro_in)):
                for n in names:
                    val = scope.find_var(n)
                    if val is None:
                        raise RuntimeError(
                            f"persistable var {n!r} not initialized in "
                            "scope; run the startup program first")
                    if known is not None and known.get(n) is not dist_plan:
                        to_place[n] = val
                    vals[n] = val
            if to_place:
                to_place = dist_plan.place_scope(to_place)
                for n, val in to_place.items():
                    (mut_in if n in mut_in else ro_in)[n] = val
                # the step returns what it consumes; the rest stays placed
                scope._set_placed({n: v for n, v in to_place.items()
                                   if n in ro_in}, dist_plan)
            key = ro_in.pop("@RNG@")
            reg.counter("executor_scope_vars_placed_total",
                        "scope variables a run handed to its plan's _put "
                        "(not left in place by a step under the same "
                        "plan)").inc(len(to_place))
            if not to_place:
                reg.counter("executor_scope_in_place_runs_total",
                            "runs that found every scope variable in "
                            "place").inc()
            # nothing below may hold a donated input but mut_in
            del val, vals, to_place
            if dist_plan is not None:
                # host values go from the host to their shards in one
                # transfer; a device array under the plan's sharding
                # passes through
                feed_in = dist_plan.shard_feed(
                    {k: _as_feed_array(v, blk.vars.get(k), on_host=True)
                     for k, v in feed.items()})
            else:
                feed_in = {k: _as_feed_array(v, blk.vars.get(k))
                           for k, v in feed.items()}

        if getattr(self, "capture_hlo", False):
            # tools/comm_volume.py: optimized HLO with the SPMD partitioner's
            # collectives, captured without disturbing the jit cache
            try:
                with compile_log().probing():
                    self.last_hlo = compiled.lower(
                        mut_in, ro_in, feed_in, key).compile().as_text()
            except Exception as e:  # pipeline/custom callables
                self.last_hlo = None
                self.last_hlo_error = str(e)

        if tele_logger is not None and was_miss:
            # XLA cost/memory analysis for MFU + peak-per-compile
            # accounting. AOT lower+compile (before the call — donation
            # consumes mut_in buffers) — one extra compile per cache
            # miss, only while a StepLogger is installed.
            self._compile_stats[cache_key] = self._analyze_compile(
                compiled, mut_in, ro_in, feed_in, key, reg)

        t0 = time.perf_counter()
        with trace_span("executor/dispatch", "executor"):
            new_mut, fetches, new_key, finite_flags = compiled(
                mut_in, ro_in, feed_in, key)

        with trace_span("executor/writeback", "executor"):
            # The donated inputs go now, while the device computes: the
            # step consumed their buffers at dispatch, and with this
            # reference gone each is freed as the scope overwrites it
            # below (some hundreds of arrays, a shard a chip each), not
            # after the fetch with the chips waiting.
            del mut_in
            scope._set_placed(new_mut, dist_plan)
            scope._set_placed({"@RNG@": new_key}, dist_plan)

            for op in host_post:  # saves/sends see the post-step scope
                with trace_span(f"host/{op.type}", "host"):
                    _HOST_OPS[op.type](op, scope, feed)

            if finite_flags:
                for tag, ok in finite_flags.items():
                    if not bool(ok):
                        idx, op_type, var = tag.split(":", 2)
                        raise FloatingPointError(
                            f"nan/inf detected in output {var!r} of op "
                            f"#{idx} ({op_type}) — FLAGS_check_nan_inf")

        if tele_logger is not None:
            fetches = self._log_step_telemetry(
                tele, tele_logger, all_fetch, fetch_names, fetches,
                feed_in, scope, cache_key, was_miss, t0, reg)

        if return_numpy:
            from .selected_rows import to_dense
            with trace_span("executor/fetch", "executor"):
                out = [np.asarray(to_dense(f)) for f in fetches]
        else:
            out = list(fetches)
        with trace_span("executor/release", "executor"):
            # what the call still holds of the step goes here, where it
            # went when the function returned: the dictionaries, the
            # fetched device arrays and the keys (the donated inputs went
            # at the head of writeback)
            del ro_in, feed_in, new_mut, fetches, key, new_key
        return out

    # -- training telemetry (observability/train_stats.py) -------------------
    def _analyze_compile(self, compiled, mut_in, ro_in, feed_in, key, reg):
        """Flops + memory footprint of the executable just compiled, via
        the AOT path; best-effort (None fields when the backend or a
        dist_plan wrapper doesn't support analysis)."""
        stats: Dict[str, Any] = {"flops": None, "temp_bytes": None,
                                 "argument_bytes": None,
                                 "output_bytes": None, "peak_bytes": None}
        try:
            # a look at the executable, not a second one: on a miss it
            # comes before the first dispatch, and the log counts the
            # compile it causes as the program's (jax keeps it)
            with compile_log().probing():
                aot = compiled.lower(mut_in, ro_in, feed_in, key).compile()
            ca = aot.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            flops = float((ca or {}).get("flops", 0.0))
            stats["flops"] = flops if flops > 0 else None
            ma = aot.memory_analysis()
            if ma is not None:
                stats["temp_bytes"] = int(ma.temp_size_in_bytes)
                stats["argument_bytes"] = int(ma.argument_size_in_bytes)
                stats["output_bytes"] = int(ma.output_size_in_bytes)
                # peak estimate: args live across the computation plus
                # temps and outputs
                stats["peak_bytes"] = (stats["temp_bytes"]
                                       + stats["argument_bytes"]
                                       + stats["output_bytes"])
                reg.gauge("executor_compile_temp_bytes",
                          "XLA temp allocation of the last "
                          "compile").set(stats["temp_bytes"])
                reg.gauge("executor_compile_peak_bytes",
                          "estimated peak device bytes of the last "
                          "compile").set(stats["peak_bytes"])
        except Exception:
            pass
        return stats

    def _log_step_telemetry(self, tele, logger, all_fetch, fetch_names,
                            fetches, feed_in, scope, cache_key, was_miss,
                            t0, reg):
        """Convert the telemetry fetches (same output tuple as the user's)
        into one StepLogger record; returns the user-visible fetch slice.
        Reading the scalars blocks on the step — that sync IS the step
        timing; no additional device round trip happens."""
        by_name = dict(zip(all_fetch, fetches))

        def _scalar(name):
            if name is None or name not in by_name:
                return None
            try:
                return float(np.asarray(by_name[name]).ravel()[0])
            except (TypeError, ValueError, IndexError):
                return None

        loss = _scalar(tele.get("loss"))
        gnorm = _scalar(tele.get("grad_norm"))
        lr = _scalar(tele.get("lr"))
        flag = by_name.get(tele.get("flag"))
        finite = bool(np.asarray(flag).ravel()[0]) if flag is not None \
            else True
        step_time = time.perf_counter() - t0

        # batch size = the largest leading dim across feeds (a (1,)
        # scalar feed like an lr scale must not masquerade as the batch)
        examples = tokens = None
        dims = [int(v.shape[0]) for v in feed_in.values()
                if getattr(v, "shape", None)]
        if dims:
            examples = max(dims)
        # tokens = the LARGEST integer feed (the token ids), not the sum
        # — an integer label/mask feed alongside must not double-count
        int_sizes = [int(v.size) for v in feed_in.values()
                     if np.issubdtype(np.dtype(str(v.dtype)), np.integer)]
        if int_sizes:
            tokens = max(int_sizes)

        scope_bytes = 0
        for n in scope.var_names():
            v = scope.find_var(n)
            nb = getattr(v, "nbytes", None)
            if nb is None:
                nb = getattr(getattr(v, "values", None), "nbytes", 0)
            scope_bytes += int(nb or 0)
        reg.gauge("executor_scope_live_bytes",
                  "bytes held by scope device arrays").set(scope_bytes)

        logger.log_step(
            loss=loss, grad_norm=gnorm, lr=lr, finite=finite,
            step_time_s=step_time, examples=examples, tokens=tokens,
            compiled=was_miss,
            compile_stats=self._compile_stats.get(cache_key),
            scope_bytes=scope_bytes, program=str(cache_key[0])[:8])
        return fetches[:len(fetch_names)]

    def _attribute_recompile(self, key):
        """Why did this compile-cache miss happen? Compare against the
        nearest cached key (same program preferred) and name the first
        differing ingredient. Returns (cause, detail)."""
        uid, version, feed_sig, fetch, mutable, readonly, dist = key
        same_prog = [k for k in self._cache if k[0] == uid]
        if not same_prog:
            if uid in self._compiled_uids:
                return "evicted", {"cache_capacity": self._cache_capacity}
            return "first_compile", {}

        def _score(k):
            return sum(a == b for a, b in zip(k, key))

        near = max(same_prog, key=_score)
        if near[1] != version:
            return "program_version", {"from": near[1], "to": version}
        if near[2] != feed_sig:
            old = {n: (s, d) for n, s, d in near[2]}
            new = {n: (s, d) for n, s, d in feed_sig}
            for n in sorted(set(old) & set(new)):
                if old[n][0] != new[n][0]:
                    return "feed_shape", {"var": n,
                                          "from": list(old[n][0]),
                                          "to": list(new[n][0])}
            for n in sorted(set(old) & set(new)):
                if old[n][1] != new[n][1]:
                    return "feed_dtype", {"var": n, "from": old[n][1],
                                          "to": new[n][1]}
            return "feed_set", {"added": sorted(set(new) - set(old)),
                                "removed": sorted(set(old) - set(new))}
        if near[3] != fetch:
            return "fetch_list", {"added": sorted(set(fetch) - set(near[3])),
                                  "removed": sorted(set(near[3])
                                                    - set(fetch))}
        if near[4] != mutable or near[5] != readonly:
            return "scope_classification", {}
        if near[6] != dist:
            return "dist_plan", {}
        return "unknown", {}

    def _run_ps(self, program, feed, fetch_list, scope, return_numpy, plan):
        from .selected_rows import to_dense

        plan.ensure_init(scope)
        plan.before_step(scope, feed)
        user = [f.name if isinstance(f, Variable) else f
                for f in (fetch_list or [])]
        extra = [n for n in plan.extra_fetches() if n not in set(user)]
        self._ps_reentry = True
        try:
            raw = self.run(program, feed=feed, fetch_list=user + extra,
                           scope=scope, return_numpy=False)
        finally:
            self._ps_reentry = False
        fetched = dict(zip(user + extra, raw))
        plan.after_step(scope, fetched)
        outs = raw[:len(user)]
        if return_numpy:
            return [np.asarray(to_dense(o)) for o in outs]
        return outs

    # -- compilation ---------------------------------------------------------
    def _compile(self, program: Program, feed_shapes, fetch_names,
                 mutable, created, readonly, dist_plan, cause=None):
        """The Program lowered to a jitted callable (the span
        `executor/compile`): no XLA yet. jax traces, lowers and compiles
        it inside the first `executor/dispatch`; `fn` tells the compile
        log, as jax traces it, which program that executable is and the
        `cause` of the miss that built it."""
        import jax

        if getattr(program, "_pipeline", None) is not None:
            if dist_plan is not None:
                raise NotImplementedError(
                    "PipelineOptimizer programs manage their own 'pp' mesh "
                    "and cannot be combined with a CompiledProgram "
                    "distribution plan yet — run the pipelined Program "
                    "directly")
            from ..parallel.pipeline import compile_pipeline_step
            return compile_pipeline_step(
                program, program._pipeline, feed_shapes, fetch_names,
                mutable, created, readonly)

        from .registry import _HOST_OPS
        blk = program.global_block
        ops = [op for op in blk.ops
               if op.type not in ("feed", "fetch")
               and op.type not in _HOST_OPS]
        out_names = list(mutable) + list(created)

        check_nan_inf = os.environ.get("FLAGS_check_nan_inf", "0") == "1"
        log_tag = f"program:{str(getattr(program, '_uid', id(program)))[:8]}"

        def fn(mut_scope, ro_scope, feed_vals, rng_key):
            import jax.numpy as jnp

            compile_log().note_tag(log_tag, cause)      # trace time only
            env: Dict[str, Any] = {}
            env.update(ro_scope)
            env.update(mut_scope)
            env.update(feed_vals)
            ctx = LowerContext(rng_key=rng_key,
                               mesh=dist_plan.mesh if dist_plan else None,
                               spmd_axes=getattr(dist_plan, "spmd_axes", ())
                               if dist_plan else ())
            # Per-op host spans (name = op type, args = var names): the
            # whole-block-jit design lowers each op exactly once, at trace
            # time, so the spans land on the compiling run — the host-side
            # analog of the reference executor's per-op RecordEvent.
            # Their number grows with the program's ops, so they stay in
            # the ring and out of the profiler's trace (Tracer.span).
            # FLAGS_trace_ops=0 suppresses them while keeping run/compile
            # spans; checked at trace time, so enable tracing BEFORE the
            # first run of a program (cached executables re-trace nothing).
            tracer = get_tracer()
            trace_ops = (tracer.enabled
                         and os.environ.get("FLAGS_trace_ops", "1") != "0")
            finite_flags = {}
            for i, op in enumerate(ops):
                if trace_ops:
                    with tracer.span(op.type, "op",
                                     {"op_index": i,
                                      "inputs": ",".join(op.input_names()),
                                      "outputs": ",".join(op.output_names())}):
                        lower_op(ctx, op, env)
                else:
                    lower_op(ctx, op, env)
                if dist_plan is not None:
                    dist_plan.constrain(op, env)
                if check_nan_inf:
                    # FLAGS_check_nan_inf sanitizer
                    # (reference: operator.cc:949 CheckNanInf)
                    from .selected_rows import SelectedRows
                    for n in op.output_names():
                        v = env.get(n)
                        if isinstance(v, SelectedRows):
                            v = v.values
                        if v is not None and jnp.issubdtype(
                                jnp.asarray(v).dtype, jnp.inexact):
                            finite_flags[f"{i}:{op.type}:{n}"] = \
                                jnp.all(jnp.isfinite(v))
            from .selected_rows import to_dense
            new_mut = {n: env[n] for n in out_names}
            # fetched SelectedRows densify at the boundary (as_numpy
            # analog) — except names the PS runtime wants raw (rows+values
            # go over the wire, not a dense vocab-sized buffer)
            sparse_keep = getattr(program, "_sparse_fetch_names", set())
            fetches = [env[n] if n in sparse_keep else to_dense(env[n])
                       for n in fetch_names]
            new_key = jax.random.fold_in(rng_key, 0x5eed)
            return new_mut, fetches, new_key, finite_flags

        if dist_plan is not None:
            return dist_plan.jit(fn, mutable, created, readonly, feed_shapes)
        return jax.jit(fn, donate_argnums=(0,) if self._donate else ())

    # -- Trainer path: dataset-driven loops ----------------------------------
    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread: int = 0, debug: bool = False,
                           fetch_list=None, fetch_info=None,
                           print_period: int = 100):
        """Run one pass over `dataset` (reference executor.py:892 — the
        Trainer/DeviceWorker path, executor.cc:142 RunFromDataset). The
        reference's thread-per-core Hogwild workers become: C++ parser
        threads keep the channel full (`thread` sets their count), while
        the device step itself is the jitted program — one TPU chip
        executes batches back to back with no Python in the parse path."""
        if dataset is None:
            raise ValueError("dataset is required")
        if program is None:
            program = default_main_program()
        scope = scope or global_scope()
        if thread:
            dataset.set_thread(thread)
        fetch_names = [f.name if hasattr(f, "name") else f
                       for f in (fetch_list or [])]
        data_vars = {v.name: v for v in program.global_block.vars.values()
                     if v.is_data}

        dataset._start_epoch()
        step = 0
        last = None
        while True:
            batch = dataset._next_batch()
            if batch is None:
                break
            feed = {}
            for name, (vals, lod) in batch.items():
                var = data_vars.get(name)
                if var is None:
                    continue
                feed[name] = _slot_batch_to_array(var, vals, lod)
            last = self.run(program, feed=feed, fetch_list=fetch_names,
                            scope=scope)
            step += 1
            if debug and fetch_names and step % print_period == 0:
                infos = fetch_info or fetch_names
                msg = ", ".join(f"{i}={np.ravel(v)[0]:.6f}"
                                for i, v in zip(infos, last))
                print(f"[train_from_dataset] step {step}: {msg}")
        return last

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread: int = 0, debug: bool = False,
                           fetch_list=None, fetch_info=None,
                           print_period: int = 100):
        """reference executor.py:815 — same loop, typically with a
        clone(for_test=True) program."""
        return self.train_from_dataset(program, dataset, scope, thread,
                                       debug, fetch_list, fetch_info,
                                       print_period)

    # -- utilities -----------------------------------------------------------
    def close(self):
        self._cache.clear()
        self._compile_stats.clear()


def _slot_batch_to_array(var: Variable, vals: np.ndarray,
                         lod: np.ndarray) -> np.ndarray:
    """Ragged slot -> static-shape batch for XLA. A var shaped (-1, d...)
    takes d=prod(trailing dims) values per record: exact-length records
    reshape for free; ragged records pad with 0 / truncate to d (the LoD
    ragged batching of the reference becomes pad-to-static)."""
    b = len(lod) - 1
    per = 1
    for d in (var.shape[1:] if var.shape and len(var.shape) > 1 else ()):
        per *= d
    counts = np.diff(lod)
    if np.all(counts == per):
        arr = vals.reshape((b,) + tuple(var.shape[1:]))
    else:
        arr = np.zeros((b, per), vals.dtype)
        for i in range(b):
            n = min(int(counts[i]), per)
            arr[i, :n] = vals[lod[i]:lod[i] + n]
        arr = arr.reshape((b,) + tuple(var.shape[1:]))
    return arr.astype(var.dtype, copy=False)


def as_jax_function(program: Program, fetch_list, is_test: bool = True,
                    seed: int = 0):
    """Export a program block as a pure JAX function
    fn(scope: dict[str, Array], feed: dict[str, Array]) -> list[Array].

    The inference-export analog of the reference's NaiveExecutor path: the
    returned fn is jit/vmap/grad-compatible and closes over nothing mutable.
    is_test=True exports the clone(for_test=True) view (dropout/batch_norm
    flipped to inference, backward/optimizer ops pruned), so the fixed seed
    only matters for programs exported with is_test=False.
    """
    import jax

    fetch_names = [f.name if isinstance(f, Variable) else f
                   for f in fetch_list]
    if is_test:
        program = program.clone(for_test=True)
    from .registry import _HOST_OPS
    host = [op.type for op in program.global_block.ops
            if op.type in _HOST_OPS]
    if host:
        raise ValueError(
            f"as_jax_function: program contains host-boundary op(s) "
            f"{host} (file IO / RPC / readers) that cannot lower into a "
            f"pure jax function; run it through Executor.run instead")
    ops = [op for op in program.global_block.ops
           if op.type not in ("feed", "fetch")]

    def fn(scope_vals, feed_vals):
        env = dict(scope_vals)
        env.update(feed_vals)
        ctx = LowerContext(rng_key=jax.random.PRNGKey(seed),
                           is_test=is_test)
        for op in ops:
            lower_op(ctx, op, env)
        return [env[n] for n in fetch_names]

    return fn
