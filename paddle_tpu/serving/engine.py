"""Request-lifecycle engine: admission queue, backpressure, streaming.

The reference's serving story is AnalysisPredictor behind async
executors/DeviceWorkers that pull work from bounded queues and keep the
device busy (SURVEY §2.8); this is that layer for the continuous-batching
scheduler. A request moves

    submit() -> QUEUED -> (slot free AND pages free) RUNNING -> FINISHED
             -> EngineOverloadError when the admission queue is full
                (shed at the door — reject-with-overload, never an
                unbounded queue; an arena out of PAGES queues instead —
                retirements free pages, so the wait is bounded)

with a per-request streaming callback fired on every emitted token and
RequestMetrics stamping queue-wait/TTFT/TPOT along the way. The engine
is driven synchronously — step() interleaves admissions with one decode
pipeline tick (launch the next fused chunk dispatch, fan out the oldest
completed block; see scheduler.py for the donation/fusion/overlap fast
path); run_until_drained() loops — so tests and batch jobs need no
threads, while submit() itself is lock-protected so producer threads can
feed a driver loop.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from ..observability import request_log as _request_log
from ..observability import watchdog as _watchdog
from ..observability.compile_log import compile_log
from ..observability.tracer import get_tracer, request_scope, trace_span
from .kv_cache import ShapeBuckets, SlotKVCache
from .model import BLOCK_DIFFUSION, require_features, serving_model
from .metrics import _TICK_PHASES, EngineMetrics, RequestMetrics
from .scheduler import (PREFILL_PENDING, CompileJournal,
                        ContinuousBatchingScheduler)

_TRACER = get_tracer()

__all__ = ["ServingConfig", "ServingEngine", "GenerationRequest",
           "EngineOverloadError", "DEFAULT_RETRY_AFTER_S"]

# Retry-After hint a shed carries before the engine has any queue-wait
# samples (cold engine): a conservative 100ms — long enough that an
# immediate-retry storm can't hammer a just-started engine, short
# enough that the first real p50 takes over almost immediately. With
# this default the hint is ALWAYS a number, so HTTP 429s carry a
# well-formed Retry-After from the very first shed.
DEFAULT_RETRY_AFTER_S = 0.1


class EngineOverloadError(RuntimeError):
    """Admission queue full: the request was shed, not enqueued.

    Structured fields — the server/router and bench tooling read state
    instead of parsing the message: `queue_depth` (requests waiting at
    shed time), `running` (slots occupied), `retry_after_s` (suggested
    client backoff: the engine's queue-wait p50 when it has samples,
    else the documented DEFAULT_RETRY_AFTER_S — never None from the
    engine's own shed path, so Retry-After headers are always
    well-formed)."""

    def __init__(self, message: str, queue_depth: Optional[int] = None,
                 running: Optional[int] = None,
                 retry_after_s: Optional[float] = None):
        super().__init__(message)
        self.queue_depth = queue_depth
        self.running = running
        self.retry_after_s = retry_after_s


class ServingConfig:
    """Engine knobs. num_slots bounds concurrency (the decode batch
    dim = page-table rows); max_queue bounds the admission queue (beyond
    it, submit() sheds); prefill_buckets is the fixed set of padded
    prompt-SUFFIX lengths (compile count is O(len(buckets))); max_len is
    the per-sequence position capacity (default: the model's positions).

    Paged pool knobs: block_size is the page granularity (HBM is paid
    per page actually mapped, and prefixes are hash-shared at block
    granularity); kv_blocks sizes the arena (default: slab-equivalent
    num_slots × pages-per-max_len + scratch — size it DOWN or num_slots
    UP to oversubscribe worst-case contexts, admission queues when pages
    run out); prefix_cache toggles hashed prefix sharing (shared system
    prompts are prefilled and stored once, refcounted, LRU-kept while
    unreferenced).

    Chunked-prefill knob: prefill_chunk=N (None = today's monolithic
    prefill, bit-identical, zero new executables) splits every
    prompt's suffix prefill into budget-bounded chunk dispatches of at
    most N tokens, interleaved one budget per engine step with the
    fused decode dispatches — a long prompt no longer stalls every
    co-batched decode stream for its whole prefill (the TPOT p99
    spike chunking exists to kill), at the cost of a bounded TTFT
    stretch for the long prompt itself (its prefill now shares ticks
    with decode). Chunk shapes come from the SAME suffix buckets, so
    the executable family grows by at most O(prefill buckets); token
    streams are pinned identical to prefill_chunk=None across greedy/
    seeded, speculation, quantized KV, mesh, and preempt/resume.
    Mid-prefill sequences are not migratable (typed MigrationError)
    and never preemption victims; cancel frees their pages.

    Speculation knobs: speculate_k > 0 turns every fused decode
    iteration into a draft -> verify -> accept pass over k self-drafted
    tokens (in-graph per-slot n-gram drafter — no second model), so
    tokens-per-model-pass rises to up to k+1 on accept streaks while
    token streams stay bit-identical to speculate_k=0;
    speculate_ngram sizes the hashed per-slot drafter table.

    Mesh knob: mesh_shape=(tp,) builds the WHOLE executable family
    (prefill, fused decode chunk, verify, admit, release, swap) GSPMD-
    sharded over a tp-device tensor-parallel mesh — attention heads and
    MLP widths split on the "tp" axis, the paged KV block arena sharded
    per-head alongside them (each chip holds pool_bytes/tp), page table
    and decode carry replicated. Token streams are pinned identical to
    mesh_shape=None (single chip), greedy and seeded, with and without
    speculation, across preempt/resume and migration; compile count is
    unchanged. Requires tp visible devices and cfg.heads % tp ==
    cfg.ffn % tp == 0. None (the default) builds the single-chip engine
    with zero mesh machinery.

    Quantization knobs (both default None = full precision):
    weight_dtype="int8" quantizes the q/k/v/out/mlp matmul weights to
    per-output-channel int8 + f32 scales at engine construction, with
    dequant fused in-graph (embeddings/LNs/biases stay fp32);
    kv_dtype="int8" allocates the paged block arena as int8 with a
    per-block f32 scale plane — K/V rows quantize at the ride-along
    scatter and dequantize inside the page-gather attention of
    prefill/decode/verify. Together they roughly quadruple resident
    weights+KV per chip; the tokens/s-per-GB win and the accuracy
    budget (greedy token agreement, max logit delta vs fp32) are
    MEASURED by `bench_serving --quantize` and pinned in tests.
    Quantized streams stay deterministic — bit-identical to themselves
    across chunk sizes, preempt/resume, migration, and mesh shapes —
    and swap/migration payloads carry dtype + scales (a
    dtype-mismatched MigrationTicket rejects with TicketError).
    Unknown dtype strings raise at construction, and so does every
    option the served model does not declare among its features
    (serving.model.require_features: int8 weights or cache, adapters,
    speculation, a mesh, chunked prefill).

    Multi-tenant adapter knobs (both default None = adapterless, the
    bit-identical pre-adapter engine with zero new executables or
    registry series): max_adapters=N + adapter_rank=r allocate a
    device-resident LoRA pool of N rows (row 0 = the reserved base
    identity) at rank r over the q/k/v/out/mlp1/mlp2 projections
    (serving.adapters.AdapterPool). upload_adapter()/evict_adapter()
    manage residency under a refcount+LRU discipline; submit(
    adapter_id=k) routes a request to a resident adapter (unknown id =
    typed UnknownAdapterError, a ValueError for the HTTP 400 mapping).
    Co-batched requests hit different adapters inside ONE fused chunk
    dispatch; compile count stays O(buckets)+admit+1 and adapter_id=0
    streams are bit-identical to an adapterless engine. Both knobs must
    be set together; geometry is validated here with typed errors — no
    silent fallback (the weight_dtype discipline).

    Observability knobs. Every tick's phases are spans of
    observability.trace_span whatever the knobs say (serving/tick/admit,
    /prefill_chunk, /launch, /collect, /stream under serving/engine_step,
    with serving/decode_dispatch inside the launch; the other waits for
    the device have their own spans: serving/wait/first_token, one a
    tick that admitted, BETWEEN launch and collect (ahead of the launch
    when no dispatch is in flight) and inside no phase
    (the tick profile books it under bookkeeping), and, under page
    pressure, serving/wait/fence inside admit): they show in any
    profiler trace and, while the ring is on, in /tracez. The knobs only
    add sinks that those spans hand their own durations to.
    dispatch_timing=True attributes every fused decode dispatch's wall
    time into launch-side host work (the serving/decode_dispatch span)
    vs the blocking wait for its result (the serving/tick/collect span,
    or serving/wait/fence for a dispatch the fence collected):
    serving_dispatch_{host,device}_seconds histograms; off by default —
    disabled adds zero registry series. tick_profile=True turns on the
    performance-attribution plane: every engine tick is decomposed into
    phases (admit / prefill_chunk / launch / collect / stream, and
    bookkeeping = the tick's span less those children)
    published as serving_tick_phase_seconds{phase} histograms, a
    bounded per-tick flight ring (/tickz), and the executable
    cost/compile journal (/compilez + serving_compiles_total{family},
    serving_compile_seconds, and the derived serving_mfu_proxy /
    serving_dispatch_hbm_bytes gauges). Off — the default — is pinned
    a no-op: identical metric family set, bit-identical streams,
    identical compile-event sequence. The request event log is
    process-wide, not an engine knob:
    observability.install_request_log()."""

    def __init__(self, num_slots: int = 4, max_queue: int = 16,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 max_len: Optional[int] = None, top_k: int = 0,
                 max_admits_per_step: Optional[int] = None,
                 decode_chunk: int = 8, overlap: bool = True,
                 block_size: int = 16,
                 kv_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 speculate_k: int = 0,
                 speculate_ngram: int = 512,
                 prefill_chunk: Optional[int] = None,
                 preempt: bool = False,
                 preempt_policy="newest",
                 mesh_shape: Optional[Sequence[int]] = None,
                 weight_dtype: Optional[str] = None,
                 kv_dtype: Optional[str] = None,
                 max_adapters: Optional[int] = None,
                 adapter_rank: Optional[int] = None,
                 fault_plan=None,
                 dispatch_timing: bool = False,
                 tick_profile: bool = False,
                 clock: Callable[[], float] = time.monotonic):
        self.num_slots = int(num_slots)
        self.max_queue = int(max_queue)
        self.prefill_buckets = tuple(prefill_buckets) \
            if prefill_buckets is not None else None
        self.max_len = max_len
        self.top_k = int(top_k)
        self.max_admits_per_step = max_admits_per_step
        self.block_size = int(block_size)
        self.kv_blocks = kv_blocks
        self.prefix_cache = bool(prefix_cache)
        # decode fast path: fused decode iterations per dispatch (token
        # streams are identical at every setting; higher amortizes
        # dispatch/sync cost, lower tightens streaming latency), and
        # whether to keep one dispatch in flight while host post-
        # processing runs (overlap=False collects each dispatch
        # immediately — simplest latency profile, no pipelining)
        self.decode_chunk = int(decode_chunk)
        self.overlap = bool(overlap)
        # speculative decoding (off by default): each chunk iteration
        # drafts speculate_k tokens from a per-slot n-gram table and
        # verifies them in ONE model pass — between 1 and k+1 tokens
        # per pass, token streams bit-identical to speculate_k=0.
        # speculate_ngram sizes the hashed trigram table per slot.
        self.speculate_k = int(speculate_k)
        self.speculate_ngram = int(speculate_ngram)
        # chunked prefill (None = monolithic, the bit-identical
        # default): per-tick prefill token budget AND per-dispatch
        # chunk ceiling — the Sarathi-style piggyback discipline that
        # keeps a long prompt's prefill from stalling co-batched decode
        if prefill_chunk is not None and int(prefill_chunk) < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1 or None, got "
                f"{prefill_chunk}")
        self.prefill_chunk = int(prefill_chunk) \
            if prefill_chunk is not None else None
        # host-swap preemption (off by default — opt in where the arena
        # is deliberately oversubscribed): under page pressure the
        # engine evicts the policy-chosen RUNNING sequence's pages to a
        # host swap pool and resumes it when pages free, instead of
        # only queueing new admissions. preempt_policy: "newest"
        # (default), "oldest", or a callable over the running table.
        # Resumed streams are bit-identical to never-preempted runs.
        self.preempt = bool(preempt)
        self.preempt_policy = preempt_policy
        # tensor-parallel serving mesh (None = single chip): (tp,)
        # normalized to a tuple; geometry/divisibility is validated by
        # ServingTPPlan at engine construction where cfg is in hand
        self.mesh_shape = tuple(int(m) for m in mesh_shape) \
            if mesh_shape is not None else None
        # quantized serving (both off by default): weight_dtype="int8"
        # runs the q/k/v/out/mlp matmuls against per-output-channel
        # int8 weights with the dequant fused in-graph
        # (the model's quantize_params); kv_dtype="int8" packs the
        # paged block arena as int8 with a per-block scale plane,
        # quantize-at-scatter / dequant-at-gather. Unknown values are
        # a LOUD config error here — there is no silent fp32 fallback
        # anywhere in the quantized path. Accuracy is a measured,
        # pinned budget (bench_serving --quantize; tests), not a
        # promise of fp32 bit-identity: a quantized engine is
        # bit-identical to ITSELF across chunk sizes, preemption,
        # migration, and mesh shapes.
        for knob, val in (("weight_dtype", weight_dtype),
                          ("kv_dtype", kv_dtype)):
            if val not in (None, "int8"):
                raise ValueError(
                    f"unknown {knob} {val!r}: expected None (full "
                    "precision) or 'int8' — quantized serving never "
                    "falls back silently")
        self.weight_dtype = weight_dtype
        self.kv_dtype = kv_dtype
        # multi-tenant adapter pool (both None = adapterless): the two
        # knobs travel together — a pool needs both its row count and
        # its rank, and validation is LOUD at construction (the
        # weight_dtype discipline: no silent fallback, no deferred
        # surprise at first upload)
        if (max_adapters is None) != (adapter_rank is None):
            raise ValueError(
                "max_adapters and adapter_rank must be set together "
                f"(got max_adapters={max_adapters!r}, "
                f"adapter_rank={adapter_rank!r}) — an adapter pool "
                "needs both its row count and its rank")
        if max_adapters is not None:
            if not isinstance(max_adapters, int) \
                    or isinstance(max_adapters, bool) or max_adapters < 2:
                raise ValueError(
                    f"max_adapters must be an int >= 2 (row 0 is the "
                    f"reserved base identity), got {max_adapters!r}")
            if not isinstance(adapter_rank, int) \
                    or isinstance(adapter_rank, bool) or adapter_rank < 1:
                raise ValueError(
                    f"adapter_rank must be an int >= 1, got "
                    f"{adapter_rank!r}")
        self.max_adapters = max_adapters
        self.adapter_rank = adapter_rank
        # deterministic fault injection (serving.faults.FaultPlan):
        # scheduled step exceptions / forced page shortages / delays —
        # None in production
        self.fault_plan = fault_plan
        # host/device dispatch split (off by default — on, every fused
        # decode dispatch's wall time is attributed into launch-side
        # host work vs the blocking wait for its result, published as
        # serving_dispatch_{host,device}_seconds; off, zero extra
        # registry series and zero extra clock reads)
        self.dispatch_timing = bool(dispatch_timing)
        # performance-attribution plane (off by default — the disabled
        # path is pinned byte-identical: no new registry families,
        # identical streams, identical compile events): per-tick phase
        # decomposition + flight ring + executable cost/compile journal
        self.tick_profile = bool(tick_profile)
        self.clock = clock


class GenerationRequest:
    """One generate call in flight. `tokens` accumulates the generated
    ids (prompt excluded); `output()` is prompt + generated. state is
    one of queued / running / finished / cancelled / shed. `request_id`
    is the engine-minted trace id (`<engine_label>-<n>`) every span this
    request produces carries — `/tracez?request_id=` keys on it."""

    def __init__(self, prompt: np.ndarray, max_new_tokens: int,
                 temperature: float, seed: int, eos_id: Optional[int],
                 on_token: Optional[Callable[["GenerationRequest", int],
                                             Any]],
                 clock: Callable[[], float],
                 request_id: Optional[str] = None,
                 adapter_id: int = 0):
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.seed = int(seed)
        self.eos_id = eos_id
        self.adapter_id = int(adapter_id)
        self.on_token = on_token
        self.tokens: List[int] = []
        # block diffusion alone: for each generated token the pass of
        # its block at which it was fixed (0 .. denoising_steps - 1) and
        # the probability that pass gave it
        self.fixed_at: List[int] = []
        self.confidence: List[float] = []
        self.state = "queued"
        self.metrics = RequestMetrics(clock)
        self.request_id = request_id
        self._submit_ns: Optional[int] = None  # tracer queue-wait anchor

    @property
    def finished(self) -> bool:
        return self.state == "finished"

    def output(self) -> np.ndarray:
        return np.concatenate(
            [self.prompt, np.asarray(self.tokens, np.int32)])


def _default_buckets(max_len: int):
    sizes, s = [], 16
    while s < max_len:
        sizes.append(s)
        s *= 2
    sizes.append(max_len)
    return sizes


# per-tick flight records kept for /tickz (bounded: a day of serving
# must not grow host memory — same discipline as the tracer ring)
TICK_RING_SIZE = 256


class ServingEngine:
    """Continuous-batching generate service over a model's parameter
    pytree and config.

    The config names its serving model (serving.model.ServingModel:
    `cfg.serving_model()`), and params are that model's tree: a
    GPTConfig with the GPT family's (collect_gpt_params;
    inference.create_engine() wires them from a saved model dir), a
    MoonlightConfig with models.moonlight's. Options the model does not
    implement refuse here, at construction."""

    def __init__(self, params, cfg, serving: Optional[ServingConfig] = None):
        # a start's `serving/engine_build` (once an engine): a span of the
        # one tracer and a phase of the compile log
        with compile_log().phase("serving/engine_build"):
            self._build(params, cfg, serving or ServingConfig())

    def _build(self, params, cfg, serving: ServingConfig):
        self.cfg = cfg
        self.config = serving
        model = self.model = serving_model(cfg)
        require_features(model, serving, cfg)
        max_pos = model.max_positions(cfg)
        max_len = int(serving.max_len if serving.max_len is not None
                      else max_pos)
        if max_len > max_pos:
            raise ValueError(
                f"max_len {max_len} exceeds cfg.max_pos {max_pos}")
        if serving.prefill_buckets is not None:
            buckets = serving.prefill_buckets
            too_big = [b for b in buckets if b > max_len]
            if too_big:
                raise ValueError(
                    f"prefill_buckets {too_big} exceed max_len {max_len} "
                    "— a prompt filling such a bucket could never fit the "
                    "KV pool")
        else:
            buckets = _default_buckets(max_len)
        self.buckets = ShapeBuckets(buckets)
        import jax.numpy as jnp
        dtype = model.activation_dtype(params)
        # quantized serving: weight-only int8 happens HERE, before the
        # scheduler shards anything, so the int8 tensors + scales ride
        # the same Megatron TP placement the fp32 weights would
        if serving.weight_dtype == "int8":
            params = model.quantize_params(params, cfg)
        # whole-model parameter bytes AS SERVED (post-quantization,
        # pre-sharding: the sum across chips on a mesh) — the
        # capacity-planning number next to pool_bytes — and the dtype
        # label stats() reports: the quantization knob when set, else
        # the ACTUAL matmul-weight dtype (a bf16 checkpoint serves
        # bfloat16 weights, not "float32")
        import jax
        self.weight_bytes = int(sum(
            leaf.nbytes for leaf in jax.tree_util.tree_leaves(params)))
        self._weight_dtype = serving.weight_dtype or str(jnp.dtype(dtype))
        # tensor-parallel mesh plan: built ONCE here (validates device
        # count + head/ffn divisibility), threaded into the scheduler,
        # which shards params + arena at construction so every jitted
        # entry point compiles GSPMD-partitioned from its first trace
        plan = None
        if serving.mesh_shape is not None:
            from ..parallel.plan import ServingTPPlan
            plan = ServingTPPlan(cfg, serving.mesh_shape)
        self.plan = plan
        # device-resident LoRA pool, allocated AFTER the plan so on a
        # mesh every A/B stack materializes under its TP sharding
        # (column projections shard B on the out axis, row projections
        # shard A on the in axis — plan.adapter_shardings)
        self.adapters = None
        if serving.max_adapters is not None:
            from .adapters import AdapterPool
            self.adapters = AdapterPool(cfg, serving.max_adapters,
                                        serving.adapter_rank, plan=plan)
        self.kv = SlotKVCache(cfg, serving.num_slots, max_len, dtype,
                              block_size=serving.block_size,
                              num_blocks=serving.kv_blocks,
                              # a hit would need a block-causal warm
                              # prefill, which no model has written
                              prefix_cache=serving.prefix_cache
                              and BLOCK_DIFFUSION not in model.features,
                              mesh_shards=plan.tp if plan else 1,
                              arena_device=plan.arena_sharding
                              if plan else None,
                              kv_dtype=serving.kv_dtype)
        if len(self.kv.group_layout) > 1 and serving.preempt:
            raise ValueError(
                f"the serving model {model.name!r} has "
                f"{len(self.kv.group_layout)} cache groups: host swap "
                "(preempt=True) carries one group's blocks — refusing at "
                "construction rather than parking a sequence without its "
                "window rows")
        self.scheduler = ContinuousBatchingScheduler(
            params, cfg, self.kv, self.buckets, top_k=serving.top_k,
            decode_chunk=serving.decode_chunk, overlap=serving.overlap,
            speculate_k=serving.speculate_k,
            speculate_ngram=serving.speculate_ngram, plan=plan,
            prefill_chunk=serving.prefill_chunk,
            adapters=self.adapters)
        # chunked-prefill telemetry: one counter bump + one latency
        # sample per dispatched chunk (bound through self.metrics at
        # call time, so a bench's metrics reset keeps feeding the
        # replacement instance)
        self.scheduler.on_prefill_chunk = self._on_prefill_chunk
        # launch-side heartbeat: bumped at dispatch ENQUEUE inside the
        # scheduler, not after step() returns — a device hang leaves the
        # host blocked in the next fetch, and the watchdog/flight record
        # must still see the last launch that went in
        self.scheduler.on_launch = self._on_dispatch_launched
        # an admission's first token goes out the moment the scheduler
        # has read it, inside scheduler.step() (or the fence)
        self.scheduler.on_first_tokens = self._emit_first_tokens
        self._first_emitted = 0           # ... since the tick began
        # count-scaled histogram layout: one dispatch can emit up to
        # num_slots * decode_chunk * (1 + speculate_k) tokens, and the
        # acceptance histogram spans 0..speculate_k accepted per pass
        self.metrics = EngineMetrics(
            max_tokens_per_dispatch=(serving.num_slots
                                     * serving.decode_chunk
                                     * (1 + serving.speculate_k)),
            speculate_k=serving.speculate_k,
            dispatch_timing=serving.dispatch_timing,
            adapters=self.adapters is not None,
            tick_profile=serving.tick_profile)
        if serving.dispatch_timing:
            # bound through self.metrics at CALL time so a bench's
            # metrics reset keeps feeding the replacement instance
            self.scheduler.on_dispatch_timed = self._on_dispatch_timed
        # performance-attribution plane (tick_profile=True only — the
        # default constructs NONE of this: no phase table, no ring, no
        # journal, and the registry family set is pinned unchanged).
        # The tick's phase spans are opened either way; _phases is
        # where they put their durations down when this plane is on.
        self._phases = None
        self._tick_ring = None
        if serving.tick_profile:
            self._phases = dict.fromkeys(_TICK_PHASES, 0.0)
            self._tick_ring = collections.deque(maxlen=TICK_RING_SIZE)
            # the scheduler opens the launch and collect spans itself
            self.scheduler.on_tick_phase = self._phases.__setitem__
            journal = CompileJournal()
            # bound through self.metrics at CALL time (bench reset
            # discipline, same as the other hooks)
            journal.on_compile = self._on_compile
            self.scheduler.compile_journal = journal
            # /tickz + /compilez read through the debug server's
            # perf-source registry — closures here, unregistered in
            # close(), so the server itself still holds no references
            # into the engine beyond this explicit lifecycle
            from ..observability import debug_server as _dbg
            _dbg.register_perf_source(
                "tick", self.metrics.engine_label, self._tick_records)
            _dbg.register_perf_source(
                "compile", self.metrics.engine_label,
                self._compile_snapshot)
        self.metrics.kv_blocks_total = self.kv.blocks_total
        # mesh + quantization geometry gauges, constant for the
        # engine's life: the shard count, the PER-CHIP arena bytes
        # (pool_bytes / tp), the arena storage itemsize, and the
        # served weight bytes — the numbers /varz' mesh rollup and
        # capacity planning read; whole-arena pool_bytes alone
        # overstates per-chip HBM by tp, and a dtype-blind reader
        # would overstate a quantized pool ~4x
        self.metrics.mesh_shards = self.kv.mesh_shards
        self.metrics.kv_pool_per_chip_bytes = self.kv.hbm_per_chip_bytes
        self.metrics.kv_dtype_bytes = self.kv.dtype.itemsize
        self.metrics.weight_bytes = self.weight_bytes
        if self.adapters is not None:
            self._sync_adapter_metrics()
        self._queue: List[GenerationRequest] = []
        self._pending_cancels: List[GenerationRequest] = []
        # host swap pool: SwappedSequence records of preempted RUNNING
        # sequences, FIFO (oldest-preempted resumes first). Driver-
        # thread state, like the scheduler.
        self._swapped: List[Any] = []
        self.faults = serving.fault_plan
        self._step_no = 0
        self._lock = threading.Lock()
        # drain flag (begin_drain): cross-replica migration refuses on
        # a draining engine — a sequence handed off mid-drain could
        # never resume (the router has stopped adopting), so refusal
        # beats a stuck ticket. Normal stepping/drain is unaffected.
        self._draining = False
        self._rid_counter = itertools.count()
        self.debug_port: Optional[int] = None   # set by create_engine
        # debug-server release token from acquire_debug_server (None =
        # this engine holds no reference); set by create_engine
        self._debug_server_ref: Optional[int] = None

    @property
    def faults(self):
        """The installed FaultPlan (None = no injection). Assigning
        here is the documented post-construction install path — the
        setter mirrors the plan onto the scheduler so dispatch-level
        faults (slow_dispatches) fire too, not just step-level ones."""
        return self._faults

    @faults.setter
    def faults(self, plan) -> None:
        self._faults = plan
        self.scheduler.faults = plan

    # -- admission ----------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int, temperature: float = 0.0,
               seed: int = 0, eos_id: Optional[int] = None,
               on_token: Optional[Callable] = None,
               adapter_id: int = 0) -> GenerationRequest:
        """Enqueue one generate request. Raises ValueError for requests
        that can never be served (too long for the buckets/pool,
        unknown/unresident adapter_id) and EngineOverloadError when the
        queue is full (backpressure: the caller sheds load or retries
        later; nothing queues unboundedly). adapter_id pins the named
        LoRA adapter (uploaded via upload_adapter) for this request's
        whole lifetime — its pool row cannot be evicted or overwritten
        until the request finishes, cancels, or migrates away."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        adapter_id = int(adapter_id)
        if adapter_id < 0:
            raise ValueError(f"adapter_id must be >= 0, got {adapter_id}")
        if adapter_id and self.adapters is None:
            raise ValueError(
                f"adapter_id {adapter_id} on an engine with no adapter "
                "pool (ServingConfig(max_adapters=..., adapter_rank=...))")
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        self.buckets.bucket_for(prompt.size)          # raises if too long
        total = prompt.size + max_new_tokens
        if total > self.kv.max_len:
            # max_len <= the model's positions (enforced at
            # construction), so this also guards a position table's clamp
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({max_new_tokens}) exceeds the pool's max_len "
                f"({self.kv.max_len})")
        shortfall = self.kv.request_shortfall(total)
        if shortfall is not None:
            # an undersized arena (kv_blocks oversubscription, of any
            # cache group's pool) must shed impossible requests at the
            # door, not queue them forever
            raise ValueError(shortfall)
        req = GenerationRequest(
            prompt, max_new_tokens, temperature, seed, eos_id, on_token,
            self.config.clock,
            request_id=f"{self.metrics.engine_label}-"
                       f"{next(self._rid_counter)}",
            adapter_id=adapter_id)
        if _TRACER.enabled:  # queue-wait anchor; no clock read when off
            req._submit_ns = time.monotonic_ns()
        rlog = _request_log.get_request_log()
        if rlog is not None:
            rlog.event("submitted", request_id=req.request_id,
                       engine=self.metrics.engine_label,
                       prompt_len=int(prompt.size),
                       max_new=int(max_new_tokens),
                       adapter_id=adapter_id)
        with self._lock:
            # pin the adapter row FIRST: an unknown id is the typed 4xx
            # (UnknownAdapterError is a ValueError) and must not count
            # as a submission; once acquired, the row survives every
            # upload/evict until this request's terminal release
            if adapter_id:
                self.adapters.acquire(adapter_id)
            self.metrics.submitted += 1
            if len(self._queue) >= self.config.max_queue:
                self.metrics.shed += 1
                if adapter_id:   # release-then-raise: shed pins nothing
                    self.adapters.release(adapter_id)
                req.state = "shed"
                shed_depth = len(self._queue)
                queued_depth = None
            else:
                req.metrics.mark_submitted()
                self._queue.append(req)
                self.metrics.queue_depth = queued_depth = \
                    len(self._queue)
        # journal + hooks OUTSIDE the lock: the overload hook may write
        # a flight record (no-op unless a watchdog with dump_on_overload
        # is installed) and neither it nor the JSONL write may stall
        # concurrent submits/steps
        if queued_depth is not None:
            if rlog is not None:
                rlog.event("queued", request_id=req.request_id,
                           queue_depth=queued_depth)
            return req
        if rlog is not None:
            rlog.event("shed", request_id=req.request_id,
                       queue_depth=shed_depth)
        _watchdog.notify_overload(self.metrics.engine_label)
        p50 = self.metrics.queue_wait_p50()
        raise EngineOverloadError(
            f"admission queue full ({self.config.max_queue}); "
            "request shed",
            queue_depth=shed_depth, running=self.kv.active_count,
            retry_after_s=p50 if p50 is not None
            else DEFAULT_RETRY_AFTER_S)

    # -- drive loop ---------------------------------------------------------

    def _emit(self, event):
        req: GenerationRequest = event.request
        if req.state == "cancelled":
            # cancelled concurrently with the decode step that produced
            # this token: swallow the emission, the slot frees next step
            return
        req.tokens.append(event.token)
        if event.fixed_at is not None:
            req.fixed_at.append(event.fixed_at)
            req.confidence.append(event.confidence)
        req.metrics.mark_token()
        self.metrics.tokens_out += 1
        if event.finished:
            req.state = "finished"
            req.metrics.mark_finished()
            self.metrics.record(req.metrics)
            aid = getattr(req, "adapter_id", 0)
            if aid and self.adapters is not None:
                # terminal unpin: the adapter row becomes LRU-evictable
                # again (lock: submit acquires from client threads)
                with self._lock:
                    self.adapters.release(aid)
            rlog = _request_log.get_request_log()
            if rlog is not None:
                rlog.event(
                    "finished", request_id=req.request_id,
                    finish_reason="stop" if (req.eos_id is not None
                                             and event.token == req.eos_id)
                    else "length",
                    tokens=len(req.tokens))
        if req.on_token is not None:
            if _TRACER.enabled:
                # streamed-token callback on the request's trace timeline
                # (args built only here — the disabled path allocates
                # nothing and calls the callback directly)
                with _TRACER.span("serving/on_token", "serving",
                                  {"request_id": req.request_id,
                                   "token": event.token,
                                   "finished": event.finished}):
                    req.on_token(req, event.token)
            else:
                req.on_token(req, event.token)

    def step(self) -> int:
        """Admit waiting requests into free slots, then run one decode
        pipeline tick: launch the next fused chunk dispatch and fan out
        the oldest completed one (with overlap on, the first tick of a
        burst only launches — its tokens surface next tick, hidden
        under the following dispatch's device time). Returns the number
        of tokens emitted; 0 means idle OR a launch-only warm-up tick,
        so drive loops should key on queue/active state, not on the
        return value."""
        step_no = self._step_no
        self._step_no += 1
        with trace_span("serving/engine_step", "serving") as tick:
            emitted = self._step_impl(step_no)
        if self._phases is not None:
            self._finish_tick(step_no, emitted, tick.seconds)
        return emitted

    def _step_impl(self, step_no: int) -> int:
        # the tick's phases are child spans of serving/engine_step, each
        # timed once, by its span; on a tick_profile engine the span's
        # own duration goes into `phases` (None otherwise), and what the
        # children leave of the tick is its bookkeeping
        phases = self._phases
        if phases is not None:
            for phase in phases:
                phases[phase] = 0.0
        if self.faults is not None:
            # counter already advanced: an injected exception fires
            # exactly once, and a supervisor retrying the driver loop
            # proceeds past it
            self.faults.begin_step(step_no)
        with trace_span("serving/tick/admit", "serving") as sp:
            self._admit_tick(step_no)
        if phases is not None:
            phases["admit"] = sp.seconds
        # chunked prefill: dispatch at most one prefill token budget,
        # interleaved with (and ordered before) this tick's decode
        # dispatch. A monolithic engine has nothing mid-prefill and
        # opens no span.
        if self.scheduler.prefill_pending:
            with trace_span("serving/tick/prefill_chunk", "serving") as sp:
                self.scheduler.advance_prefill()
            if phases is not None:
                phases["prefill_chunk"] = sp.seconds
        # scheduler.step() opens serving/tick/launch and
        # serving/tick/collect around its two segments and, between
        # them (ahead of both when no dispatch is in flight),
        # serving/wait/first_token around the one fetch of the
        # first tokens of this tick's admissions. Those go out at once
        # (_emit_first_tokens), apart from the collected block's
        # events, which alone are a dispatch's tokens
        self._first_emitted = 0
        events = self.scheduler.step()
        if events:
            self.metrics.decode_steps += 1
            self.metrics.observe_dispatch_tokens(len(events))
            # token fan-out: callbacks + journal writes
            with trace_span("serving/tick/stream", "serving") as sp:
                for event in events:
                    self._emit(event)
            if phases is not None:
                phases["stream"] = sp.seconds
        self._sync_gauges()
        return self._first_emitted + len(events)

    def _emit_first_tokens(self, events) -> None:
        """The scheduler's on_first_tokens: fan the first tokens out
        where they were read (between a tick's phases, or inside the
        admit phase under a fence; no phase span of their own)."""
        self._first_emitted += len(events)
        for event in events:
            self._emit(event)

    def _admit_tick(self, step_no: int) -> None:
        """The admit phase of a tick: deferred cancels, swap-ins, queue
        pops, and admissions with their prefill dispatches. Nothing is
        emitted here: an admission's first token is read behind the
        tick's launch (scheduler.step())."""
        admitted = []
        with self._lock:
            # apply deferred cancels first (scheduler state is only ever
            # touched from the driver thread; cancel() just marks)
            for req in self._pending_cancels:
                if not self.scheduler.cancel(req):
                    # not running on-device: the request may be parked
                    # in the host swap pool — drop its record (its
                    # pages were already freed at swap-out)
                    n = len(self._swapped)
                    self._swapped = [s for s in self._swapped
                                     if s.req is not req]
                    if len(self._swapped) != n:
                        self.metrics.swapped_slots = len(self._swapped)
            self._pending_cancels.clear()
        # resume-first: preempted sequences have strict priority over
        # new admissions for freed pages/slots (they hold finished work
        # and a host-side arena copy; admissions behind them are what
        # put them out). FIFO scan — oldest-preempted first, but a
        # record whose ORIGINAL slot is still occupied doesn't block a
        # later one whose slot freed.
        if self._swapped:
            for sw in list(self._swapped):
                if not self.scheduler.can_swap_in(sw):
                    continue
                t0 = time.perf_counter()
                slot = self.scheduler.swap_in(sw)
                assert slot is not None  # checked, same thread
                self._swapped.remove(sw)
                self.metrics.swap_ins += 1
                self.metrics.observe_swap("swap_in",
                                          time.perf_counter() - t0)
            self.metrics.swapped_slots = len(self._swapped)
        with self._lock:
            limit = self.config.max_admits_per_step
            # slots are claimed later in scheduler.admit, so bound the
            # pop count by the free slots NOW, not per-iteration
            can_take = self.kv.free_count
            if limit is not None:
                can_take = min(can_take, limit)
            while self._queue and len(admitted) < can_take:
                admitted.append(self._queue.pop(0))
            self.metrics.queue_depth = len(self._queue)
        for i, req in enumerate(admitted):
            with self._lock:
                if req.state != "queued":
                    # cancelled while popped out of the queue (cancel()
                    # keys on state, so a request in this local list is
                    # still cancellable): drop it without admitting
                    continue
            # pages-aware admission: the pop above was bounded by free
            # SLOTS, but the arena may be out of PAGES (short on blocks
            # after prefix-cache accounting). Head-of-line requests that
            # don't fit yet go back to the FRONT of the queue — FIFO
            # order is preserved and a later retirement frees their
            # pages. With preemption enabled, page pressure first tries
            # to evict running sequences to the host swap pool (inside
            # _admission_feasible).
            if not self._admission_feasible(req, step_no):
                with self._lock:
                    self._queue[:0] = [r for r in admitted[i:]
                                       if r.state == "queued"]
                    self.metrics.queue_depth = len(self._queue)
                break
            with self._lock:
                if req.state != "queued":   # cancelled during can_admit
                    continue
                # the queued->running transition happens under the lock
                # so cancel() can never miss a request mid-admission
                req.state = "running"
            # stamp BEFORE the prefill dispatch: queue_wait is time spent
            # waiting for a slot, not prefill/compile latency (that lands
            # in ttft)
            req.metrics.mark_admitted()
            self.metrics.admitted += 1
            self.metrics.prefills += 1
            rlog = _request_log.get_request_log()
            if rlog is not None:
                rlog.event("admitted", request_id=req.request_id,
                           queue_wait_s=req.metrics.queue_wait,
                           adapter_id=getattr(req, "adapter_id", 0))
            if _TRACER.enabled and req._submit_ns is not None:
                # the queue-wait interval only materializes as a span at
                # admission (submit -> slot), retroactively timed
                _TRACER.record_complete(
                    "serving/queue_wait", req._submit_ns,
                    time.monotonic_ns(), "serving",
                    {"request_id": req.request_id})
            # ambient request scope: the prefill RecordEvent below (and
            # any executor/compile spans it triggers) inherit the id;
            # request_scope is the shared no-op when tracing is off
            with request_scope(req.request_id):
                admitted_now = self.scheduler.admit(
                    req, req.prompt, req.max_new_tokens,
                    temperature=req.temperature, seed=req.seed,
                    eos_id=req.eos_id,
                    adapter_id=getattr(req, "adapter_id", 0))
                # can_admit checked, same thread
                assert admitted_now is PREFILL_PENDING

    def _sync_gauges(self) -> None:
        """The tick's tail: registry gauges and counters set from the
        scheduler's and the allocator's host totals."""
        if self.scheduler.speculate_k:
            # speculation telemetry: the scheduler's cumulative host
            # totals ARE the registry truth (same discipline as the
            # prefix-cache counters below), and each live verify pass
            # feeds one accepted-run sample into the histogram
            self.metrics.spec_proposed = self.scheduler.spec_proposed
            self.metrics.spec_accepted = self.scheduler.spec_accepted
            for run in self.scheduler.drain_spec_samples():
                self.metrics.observe_spec_run(run)
        self.metrics.active_slots = self.kv.active_count
        self.metrics.swapped_slots = len(self._swapped)
        # paged-pool visibility: block occupancy gauges + prefix-cache
        # counters (set from the allocator's cumulative totals — the
        # registry series a scrape reads track the authoritative host
        # bookkeeping exactly)
        self.metrics.kv_blocks_total = self.kv.blocks_total
        self.metrics.kv_blocks_used = self.kv.blocks_used
        self.metrics.kv_blocks_cached = self.kv.blocks_cached
        self.metrics.prefix_cache_hits = self.kv.prefix_hits
        self.metrics.prefix_cache_misses = self.kv.prefix_misses
        # constant mesh/quantization geometry refreshed with the other
        # gauges so a replaced metrics instance (the bench's
        # post-warmup reset) heals on the next step instead of
        # scraping as single-chip full-precision
        self.metrics.mesh_shards = self.kv.mesh_shards
        self.metrics.kv_pool_per_chip_bytes = self.kv.hbm_per_chip_bytes
        self.metrics.kv_dtype_bytes = self.kv.dtype.itemsize
        self.metrics.weight_bytes = self.weight_bytes
        if self.adapters is not None:
            self._sync_adapter_metrics()

    def _admission_feasible(self, req, step_no: int) -> bool:
        """Can `req` take a slot + pages RIGHT NOW? Applies, in order:
        injected page shortages (requeue, never preempt — a forced
        shortage simulates transient pressure, not an evictable
        resident), the swap-pool page reservation (parked sequences
        have strict priority over new admissions for freed pages, else
        a stream of short requests starves every preempted one), the
        real allocator check, and finally — preemption enabled, nothing
        already parked — eviction of running sequences until the
        admission fits."""
        if self.faults is not None and self.faults.deny_pages(step_no):
            return False
        if self._swapped:
            # page reservation for parked sequences, checked against
            # the blocks this admission would ACTUALLY consume from
            # the available supply (blocks_needed's non-mutating
            # planner walk: fresh pages + LRU hits it would incref out
            # of the evictable pool; hits on a live sequence's blocks
            # are free), not the full prompt. Reserving
            # blocks_for(prompt + budget) here over-reserved by the
            # live-shared hit depth: with the swap pool non-empty, a
            # prompt sharing a running sequence's prefix that
            # comfortably fit could requeue at the head of the line
            # and starve admission.
            reserved = sum(s.n_blocks for s in self._swapped)
            need = self.kv.blocks_needed(req.prompt,
                                         req.prompt.size
                                         + req.max_new_tokens,
                                         adapter_id=getattr(
                                             req, "adapter_id", 0))
            if self.kv.blocks_available < reserved + need:
                return False
            # no slot reservation needed: the resume-first loop at the
            # top of every step hands freed slots to parked sequences
            # BEFORE any admission runs, and the sampler is
            # slot-independent, so resumes take whatever row frees up
        aid = getattr(req, "adapter_id", 0)
        if self.scheduler.can_admit(req.prompt, req.max_new_tokens,
                                    adapter_id=aid):
            return True
        if not self.config.preempt or self._swapped:
            # preempting while sequences already wait in the swap pool
            # would ping-pong residents; pressure with a non-empty pool
            # always queues
            return False
        while not self.scheduler.can_admit(req.prompt,
                                           req.max_new_tokens,
                                           adapter_id=aid):
            if not self._preempt_once(req):
                return False
        return True

    def _preempt_once(self, req) -> bool:
        """Evict one policy-chosen RUNNING sequence to the host swap
        pool. Returns True when admission should be re-checked: either
        a victim moved out, or the pipeline fence's collected
        retirements already freed the pages without any eviction."""
        if self.scheduler.active_count == 0:
            return False
        # swap_out requires an empty pipeline; the fence's tokens fan
        # out NOW (and may retire slots — re-check before sacrificing
        # anything)
        self._fence()
        if self.scheduler.can_admit(req.prompt, req.max_new_tokens,
                                    adapter_id=getattr(
                                        req, "adapter_id", 0)):
            return True
        slot = self.scheduler.pick_victim(self.config.preempt_policy)
        if slot is None:
            return False
        t0 = time.perf_counter()
        sw = self.scheduler.swap_out(slot)
        self._swapped.append(sw)
        self.metrics.preemptions += 1
        self.metrics.observe_swap("swap_out", time.perf_counter() - t0)
        self.metrics.swapped_slots = len(self._swapped)
        return True

    def _fence(self) -> None:
        """Drain the overlap pipeline and fan its tokens out NOW — the
        precondition for swap_out/migrate_out (a block in flight could
        still carry the victim's tokens). Per-dispatch batches so
        fenced collections feed the same decode_steps /
        tokens-per-dispatch telemetry the normal step() path does —
        fence-heavy regimes would otherwise read inconsistently high
        tokens-per-dispatch."""
        # the first tokens of admissions the fence finds half done go out
        # ahead of every block (_emit_first_tokens), and count towards no
        # dispatch
        for batch in self.scheduler._sync_batches():
            if batch:
                self.metrics.decode_steps += 1
                self.metrics.observe_dispatch_tokens(len(batch))
            for event in batch:
                self._emit(event)

    @property
    def swapped_count(self) -> int:
        """Preempted sequences currently parked in the host swap pool
        (they still owe tokens: drain loops must count them as work)."""
        return len(self._swapped)

    @property
    def mesh_shape(self):
        """This engine's serving mesh geometry, (tp,) — (1,) for a
        single-chip engine. The /healthz replica gauges and migration
        tickets carry it so operators (and the router's handoff
        journal) can see which replicas are tensor-parallel."""
        return self.kv.mesh_shape

    # -- cross-replica migration ---------------------------------------------

    @property
    def draining(self) -> bool:
        return self._draining

    def begin_drain(self) -> None:
        """Flag the engine as draining: from here on migrate_out and
        migrate_in REFUSE with MigrationError (never deadlock) — a
        sequence handed off during drain could never resume, because
        the drain loop is finishing residents, not adopting new ones.
        Stepping, run_until_drained, and swap-in of already-parked
        sequences continue unaffected. Idempotent; the router calls
        this on every replica engine when its own drain begins."""
        self._draining = True

    def _refuse_grouped_migration(self, what: str) -> None:
        """A ticket carries the primary cache group's blocks alone."""
        from .migration import MigrationError
        if len(self.kv.group_layout) > 1:
            raise MigrationError(
                f"{what} refused: the serving model {self.model.name!r} "
                f"has {len(self.kv.group_layout)} cache groups and a "
                "ticket carries one")
        if BLOCK_DIFFUSION in self.model.features:
            raise MigrationError(
                f"{what} refused: the serving model {self.model.name!r} "
                "generates by diffusion over blocks and a ticket carries "
                "no block")

    def migrate_out(self, request) -> "Any":
        """Extract one RUNNING or PARKED sequence into a portable
        MigrationTicket: fence the pipeline (its tokens fan out
        normally — they were produced before the handoff), copy the
        sequence's KV blocks + decode carry to host via the swap-out
        path, free its pages/slot, and detach the stream (the
        GenerationRequest left behind goes state="migrated" and never
        emits again). `request` is a GenerationRequest or its
        request_id. DRIVER-THREAD ONLY, like every scheduler-touching
        path.

        Raises MigrationError — with the sequence left exactly where it
        was — when the engine is draining (a migrated sequence could
        never resume; refusal beats deadlock), when the request is not
        running or parked here (queued requests re-route without a
        ticket; finished/cancelled ones have nothing to move), or when
        the pipeline fence finishes the sequence first. An injected
        extract-phase fault (FaultPlan.migration_faults) fires after
        the fence and before any state moves, so a fault there leaves
        the sequence running on this engine."""
        from .migration import MigrationError, MigrationTicket

        self._refuse_grouped_migration("migrate_out")
        if self._draining:
            raise MigrationError(
                "engine is draining; migrate_out refused — the drain "
                "loop finishes residents in place")
        rid = request if isinstance(request, str) \
            else getattr(request, "request_id", None)
        rlog = _request_log.get_request_log()
        # parked first: a swap-pool record is already serialized — the
        # handoff is a pure host-side wrap, no fence, no dispatch
        for sw in self._swapped:
            if getattr(sw.req, "request_id", None) == rid:
                if sw.req.state != "running":
                    raise MigrationError(
                        f"request {rid} is {sw.req.state}, not "
                        "migratable")
                if self.faults is not None:
                    self.faults.migration_phase("extract")
                self._swapped.remove(sw)
                self.metrics.swapped_slots = len(self._swapped)
                sw.req.state = "migrated"
                ticket = MigrationTicket.from_swapped(
                    sw, self.kv.block_size,
                    mesh_shape=self.mesh_shape,
                    adapter_digest=self._adapter_digest_for(sw))
                self._release_migrated(sw)
                if rlog is not None:
                    rlog.event("migrate_out", request_id=rid,
                               replica=self.metrics.engine_label,
                               phase="parked", blocks=ticket.n_blocks,
                               bytes=ticket.swap_bytes,
                               produced=ticket.produced,
                               adapter_id=ticket.adapter_id)
                return ticket

        # mid-chunked-prefill: the fill cursor is not ticketable (the
        # slot has no sampled token, no key-chain position, and its
        # blocks are part-filled) — a typed refusal, never a corrupt
        # handoff; the sequence keeps prefilling here and migrates
        # normally once its first token lands
        for pf in self.scheduler._prefilling.values():
            if getattr(pf.req, "request_id", None) == rid:
                raise MigrationError(
                    f"request {rid} is mid-prefill (chunked-prefill "
                    "cursor not yet ticketable); migrate_out refused — "
                    "retry after its first token")

        def _find_slot():
            return next(
                (s for s, st in self.scheduler._running.items()
                 if getattr(st.req, "request_id", None) == rid
                 and st.req.state == "running"), None)

        if _find_slot() is None:
            raise MigrationError(
                f"request {rid} is not running or parked on this "
                "engine (queued requests re-route without a ticket)")
        # fence BEFORE extraction: in-flight blocks may still carry the
        # victim's tokens; they stream to the client normally
        self._fence()
        if self.faults is not None:
            self.faults.migration_phase("extract")
        slot = _find_slot()
        if slot is None:
            # the fence's collected tokens finished (or a pending
            # cancel consumed) the sequence: nothing left to move
            raise MigrationError(
                f"request {rid} finished during the migration fence")
        # journal=False: this copy-out is a handoff, not page pressure —
        # the migrate_out event below tells the story, and a spurious
        # "preempted" would miscount real preemptions in the summary
        sw = self.scheduler.swap_out(slot, journal=False)
        sw.req.state = "migrated"
        ticket = MigrationTicket.from_swapped(
            sw, self.kv.block_size, mesh_shape=self.mesh_shape,
            adapter_digest=self._adapter_digest_for(sw))
        self._release_migrated(sw)
        if rlog is not None:
            rlog.event("migrate_out", request_id=rid,
                       replica=self.metrics.engine_label,
                       phase="running", blocks=ticket.n_blocks,
                       bytes=ticket.swap_bytes,
                       produced=ticket.produced,
                       adapter_id=ticket.adapter_id)
        return ticket

    def _adapter_digest_for(self, sw) -> bytes:
        """The content digest a migration ticket commits for the
        sequence's adapter (b"" for the base identity / adapterless) —
        read BEFORE the refcount release so the row is still pinned."""
        aid = getattr(sw, "adapter_id", 0)
        if not aid or self.adapters is None:
            return b""
        return self.adapters.digest_of(aid)

    def _release_migrated(self, sw) -> None:
        """Drop the departing sequence's adapter pin: the ticket now
        carries (id, digest), and the target re-acquires on adoption."""
        aid = getattr(sw, "adapter_id", 0)
        if aid and self.adapters is not None:
            with self._lock:
                self.adapters.release(aid)

    def migrate_in(self, ticket, on_token: Optional[Callable] = None
                   ) -> GenerationRequest:
        """Adopt a migrated sequence: validate the ticket (checksum +
        geometry — TicketError rejects it whole, nothing mutated), mint
        a fresh GenerationRequest continuing the SAME client stream
        (emitted prefix pre-loaded, so budget math and finish_reason
        land on the exact token a never-migrated run would), and park
        the sequence in the host swap pool — the resume-first rule then
        gives it STRICT priority over new admissions for freed
        pages/slots, exactly like a PR 10 preemption resume. The
        restored PRNG key row continues the per-token split chain, so
        the resumed stream is bit-identical wherever it lands.
        DRIVER-THREAD ONLY. Raises MigrationError while draining; an
        injected adopt-phase fault fires before any state changes."""
        from .migration import MigrationError

        self._refuse_grouped_migration("migrate_in")
        if self._draining:
            raise MigrationError(
                "engine is draining; migrate_in refused — not adopting "
                "new residents")
        if self.faults is not None:
            self.faults.migration_phase("adopt")
        ticket.validate_for(self)
        aid = getattr(ticket, "adapter_id", 0)
        if aid:
            # validate_for proved residency + digest match; pin the row
            # for the adopted request's lifetime, exactly as submit does
            with self._lock:
                self.adapters.acquire(aid)
        req = GenerationRequest(
            ticket.prompt, ticket.max_new, ticket.temperature,
            ticket.seed, ticket.eos_id, on_token, self.config.clock,
            request_id=f"{self.metrics.engine_label}-"
                       f"{next(self._rid_counter)}",
            adapter_id=aid)
        req.tokens = list(ticket.tokens)
        req.state = "running"
        # adoption stamps: queue_wait/ttft on THIS engine measure the
        # handoff-to-next-token gap; client-facing SLO cuts live on the
        # router's StreamHandle and span the whole migration
        req.metrics.mark_submitted()
        req.metrics.mark_admitted()
        self._swapped.append(ticket.to_swapped(req))
        self.metrics.swapped_slots = len(self._swapped)
        rlog = _request_log.get_request_log()
        if rlog is not None:
            # rerouted_from chains the journals (and retires the
            # superseded id from the in-flight set), the same link a
            # failover re-submission writes
            rlog.event("migrate_in", request_id=req.request_id,
                       replica=self.metrics.engine_label,
                       rerouted_from=ticket.request_id,
                       bytes=ticket.swap_bytes,
                       produced=ticket.produced,
                       adapter_id=aid)
        return req

    def _on_dispatch_launched(self) -> None:
        self.metrics.dispatches += 1

    def _on_prefill_chunk(self, seconds: float) -> None:
        self.metrics.prefill_chunks += 1
        self.metrics.observe_prefill_chunk(seconds)

    def _on_dispatch_timed(self, host_s: float, device_s: float) -> None:
        self.metrics.observe_dispatch_split(host_s, device_s)

    def _on_compile(self, family: str, seconds: float) -> None:
        self.metrics.observe_compile(family, seconds)

    @property
    def compile_journal(self):
        """The executable cost & compile journal (CompileJournal), or
        None unless ServingConfig(tick_profile=True)."""
        return self.scheduler.compile_journal

    def _tick_records(self) -> List[Dict[str, Any]]:
        """The /tickz perf-source provider: the bounded per-tick flight
        ring, oldest first."""
        return list(self._tick_ring) if self._tick_ring is not None \
            else []

    def _compile_snapshot(self) -> Dict[str, Any]:
        """The /compilez perf-source provider: the journal's per-family
        attribution table plus the compile-event records."""
        journal = self.scheduler.compile_journal
        if journal is None:
            return {"families": {}, "records": []}
        snap = journal.snapshot()
        snap["records"] = list(journal.records)
        return snap

    def _finish_tick(self, step_no: int, emitted: int,
                     wall: float) -> None:
        """Publish one completed tick of `wall` seconds (the duration of
        its serving/engine_step span): per-phase histogram samples, a
        flight-ring record (t_mono-stamped so serving_summary --phases
        can join it against the request log), and the journal-derived
        mfu/bytes gauges."""
        phases = self._phases
        phases["bookkeeping"] = wall - sum(phases.values())
        for phase in _TICK_PHASES:
            self.metrics.observe_tick_phase(phase, phases[phase])
        self._tick_ring.append({
            "step": step_no, "t_mono": time.monotonic(),
            "wall_s": wall, "phases": dict(phases),
            "emitted": emitted, "active": self.kv.active_count,
            "queue": len(self._queue)})
        journal = self.scheduler.compile_journal
        if journal is not None:
            self.metrics.set_perf_gauges(journal.mfu_proxy(),
                                         journal.dispatch_hbm_bytes())

    def run_until_drained(self, max_steps: Optional[int] = None) -> int:
        """Step until queue, slots, and swap pool are empty; returns
        steps taken."""
        steps = 0
        while (self._queue or self.scheduler.active_count
               or self._swapped):
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return steps

    def generate(self, prompts: Sequence, max_new_tokens: int,
                 **kw) -> List[np.ndarray]:
        """Convenience batch call: submit + drive interleaved (steps the
        engine whenever the admission queue is full, so prompt lists
        longer than max_queue flow through instead of shedding), then
        drain. Returns each prompt's full (prompt + generated) array."""
        reqs = []
        for p in prompts:
            while len(self._queue) >= self.config.max_queue:
                self.step()
            reqs.append(self.submit(p, max_new_tokens, **kw))
        self.run_until_drained()
        return [r.output() for r in reqs]

    def cancel(self, req: GenerationRequest) -> bool:
        """Abandon a request (client disconnect): drop it from the queue,
        or mark a running request for the DRIVER thread to free at the
        start of its next step() — scheduler/slot state is never touched
        from the calling thread, so cancel() is safe concurrently with a
        driver inside step()."""
        cancelled_from = None
        with self._lock:
            if req.state == "queued":
                # keyed on STATE, not queue membership: a head-of-line
                # request popped for a pages-aware admission check (and
                # possibly about to be requeued) is still cancellable —
                # the driver claims queued->running under this same
                # lock, so the cancel can never be lost
                if req in self._queue:
                    self._queue.remove(req)
                    self.metrics.queue_depth = len(self._queue)
                req.state = "cancelled"
                cancelled_from = "queued"
            elif req.state == "running":
                req.state = "cancelled"
                self._pending_cancels.append(req)
                cancelled_from = "running"
            if cancelled_from is not None:
                aid = getattr(req, "adapter_id", 0)
                if aid and self.adapters is not None:
                    # terminal unpin (safe even with the slot still
                    # live until the driver's next step: a cancelled
                    # request's emissions are swallowed, so a row
                    # reassigned meanwhile only feeds discarded tokens)
                    self.adapters.release(aid)
        if cancelled_from is None:
            return False
        rlog = _request_log.get_request_log()
        if rlog is not None:   # journal outside the lock (JSONL write)
            rlog.event("cancelled", request_id=req.request_id,
                       was=cancelled_from, tokens=len(req.tokens))
        return True

    # -- multi-tenant adapters ----------------------------------------------

    def _require_adapters(self):
        if self.adapters is None:
            raise ValueError(
                "this engine has no adapter pool "
                "(ServingConfig(max_adapters=..., adapter_rank=...))")
        return self.adapters

    def _sync_adapter_metrics(self) -> None:
        """Mirror the pool's authoritative host bookkeeping into the
        registry series (same discipline as the prefix-cache counters:
        the scrape reads exactly what the allocator knows)."""
        pool = self.adapters
        self.metrics.adapters_resident = pool.resident_count
        self.metrics.adapter_pool_bytes = pool.pool_bytes
        self.metrics.adapter_uploads = pool.uploads_total
        self.metrics.adapter_evictions = pool.evictions_total

    def upload_adapter(self, adapter_id: int, weights) -> int:
        """Install a LoRA adapter's A/B stack under `adapter_id`,
        validating geometry against the base model and LRU-evicting the
        oldest unreferenced resident under pressure. Returns the pool
        row claimed. Typed AdapterError subclasses (all ValueError) on
        bad geometry, a referenced id, or a pool with every row pinned.
        Thread-safe against submit/cancel; fixed pool shapes mean zero
        recompiles — the next dispatch simply reads the new rows."""
        pool = self._require_adapters()
        with self._lock:
            row = pool.upload(adapter_id, weights)
            self._sync_adapter_metrics()
        rlog = _request_log.get_request_log()
        if rlog is not None:   # journal outside the lock (JSONL write)
            rlog.event("adapter_upload", engine=self.metrics.engine_label,
                       adapter_id=int(adapter_id), row=row,
                       resident=pool.resident_count)
        return row

    def evict_adapter(self, adapter_id: int) -> None:
        """Explicitly drop a resident adapter, freeing its pool row.
        AdapterReferencedError while any live request pins it;
        UnknownAdapterError if it is not resident."""
        pool = self._require_adapters()
        with self._lock:
            pool.evict(adapter_id)
            self._sync_adapter_metrics()
        rlog = _request_log.get_request_log()
        if rlog is not None:
            rlog.event("adapter_evict", engine=self.metrics.engine_label,
                       adapter_id=int(adapter_id),
                       resident=pool.resident_count)

    # -- observability ------------------------------------------------------

    def close(self) -> None:
        """Retire the engine: remove its labeled series from the global
        metrics registry so scrapes stop reporting a dead engine (a
        long-lived service recreating engines must not accumulate dead
        labels), and release this engine's debug-server reference
        (inference.create_engine(debug_port=...)) — the shared server
        stops only when the last referencing engine closes, so rolling
        replacement never kills diagnostics under a live engine.
        stats()/metrics keep working locally afterwards."""
        self.metrics.unregister()
        if self._tick_ring is not None:
            # drop the /tickz + /compilez provider closures — the
            # perf-source registry must never outlive the engine it
            # reads from
            from ..observability import debug_server as _dbg
            _dbg.unregister_perf_source("tick",
                                        self.metrics.engine_label)
            _dbg.unregister_perf_source("compile",
                                        self.metrics.engine_label)
        if self._debug_server_ref is not None:
            from ..observability.debug_server import release_debug_server
            token, self._debug_server_ref = self._debug_server_ref, None
            release_debug_server(token)

    def stats(self) -> Dict[str, Any]:
        s = self.metrics.snapshot()
        s.update(self.kv.occupancy())
        s["queue_depth"] = len(self._queue)
        # quantization identity next to the pool numbers (occupancy
        # already carries kv_dtype): which weight path this engine
        # serves and the bytes it actually holds
        s["weight_dtype"] = self._weight_dtype
        s["weight_bytes"] = self.weight_bytes
        # host memory the swap pool currently pins (0 when nothing is
        # preempted — the pool exists only under pressure)
        s["swap_pool_bytes"] = sum(sw.swap_bytes for sw in self._swapped)
        # adapter pool occupancy (multi-tenant serving): resident count,
        # device bytes the pool pins, cumulative upload/eviction totals
        if self.adapters is not None:
            s.update(self.adapters.occupancy())
        s["compiled_executables"] = self.scheduler.compile_count
        # what the PROCESS traced, lowered, compiled or loaded so far, in
        # sums; /compilez and `compile_log().snapshot()` have the table,
        # an executable a row (this engine's carry the scheduler's tags)
        s["compile"] = compile_log().totals()
        # the admissions' first tokens: fetches made (one a tick that
        # admitted), tokens they carried, and those read with a dispatch
        # already launched behind their sampler (the device had work
        # queued while the host waited)
        for name in ("first_token_waits", "first_tokens",
                     "first_tokens_behind_launch"):
            s[name] = getattr(self.scheduler, name)
        # "paged_kernel", "latent_paged_kernel" or "gather": the decode
        # step's attention path
        s["decode_attention"] = self.scheduler.decode_attention
        # the prefill's: "flash" if a cold prompt attends over its own
        # rows through the flash forward in some bucket (`flash_buckets`
        # says which), else what the largest bucket runs ("gather";
        # None: the model does not say), how many prefills were
        # dispatched cold under either and warm (rows already cached: a
        # prefix hit, a later chunk; gathers), and the tiles the cold
        # flash prefills' walks visited of those their buckets hold
        # (`tiles_visited`, `tiles_in_bucket`: 1.0 where every prompt
        # fills its bucket)
        verdicts = self.scheduler.prefill_attention
        flash = [b for b, path in verdicts.items() if path == "flash"]
        s["prefill_attention"] = dict(
            self.scheduler.prefill_counts, flash_buckets=flash,
            path="flash" if flash else verdicts[max(verdicts)])
        if len(self.kv.group_layout) > 1:
            # every cache group's layers prefill by the one verdict (a
            # window group's flash forward is the banded one), and
            # `decode_attention` above is the model's {group: path}
            s["prefill_attention"]["groups"] = {
                g.spec.name: s["prefill_attention"]["path"]
                for g in self.kv.group_layout if not g.spec.state}
        # the served architecture, what a token costs the arena in a
        # layer, and the model's own in-graph counters (a routed model's
        # `expert_tokens` and `router_tokens` since start)
        s["model"] = self.model.name
        s.update(self.model.describe(self.cfg))
        state = self.kv.state_occupancy()
        if state is not None:
            # the state groups' blocks (one a slot a group) and bytes a
            # slot, beside what the model says of its recurrence
            # (`describe`: which path it took, the prefill's chunk)
            s["state"] = dict(state, **s.get("state", {}))
        diffusion = self.model.diffusion(self.cfg)
        if diffusion is not None:
            # generation by diffusion over blocks: the model's parameters,
            # what the loop's counters say a block and a pass came to, and
            # the time to the first committed BLOCK (a request's first
            # tokens arrive with it: `mean_ttft` is this number)
            counted = self.scheduler.model_counters
            passes = int(counted["block_passes"])
            blocks = int(counted["blocks_committed"])
            s["diffusion"] = dict(
                diffusion,
                passes_per_block=passes / blocks if blocks else None,
                tokens_per_pass=self.scheduler.block_tokens / passes
                if passes else None,
                mean_time_to_first_block=s.get("mean_ttft"))
            s["prefix_cache"] = "off: a hit would need a block-causal " \
                "warm prefill"
        s["cache_row_bytes"] = self.kv.cache_row_bytes
        for name, value in self.scheduler.model_counters.items():
            s[name] = value.tolist()
        # the registry label this engine's serving_* series carry, so a
        # caller can find them in observability.get_registry().snapshot()
        s["engine_label"] = self.metrics.engine_label
        return s
