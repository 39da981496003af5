"""What a start costs, told where the work happens: one record for every
executable jax traces, lowers, compiles or loads from its persistent cache.

jax announces each phase as it runs (`jax/_src/dispatch.py::
LogElapsedTimeContextManager`: a scalar at entry, a duration at exit, each
with `fun_name`; `jax/_src/compiler.py`: whether the cache was asked and
whether it hit). `install()` registers listeners for those events, and the
log turns them into

* ONE RECORD an executable: `fun_name`, the layer's own `tag` (the serving
  scheduler's `prefill:L2048`, the executor's `program:<id>` with the `cause`
  of the miss), thread, `begin_ns` on `time.monotonic_ns` (the tracer's
  clock), seconds of `trace`, `lower`, `backend_compile` or `cache_load`,
  `cache: hit | miss | off`, and `inner_traces` (count and seconds). Nesting
  is per thread: a jit traced INSIDE another's trace or lowering (a Pallas
  body under `jax.jit(inline=True)`, `_flash_bwd_call`) is the outer
  record's `inner_traces`, never a second executable, and its seconds are
  part of the outer phase's and counted once. A whole executable compiled
  inside another's trace (a constant evaluated eagerly) is a record of its
  own, `nested`, and its lowering and compile are taken OUT of the outer
  trace's seconds.
* SPANS of the one tracer (`tracer.trace_span`): `compile/trace`,
  `compile/lower`, `compile/backend` opened at jax's entry scalar and closed
  at its duration, so that with the ring on they nest under whatever the
  thread was in (`executor/dispatch` of a first step, `serving/tick/admit` of
  a first request), and under any profiler session they lie in the xplane on
  the device's clock. A HIT's whole backend phase (the key, the read, the
  executable onto the device: no compiler ran) is `cache_load` in the record
  and `compile/cache_load` in the ring; jax says `hit` only inside the phase,
  so the span is opened as `compile/backend` and renamed as it closes, and
  the xplane, which takes a name at entry, keeps `compile/backend` there.
* PHASES that are not jax's but belong to a start: `setup/import`
  (`paddle_tpu/__init__.py` stamps the clock at its first and last line),
  and whatever a layer brackets with `phase()` (`serving/engine_build`,
  and `serving/engine_build/jits` on the drive thread at the first request).
* registry counters `compile_phase_seconds_total{phase}` and
  `compile_cache_total{result}`.

`cache` is `hit` where the executable was read back, `miss` where the cache
was asked and the compiler ran (jax's own `cache_misses` event counts only
the entries it then WROTE; with the write thresholds at zero, as the
benchmark sets them, the two agree), `off` where no cache was asked.

Records made under `probing()` (a second lowering for `cost_analysis`, the
executor's `capture_hlo` and `_analyze_compile`) are a second look at an
executable that exists: marked `probe`, in no sum and no count of
executables. But jax keeps what a look made: where the look comes BEFORE the
first dispatch (the executor's two) it IS that executable's one compile, and
the dispatch behind it finds jax's trace and executable. So a look that
reaches the backend is counted like any other executable, and is marked
`probe` only if the very next executable the thread makes is the same module
under the same tag reaching the backend again (jax compiled twice; the
registry's counters, being counters, keep both).

The log is bounded and always on. It is written only while jax traces,
lowers, compiles or loads: O(executables) a process, nothing in the steady
state (`calls` counts the listeners' invocations; tests pin it across warm
steps and ticks). Stdlib-only on import: jax is imported by `install()`.
"""

from __future__ import annotations

import contextlib
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from . import metrics as _metrics
from . import tracer as _tracer

__all__ = ["CompileLog", "compile_log", "PHASES"]

_EVENT_PHASE = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
# the order jax runs an executable's phases in: a phase at or before the
# open record's last one starts the next executable
_ORDER = {"trace": 0, "lower": 1, "backend": 2}
# the seconds a record (and every sum) holds
PHASES = ("trace", "lower", "backend_compile", "cache_load")
_SPAN_OF = {"backend_compile": "backend"}     # compile/<this> in `spans`
IMPORT_PHASE = "setup/import"


class _Frame:
    """One of jax's phases open on a thread."""

    __slots__ = ("phase", "fun_name", "begin_ns", "span", "record",
                 "nested_ns")

    def __init__(self, phase, fun_name, begin_ns, span, record):
        self.phase = phase
        self.fun_name = fun_name
        self.begin_ns = begin_ns
        self.span = span            # the tracer's live span, or None
        self.record = record        # None for an inner trace
        self.nested_ns = 0          # whole executables compiled inside


class _ThreadState(threading.local):
    def __init__(self):
        self.stack: List[_Frame] = []
        # the executable whose phases are still arriving, at top level
        # and inside another's trace
        self.open: Dict[bool, Optional[Dict[str, Any]]] = {False: None,
                                                          True: None}
        self.probing = 0
        # the executable a look compiled, until the thread makes the next
        self.looked: Optional[Dict[str, Any]] = None


def _same_executable(a, b):
    """Records of one module under one tag (jax names a trace by the
    function and what follows by the module, `jit(<function>)`)."""
    def bare(name):
        return name[4:-1] if name.startswith("jit(") else name
    return (a is not None and a["tag"] == b["tag"]
            and bare(a["fun_name"]) == bare(b["fun_name"]))


def _new_record(fun_name, begin_ns, probe, nested):
    t = threading.current_thread()
    return {"fun_name": fun_name, "tag": None, "cause": None,
            "probe": probe, "nested": nested,
            "thread": t.name, "tid": t.ident, "begin_ns": begin_ns,
            "trace_s": 0.0, "lower_s": 0.0, "backend_compile_s": 0.0,
            "cache_load_s": 0.0, "cache": None,
            "inner_traces": 0, "inner_trace_s": 0.0, "inner_by_name": {},
            # [name, begin_ns, end_ns] of each of its phases: what a
            # reader takes unions of
            "spans": [], "_last": -1, "_after": None}


class CompileLog:
    """The process's compile log (`compile_log()`); a test may build its
    own and `install()` it beside."""

    def __init__(self, capacity: int = 4096):
        self._lock = threading.Lock()
        self._records: "deque[Dict[str, Any]]" = deque(maxlen=int(capacity))
        self._phases: "deque[Dict[str, Any]]" = deque(maxlen=int(capacity))
        self._recorded = 0
        self._local = _ThreadState()
        self._import_noted = False
        # module name -> the newest (tag, cause) a trace of it was given:
        # jax keeps traces, so an executable made from a trace it had
        # (the dispatch after a probe's lowering) runs no body to tag it
        self._tags: Dict[str, Any] = {}
        # invocations of the jax listeners: the steady state makes none
        self.calls = 0

    # -- installation --------------------------------------------------------

    def _listeners(self):
        """(registered so far, how to register, ours) for each of jax's
        three lists the log listens on."""
        from jax._src import monitoring

        return ((monitoring.get_scalar_listeners(),
                 monitoring.register_scalar_listener, self._on_scalar),
                (monitoring.get_event_duration_listeners(),
                 monitoring.register_event_duration_secs_listener,
                 self._on_duration),
                (monitoring.get_event_listeners(),
                 monitoring.register_event_listener, self._on_event))

    def install(self) -> "CompileLog":
        """Registers the listeners where they are not registered (by
        looking: a test that clears jax's lists calls this again) and
        notes the package's import. `import paddle_tpu` makes the one
        call the program needs, at its last line."""
        missing = [(register, cb) for registered, register, cb
                   in self._listeners() if cb not in registered]
        if missing:
            # a phase heard to open while some listeners were gone will
            # never be heard to close: every thread starts afresh
            self._local = _ThreadState()
            for register, cb in missing:
                register(cb)
        if not self._import_noted:
            self._note_import()
        return self

    def installed(self) -> bool:
        return all(cb in registered
                   for registered, _, cb in self._listeners())

    def _note_import(self) -> None:
        package = sys.modules.get(__name__.split(".")[0])
        begin = getattr(package, "_IMPORT_BEGIN_NS", None)
        end = getattr(package, "_IMPORT_END_NS", None)
        if begin is None or end is None:
            return                      # the package is still importing
        self._import_noted = True
        self.note_phase(IMPORT_PHASE, begin, end)

    # -- what the layers say -------------------------------------------------

    def note_tag(self, tag: str, cause: Optional[str] = None) -> None:
        """Called from a jitted body, so exactly while jax traces a new
        executable: the executable open on this thread is `tag`."""
        st = self._local
        if st.stack and st.stack[0].record is not None:
            record = st.stack[0].record
            record["tag"], record["cause"] = tag, cause
            self._tags[f"jit({st.stack[0].fun_name})"] = (tag, cause)
            st.stack[0].span.args["tag"] = tag

    @contextlib.contextmanager
    def probing(self):
        """Executables made in the body (this thread) are a second look
        at one that exists: marked `probe`, in no sum (but see the
        module's text: a look that compiles what jax did not have is that
        executable's compile)."""
        st = self._local
        st.probing += 1
        try:
            yield
        finally:
            st.probing -= 1
            for nested, record in st.open.items():
                # a probe that stopped at the lowering: what jax does
                # next on this thread is not its compile
                if record is not None and record["probe"]:
                    st.open[nested] = None

    @contextlib.contextmanager
    def phase(self, name: str, args: Optional[Dict[str, Any]] = None):
        """A stretch of a start that is not jax's (`serving/engine_build`):
        a span of the one tracer, kept here as a phase whether or not the
        ring is on. For what happens once a process or an engine, and
        only where a metric or a reader's union needs the seconds."""
        with _tracer.trace_span(name, "setup", args) as sp:
            try:
                yield sp
            finally:
                self.note_phase(name, sp.begin_ns, time.monotonic_ns())

    def note_phase(self, name: str, begin_ns: int, end_ns: int) -> None:
        t = threading.current_thread()
        with self._lock:
            self._phases.append({"phase": name, "thread": t.name,
                                 "tid": t.ident, "begin_ns": int(begin_ns),
                                 "seconds": (end_ns - begin_ns) * 1e-9})

    # -- jax's events ----------------------------------------------------------

    def _on_scalar(self, event, value, fun_name="", **_):
        self.calls += 1
        phase = _EVENT_PHASE.get(event)
        if phase is None:
            return
        st = self._local
        nested = bool(st.stack)
        now = time.monotonic_ns()
        if nested and phase == "trace":
            # a jit traced inside another's trace or lowering
            st.stack.append(_Frame(phase, fun_name, now, None, None))
            return
        record = st.open[nested]
        if record is None or _ORDER[phase] <= record["_last"]:
            record = _new_record(fun_name, now, st.probing > 0, nested)
            st.open[nested] = record
            if not (nested or st.probing):
                # the one executable that may be what a look compiled
                record["_after"], st.looked = st.looked, None
            with self._lock:
                self._recorded += 1
                self._records.append(record)
        record["_last"] = _ORDER[phase]
        args = {"fun_name": fun_name}
        if record["tag"] is not None:
            args["tag"] = record["tag"]
        span = _tracer.trace_span("compile/" + phase, "compile", args)
        span.__enter__()
        st.stack.append(_Frame(phase, fun_name, now, span, record))

    def _on_duration(self, event, seconds, fun_name="", **_):
        self.calls += 1
        phase = _EVENT_PHASE.get(event)
        if phase is None:
            return
        st = self._local
        if not st.stack or st.stack[-1].phase != phase:
            return      # opened before the listeners were: nothing to close
        frame = st.stack.pop()
        now = time.monotonic_ns()
        if frame.record is None:
            self._close_inner(st, frame, now)
            return
        record = frame.record
        if record["tag"] is None:       # no body ran: jax had the trace
            name = frame.fun_name
            record["tag"], record["cause"] = self._tags.get(
                name if phase != "trace" else f"jit({name})", (None, None))
        own_s = max(0, now - frame.begin_ns - frame.nested_ns) * 1e-9
        cache = None
        if phase == "backend":
            cache = record["cache"] = record["cache"] or "off"
            frame.span.args["cache"] = cache
            # a hit's whole phase is loading: the key, the read, the
            # executable onto the device; no compiler ran
            phase = "cache_load" if cache == "hit" else "backend_compile"
            frame.span.name = "compile/" + _SPAN_OF.get(phase, phase)
            st.open[record["nested"]] = None    # the executable is whole
            self._settle_look(st, record)
        frame.span.__exit__(None, None, None)
        with self._lock:
            record[phase + "_s"] += own_s
            record["spans"].append(["compile/" + _SPAN_OF.get(phase, phase),
                                    frame.begin_ns, now])
        if st.stack and all(f.phase == "trace" for f in st.stack[1:]):
            st.stack[0].nested_ns += now - frame.begin_ns
        if not record["probe"]:
            self._count(phase, own_s, cache)

    def _settle_look(self, st, record):
        """At the backend's end: which of a look and the executable made
        right behind it is the second look."""
        if record["probe"]:
            if not _same_executable(st.looked, record):
                # jax had no such executable: this IS its compile, and the
                # dispatch behind the look will find it
                record["probe"] = False
                for done in ("trace", "lower"):
                    if record[done + "_s"]:
                        self._count(done, record[done + "_s"], None)
                st.looked = record
        elif _same_executable(record["_after"], record):
            record["_after"]["probe"] = True    # jax compiled it again
        record["_after"] = None

    def _close_inner(self, st, frame, now):
        outer = st.stack[0].record
        if outer is None:
            return
        with self._lock:
            outer["inner_traces"] += 1
            by_name = outer["inner_by_name"].setdefault(frame.fun_name,
                                                        [0, 0.0])
            by_name[0] += 1
            if len(st.stack) == 1:      # a child of the outer phase itself:
                seconds = (now - frame.begin_ns) * 1e-9     # counted once
                outer["inner_trace_s"] += seconds
                by_name[1] += seconds

    def _on_event(self, event, **_):
        self.calls += 1
        if event not in (_CACHE_ASKED, _CACHE_HIT):
            return
        st = self._local
        if st.stack and st.stack[-1].phase == "backend":
            record = st.stack[-1].record
            if event == _CACHE_HIT:
                record["cache"] = "hit"
            elif record["cache"] is None:
                record["cache"] = "miss"

    def _count(self, phase, seconds, cache):
        reg = _metrics.get_registry()
        reg.counter("compile_phase_seconds_total",
                    "seconds jax spent making executables, by phase "
                    "(trace, lower, backend_compile, cache_load)"
                    ).labels(phase=phase).inc(seconds)
        if cache is not None:
            reg.counter("compile_cache_total",
                        "executables by what the persistent compile cache "
                        "did (hit, miss, off)").labels(result=cache).inc()

    # -- reading ---------------------------------------------------------------

    def records(self, include_probes: bool = True,
                limit: Optional[int] = None) -> List[Dict[str, Any]]:
        """Copies of the per-executable records (the newest `limit`; all
        by default), oldest first, each with `seconds` (trace + lower +
        backend_compile + cache_load)."""
        with self._lock:
            kept = list(self._records)
            if limit is not None:
                kept = kept[-limit:] if limit else []
            rows = [{k: v for k, v in r.items() if not k.startswith("_")}
                    for r in kept if include_probes or not r["probe"]]
            for row in rows:
                row["inner_by_name"] = dict(row["inner_by_name"])
                row["spans"] = [list(s) for s in row["spans"]]
        for row in rows:
            row["seconds"] = sum(row[p + "_s"] for p in PHASES)
        return rows

    def phases(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(p) for p in self._phases]

    def seconds_of(self, tag: str) -> float:
        """What the newest executable a layer tagged `tag` cost (trace +
        lowering + compile or load), probes apart; 0.0 with none."""
        with self._lock:
            for r in reversed(self._records):
                if r["tag"] == tag and not r["probe"]:
                    return sum(r[p + "_s"] for p in PHASES)
        return 0.0

    def totals(self) -> Dict[str, Any]:
        """The sums over every record kept, probes apart."""
        with self._lock:
            counted = [r for r in self._records if not r["probe"]]
            out: Dict[str, Any] = {p + "_s": sum(r[p + "_s"] for r in counted)
                                   for p in PHASES}
            out.update(
                # an executable reached the backend; a trace alone did not
                # (`jax.eval_shape` of a jitted function: shape inference)
                executables=sum(r["cache"] is not None for r in counted),
                traces_alone=sum(r["cache"] is None and not r["lower_s"]
                                 for r in counted),
                probes=len(self._records) - len(counted),
                inner_traces=sum(r["inner_traces"] for r in counted),
                inner_trace_s=sum(r["inner_trace_s"] for r in counted),
                cache={k: sum(r["cache"] == k for r in counted)
                       for k in ("hit", "miss", "off")})
        return out

    def snapshot(self, limit: Optional[int] = None) -> Dict[str, Any]:
        """The table `/compilez` and the benchmark's reader show: the
        newest `limit` executables (all by default), the phases, and
        `totals()`."""
        with self._lock:
            dropped = self._recorded - len(self._records)
        return {"listener_calls": self.calls, "dropped": dropped,
                "totals": self.totals(), "phases": self.phases(),
                "executables": self.records(limit=limit)}

    def clear(self) -> None:
        """Forgets the records (a test's clean slate); the listeners and
        the import's phase stay."""
        with self._lock:
            self._records.clear()
            self._recorded = 0
            kept = [p for p in self._phases if p["phase"] == IMPORT_PHASE]
            self._phases.clear()
            self._phases.extend(kept)


_GLOBAL = CompileLog()


def compile_log() -> CompileLog:
    """The process-wide log every layer tags and every surface reads."""
    return _GLOBAL
