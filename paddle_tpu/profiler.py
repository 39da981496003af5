"""Profiling (reference: python/paddle/fluid/profiler.py + platform/profiler.h
RecordEvent / platform/device_tracer.cc CUPTI capture).

Thin adapter over `paddle_tpu.observability`. Host spans have ONE
source, `observability.trace_span`: every span it opens (the executor's
`executor/run` and its phases, the engine's `serving/engine_step` and
its `serving/tick/*` phases, `RecordEvent`, which is an alias of it) is
a `jax.profiler.TraceAnnotation` around its body, so whoever starts a
profiler session (this module, `jax.profiler.start_trace`, a TensorBoard
capture) finds the program's phases on the `/host:CPU` lines of the
xplane, on the clock of the device's `XLA Ops` (the analog of the
reference's host event table + CUPTI DeviceTracer merged timeline); the
same spans land in the tracer's ring (`/tracez`, chrome export) while
that is enabled. The executor annotates every lowered op with
jax.named_scope so op-level names survive into XLA metadata.

start_profiler/profiler() drive BOTH: they start a jax xplane trace and
enable the observability tracer; stop_profiler stops the xplane trace
and drops a `host_spans.json` chrome trace of the recorded host spans
into the trace directory. For tracer-only (no jax trace) capture, use
`paddle_tpu.observability.enable_tracing()` directly.
"""

from __future__ import annotations

import contextlib
import os

from .observability import export as _obs_export
from .observability import metrics as _obs_metrics
from .observability import tracer as _obs_tracer

__all__ = ["profiler", "start_profiler", "stop_profiler", "RecordEvent",
           "cuda_profiler", "record_event"]

_active_dir = None
_tracer_was_enabled = False  # tracer state to restore at stop_profiler


def start_profiler(state: str = "All", log_dir: str = "/tmp/paddle_tpu_prof"):
    """reference: profiler.py start_profiler → core.EnableProfiler. Starts
    a jax xplane trace AND enables the observability tracer. A second
    start while profiling is absorbed (like stop without start), and no
    profiler state mutates unless jax's trace actually started — a failed
    start must not leave the tracer stuck on or repoint the active dir."""
    global _active_dir, _tracer_was_enabled
    import jax

    if _active_dir is not None:
        return
    jax.profiler.start_trace(log_dir)   # may raise: state untouched above
    _tracer_was_enabled = _obs_tracer.tracing_enabled()
    _obs_tracer.enable_tracing()
    _active_dir = log_dir


def stop_profiler(sorted_key=None, profile_path=None):
    """Stop the active trace and return its directory. Safe no-op (returns
    None) when no trace is active — the reference's stop without start is
    a user error we absorb, and it makes the profiler() context manager
    exception-safe when the body already stopped the trace itself.

    Also exports the host spans recorded since start_profiler as
    `<dir>/host_spans.json` (chrome-trace JSON) plus a metrics-registry
    snapshot as `<dir>/metrics.json` (the same numbers the debug
    server's /varz serves, frozen at trace stop), and restores the
    tracer to its pre-start enabled/disabled state."""
    global _active_dir
    if _active_dir is None:
        return None
    import jax

    d = _active_dir
    _active_dir = None
    if not _tracer_was_enabled:
        _obs_tracer.disable_tracing()  # restore; spans stay readable
    try:
        jax.profiler.stop_trace()
    except RuntimeError:
        # the trace was torn down behind our back (e.g. jax-level
        # stop_trace inside the profiler() body): already stopped is the
        # state we wanted
        return None
    try:
        _obs_export.export_chrome_trace(os.path.join(d, "host_spans.json"))
        with open(os.path.join(d, "metrics.json"), "w") as f:
            f.write(_obs_metrics.get_registry().to_json(indent=2))
    except OSError:
        pass  # trace dir vanished (reset_profiler mid-flight): device
        # trace already stopped cleanly, host spans stay in the ring
    return d


@contextlib.contextmanager
def profiler(state: str = "All", sorted_key=None,
             profile_path: str = "/tmp/paddle_tpu_prof"):
    """fluid.profiler.profiler context manager analog. The trace directory
    is TensorBoard-loadable (the timeline.py analog is `tensorboard
    --logdir`). Double-stop safe: if the body raises after the trace was
    already stopped (or stops it explicitly), the exit path no-ops instead
    of raising over the original exception."""
    start_profiler(state, profile_path)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def cuda_profiler(*a, **kw):  # API parity; device tracing is always on
    with profiler():
        yield


def RecordEvent(name: str, **args):
    """RAII profiling range (reference: platform/profiler.h:81), usable
    as a context manager: an alias of `observability.trace_span(name,
    "record_event", args)`. The span is an event in any profiler trace
    that is being taken (a `/host:CPU` line of the xplane, beside the
    device's operations) and, while the ring is enabled, in `/tracez`
    and the chrome export; keyword args (e.g. byte counts) ride on
    both."""
    return _obs_tracer.trace_span(name, "record_event", args or None)


record_event = RecordEvent


def reset_profiler():
    """reference: profiler.py reset_profiler — drop collected events so the
    next start_profiler begins clean."""
    import glob
    import shutil
    _obs_tracer.get_tracer().clear()
    for d in glob.glob("/tmp/paddle_tpu_prof*"):
        shutil.rmtree(d, ignore_errors=True)
