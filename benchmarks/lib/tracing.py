"""The profiler around part of a window, and jax's own compile events."""

import shutil
import time

from . import trace_reduce

# Host spans the benchmark's own files record (jax.profiler.TraceAnnotation,
# in modes/train.py); an idle gap on the device is named by the one that
# covers it. Spans inside the program (an engine tick's admit, prefill, chunk
# and stream) are a later tracing issue: add their names here when they exist.
HOST_SPANS = ("feed", "step")


class TracedWindow:
    """start() .. stop() bracket the traced seconds with a `bench_window` span
    on the profiler's clock. The Python tracer stays off: with tens of client
    threads it would write more events than the device does."""

    def __init__(self, trace_dir):
        self.trace_dir = trace_dir
        self._span = None

    def start(self):
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._span = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
        self._span.__enter__()

    def stop(self):
        import jax

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def summary(self):
        path = trace_reduce.find_xplane(self.trace_dir)
        if path is None:
            return None
        return trace_reduce.reduce(trace_reduce.load(path), HOST_SPANS)


def trace_for(ctx, seconds):
    """Runs on the caller's thread: traces `seconds` from now when the run is
    a traced one, and returns the reduced summary (None otherwise)."""
    if not ctx.trace:
        return None
    window = TracedWindow(ctx.out_path("trace"))
    window.start()
    time.sleep(seconds)
    window.stop()
    return window


class CompileWatch:
    """Sums jax's compile events: seconds in the backend's compiler (or in
    loading from the persistent cache) and the cache's hits and misses."""

    def __init__(self):
        import jax.monitoring

        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += seconds

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return {"compile_s": self.compile_s, "cache_hits": self.hits,
                "cache_misses": self.misses}
