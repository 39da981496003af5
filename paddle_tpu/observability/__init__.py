"""paddle_tpu.observability: tracing, metrics, export, live diagnostics.

The framework-wide observability subsystem (reference: platform/profiler
+ tools/timeline.py + the pserver monitor surface, grown into a
first-class layer):

* `tracer` — thread-safe ring-buffer span recorder with a near-no-op
  disabled path. The executor (per-op spans behind FLAGS_trace_ops),
  the serving engine/scheduler, the distributed communicator, the
  parallel collectives, and the legacy `paddle_tpu.profiler` API all
  record here. `request_scope(rid)` tags every span a thread records
  with a request id, so one request's timeline is reconstructable.
* `metrics` — process-wide registry of labeled counters / gauges /
  histograms with JSON snapshot and Prometheus text export; the
  serving engine's TTFT/TPOT metrics, the executor's progress
  heartbeats, and the HTTP service plane's per-tenant request
  counters + router gauges (`paddle_tpu.server`:
  `server_requests_total{router,tenant,code}`,
  `server_active_streams`, ...) are its tenants.
* `export` — chrome://tracing (catapult) JSON writer + per-span
  self-time rollup; `tools/trace_summary.py` is the CLI.
* `debug_server` — live diagnostics HTTP plane (stdlib-only):
  `/metrics`, `/healthz`, `/varz`, `/tracez` (`?request_id=`,
  `?chrome=1`), `/stacksz`. `start_debug_server(port=0)` returns the
  bound port; `inference.create_engine(..., debug_port=)` wires it in.
* `train_stats` — training telemetry plane: `StepLogger` per-step
  scalars (loss, lr, global grad-norm, examples/s, tokens/s, step
  wall-time, estimated MFU) into the registry + a rotating JSONL log,
  the in-graph numerics sentinel (warn / skip_step / halt on a
  non-finite step, one flag fetched with the existing outputs), and
  the Executor's recompilation-attribution log; `/trainz` serves it,
  `tools/train_summary.py` renders the JSONL.
* `request_log` — serving request-lifecycle event log: the StepLogger
  idiom applied to serving — every transition a request moves through
  (submitted/queued/shed, routed, admitted, prefill, each decode
  dispatch, preempted/swapped-in, failover, finished with
  finish_reason) journaled with monotonic stamps + request_id into a
  rotating JSONL + in-memory ring; `/requestz` serves it live,
  `tools/serving_summary.py` renders per-request phase timelines.
  Uninstalled (the default) it costs one attribute read per
  transition — streams and registry series bit-identical.
* `watchdog` — stall watchdog + flight recorder: a daemon thread that
  watches the engine/executor progress heartbeats in the registry and
  dumps stacks + spans + a metrics snapshot into a bounded-retention
  `flight_<ts>/` directory when a busy component stops moving;
  `dump_flight_record()` drives the same path manually, and overload
  sheds and firing alerts can trigger it too.
* `timeseries` — bounded in-process time-series history over the
  registry: opted-in families sample into fixed rings of
  (monotonic_ts, value) points with windowed `rate()`/`delta()`/
  `p_quantile()` derivations — the "is it getting worse" layer the
  snapshot surfaces can't answer.
* `alerts` — declarative alert engine over the store: `AlertRule`s
  with fire/clear hold-downs, built-in multi-window SLO burn-rate +
  anomaly detectors, `server_alerts_firing` gauges, a transition ring
  at `/alertz` (+ `/statusz` health-score rollup), one watchdog flight
  record per firing episode, and a `pressure_hint()` the router's
  rebalancer consumes. `FleetHealth` wires store + sampler + engine in
  one call (`Router(health=HealthConfig())`).

* `compile_log` — what a start costs: one record for every executable
  jax traces, lowers, compiles or loads from its persistent cache (seconds
  by phase, `cache: hit | miss | off`, the layer's own tag, the jits
  traced inside it), fed by jax's own monitoring events where the work
  happens, the same events as `compile/*` spans of the tracer, and the
  phases of a start that are not jax's (`setup/import`,
  `serving/engine_build`). `/compilez` serves its table,
  `compile_log().snapshot()` gives it to a trainer, and
  `engine.stats()["compile"]` carries its sums.

Quick start:

    import paddle_tpu as pt
    pt.observability.enable_tracing()
    port = pt.observability.start_debug_server()   # curl :port/metrics
    pt.observability.start_watchdog(stall_threshold=30)
    exe.run(main, feed=..., fetch_list=[loss])     # per-op spans
    pt.observability.export_chrome_trace("/tmp/trace.json")

Stdlib-only on import: safe to import anywhere in the framework with no
jax side effects.
"""

from . import (alerts, debug_server, export, metrics,  # noqa: F401
               request_log, timeseries, tracer, train_stats, watchdog)
from .alerts import (AlertEngine, AlertRule, FleetHealth, HealthConfig,
                     builtin_rules)
from .compile_log import CompileLog, compile_log
from .debug_server import (DebugServer, get_debug_server,
                           start_debug_server, stop_debug_server)
from .export import export_chrome_trace, self_times, summarize
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      get_registry)
from .request_log import (RequestLog, get_request_log,
                          install_request_log, request_logging,
                          uninstall_request_log)
from .tracer import (Span, Tracer, current_request_id, disable_tracing,
                     enable_tracing, get_tracer, request_scope, trace_span,
                     tracing_enabled)
from .timeseries import Sampler, TimeSeriesStore
from .train_stats import (StepLogger, attach_step_telemetry,
                          get_step_logger, install_step_logger,
                          recompile_log, step_logging,
                          uninstall_step_logger)
from .watchdog import (FlightRecorder, ProgressMonitor, Watchdog,
                       dump_flight_record, format_all_stacks, get_watchdog,
                       notify_alert, start_watchdog, stop_watchdog)

__all__ = [
    "Span", "Tracer", "get_tracer", "trace_span", "enable_tracing",
    "disable_tracing", "tracing_enabled", "request_scope",
    "current_request_id",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "get_registry",
    "CompileLog", "compile_log",
    "export_chrome_trace", "self_times", "summarize",
    "DebugServer", "start_debug_server", "stop_debug_server",
    "get_debug_server",
    "Watchdog", "FlightRecorder", "ProgressMonitor", "start_watchdog",
    "stop_watchdog", "get_watchdog", "dump_flight_record",
    "format_all_stacks",
    "StepLogger", "install_step_logger", "uninstall_step_logger",
    "get_step_logger", "step_logging", "attach_step_telemetry",
    "recompile_log",
    "RequestLog", "install_request_log", "uninstall_request_log",
    "get_request_log", "request_logging",
    "TimeSeriesStore", "Sampler",
    "AlertRule", "AlertEngine", "FleetHealth", "HealthConfig",
    "builtin_rules", "notify_alert",
]
