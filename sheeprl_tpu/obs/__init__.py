"""Run-telemetry subsystem (observability layer).

One instrumentation vocabulary for the whole framework:

- :class:`~sheeprl_tpu.obs.span.span` — context-decorator that puts the SAME
  section name into the wall-clock metric registry (the old ``timer``), the
  XLA/Perfetto trace (``jax.profiler.TraceAnnotation``) and the per-process
  ``telemetry.jsonl`` event stream.
- :class:`~sheeprl_tpu.obs.recompile.CompileWatchdog` — ``jax.monitoring``
  subscriber that turns every trace+lower into a ``compile`` event and raises
  a loud warning on post-warmup recompiles (silent retracing is the #1 TPU
  perf killer).
- :class:`~sheeprl_tpu.obs.telemetry.RunTelemetry` — the per-run sink: JSONL
  writer, low-rate device poller (HBM in-use/peak, optional link RTT) and the
  per-log-interval ``heartbeat`` assembly (SPS, duty cycle, MFU, HBM peak,
  recompile count).

The event schema is documented in ``howto/telemetry.md``; ``tools/report.py``
reads the same stream (``telemetry_summary``) so the report and the run
show the same numbers. Everything is inert unless
``metric.telemetry.enabled=True`` — the disabled hot path is one global read.
"""

from sheeprl_tpu.obs.heartbeat import log_sps_and_heartbeat
from sheeprl_tpu.obs.profile import TriggeredProfiler
from sheeprl_tpu.obs.registry import append_run_record, build_run_record, read_run_records, register_run
from sheeprl_tpu.obs.span import TimerError, span
from sheeprl_tpu.obs.telemetry import (
    RunTelemetry,
    configure_telemetry,
    get_telemetry,
    shutdown_telemetry,
    telemetry_actor_restart,
    telemetry_advance,
    telemetry_aot_cache,
    telemetry_aot_load,
    telemetry_child_file,
    telemetry_ckpt_commit,
    telemetry_ckpt_skipped,
    telemetry_crash_checkpoint,
    telemetry_deliberate_compiles,
    telemetry_dump_flight_record,
    telemetry_env_step,
    telemetry_fused_fallback,
    telemetry_mark_warm,
    telemetry_mark_warm_after_warmup,
    telemetry_masked_slot,
    telemetry_nan_rollback,
    telemetry_net_event,
    telemetry_preemption,
    telemetry_register_flops,
    telemetry_request_path,
    telemetry_resume_fallback,
    telemetry_run_metrics,
    telemetry_serve_event,
    telemetry_serve_stats,
    telemetry_slab,
    telemetry_slab_lag,
    telemetry_torn_slabs,
    telemetry_counters,
    telemetry_train_window,
    telemetry_worker_restart,
)
from sheeprl_tpu.obs.trace import (
    TraceRecorder,
    active_trace_ids,
    clock_offset,
    configure_trace,
    get_trace,
    new_trace_id,
    set_trace_role,
    shutdown_trace,
    trace_event,
    tracing_active,
)

__all__ = [
    "RunTelemetry",
    "TimerError",
    "TraceRecorder",
    "TriggeredProfiler",
    "active_trace_ids",
    "append_run_record",
    "build_run_record",
    "clock_offset",
    "configure_telemetry",
    "configure_trace",
    "get_telemetry",
    "get_trace",
    "log_sps_and_heartbeat",
    "new_trace_id",
    "read_run_records",
    "register_run",
    "set_trace_role",
    "shutdown_telemetry",
    "shutdown_trace",
    "span",
    "telemetry_actor_restart",
    "telemetry_advance",
    "telemetry_aot_cache",
    "telemetry_aot_load",
    "telemetry_child_file",
    "telemetry_ckpt_commit",
    "telemetry_ckpt_skipped",
    "telemetry_crash_checkpoint",
    "telemetry_deliberate_compiles",
    "telemetry_dump_flight_record",
    "telemetry_env_step",
    "telemetry_fused_fallback",
    "telemetry_mark_warm",
    "telemetry_mark_warm_after_warmup",
    "telemetry_masked_slot",
    "telemetry_nan_rollback",
    "telemetry_net_event",
    "telemetry_preemption",
    "telemetry_register_flops",
    "telemetry_request_path",
    "telemetry_resume_fallback",
    "telemetry_run_metrics",
    "telemetry_serve_event",
    "telemetry_serve_stats",
    "telemetry_slab",
    "telemetry_slab_lag",
    "telemetry_torn_slabs",
    "telemetry_counters",
    "telemetry_train_window",
    "telemetry_worker_restart",
    "trace_event",
    "tracing_active",
]
