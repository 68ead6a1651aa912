"""Per-run telemetry sink: JSONL event stream, device poller, heartbeat.

One :class:`RunTelemetry` per process per run, created by
:func:`configure_telemetry` from ``cfg.metric.telemetry`` and torn down by
:func:`shutdown_telemetry` (both wired in ``cli.run_algorithm``).  Everything
funnels into an append-only ``telemetry.jsonl`` next to the run's logs —
process 0 owns ``telemetry.jsonl``, the others write ``telemetry.<i>.jsonl``.

Event schema (one JSON object per line, documented in howto/telemetry.md):
every event carries ``event`` (kind), ``t`` (unix seconds), ``step``
(policy step at emission), ``process_index`` and optionally ``name``; the
kinds are ``run_start``, ``span``, ``compile``, ``device_poll``,
``heartbeat``, ``worker_restart``, ``masked_slot`` and
``run_end``.

The module-level accessor :func:`get_telemetry` returns ``None`` unless a run
configured telemetry — callers on hot paths pay one global read when the
subsystem is off.

Evidence-engine extensions (howto/evidence.md):

- **flight recorder** — a bounded ring of the last
  ``metric.telemetry.flightrec_events`` events, dumped to ``flightrec.json``
  by the crash-guard / NaN-rollback / preemption paths so every abnormal
  exit leaves a post-mortem artifact (newest event last).
- **rotation** — ``metric.telemetry.max_bytes`` caps the JSONL stream: on
  overflow the file rotates once to ``telemetry.jsonl.1`` (overwriting the
  previous rotation), bounding disk at ~2× the cap for soak/serve runs.
- **triggered profiler** — ``metric.telemetry.profile_windows`` /
  ``slow_window_factor`` drive :class:`~sheeprl_tpu.obs.profile.TriggeredProfiler`
  through :meth:`RunTelemetry.advance` and the span stream.
- **run rollup** — :meth:`RunTelemetry.run_summary` condenses the run into
  the registry record appended to ``RUNS.jsonl``
  (:mod:`sheeprl_tpu.obs.registry`).
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, Mapping, Optional

from sheeprl_tpu.native import status as native_status
from sheeprl_tpu.obs.profile import TriggeredProfiler
from sheeprl_tpu.obs.recompile import CompileWatchdog

_FLUSH_EVERY_EVENTS = 64
_FLUSH_EVERY_SECONDS = 5.0
# bound on per-heartbeat-window env-step latency samples: at sane log
# intervals the window never fills; a runaway loop degrades to "first N"
_ENV_STEP_RESERVOIR = 8192
_FLIGHTREC_EVENTS = 256
_TRACE_PATH_RESERVOIR = 8192
#: ``emit_span``'s default: a span event from outside ``span`` states no parent
_NO_PARENT = object()


def _pct(values: list, q: float) -> Optional[float]:
    """Nearest-rank percentile over an unsorted sample (None when empty)."""
    if not values:
        return None
    ordered = sorted(values)
    idx = min(len(ordered) - 1, int(round(q * (len(ordered) - 1))))
    return float(ordered[idx])

_active_telemetry: Optional["RunTelemetry"] = None


class TelemetryWriter:
    """Buffered, thread-safe JSONL appender.

    jax.monitoring listeners and the poller can fire from any thread; the
    lock keeps lines whole.  Events are buffered and flushed every
    ``_FLUSH_EVERY_EVENTS`` events or ``_FLUSH_EVERY_SECONDS`` seconds so the
    hot path never waits on the filesystem.

    ``max_bytes > 0`` enables size-capped rotation: when the current segment
    exceeds the cap it is renamed to ``<path>.1`` (overwriting any previous
    rotation) and a fresh segment starts, so a soak run's stream occupies at
    most ~2× the cap on disk."""

    def __init__(self, path: str, *, max_bytes: int = 0) -> None:
        self.path = path
        self.max_bytes = int(max_bytes or 0)
        self.rotations = 0
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._fh = open(path, "a", buffering=1)
        try:
            self._bytes = os.path.getsize(path)
        except OSError:
            self._bytes = 0
        self._lock = threading.Lock()
        self._buf: list = []
        self._last_flush = time.time()

    def write(self, event: Dict[str, Any]) -> None:
        line = json.dumps(event, default=str)
        with self._lock:
            self._buf.append(line)
            if len(self._buf) >= _FLUSH_EVERY_EVENTS or time.time() - self._last_flush > _FLUSH_EVERY_SECONDS:
                self._flush_locked()

    def flush(self) -> None:
        with self._lock:
            self._flush_locked()

    def _flush_locked(self) -> None:
        if self._buf:
            data = "\n".join(self._buf) + "\n"
            # rotate BEFORE a write that would cross the cap (not after): the
            # newest events — run_end, a crash's final flush — always land in
            # the CURRENT segment, never stranded at the tail of ``.1``
            if self.max_bytes > 0 and self._bytes > 0 and self._bytes + len(data) >= self.max_bytes:
                self._rotate_locked()
            self._fh.write(data)
            self._buf.clear()
            self._bytes += len(data)
        self._fh.flush()
        self._last_flush = time.time()

    def _rotate_locked(self) -> None:
        self._fh.close()
        try:
            os.replace(self.path, self.path + ".1")
        except OSError:
            pass  # someone removed the segment under us: just start fresh
        self._fh = open(self.path, "a", buffering=1)
        self._bytes = 0
        self.rotations += 1

    def segments(self) -> List[str]:
        """Existing stream segments, oldest first (``.1`` before current)."""
        return [p for p in (self.path + ".1", self.path) if os.path.exists(p)]

    def close(self) -> None:
        # under the lock: a racing write() could rotate and swap _fh between
        # a bare flush() and the close, leaking the fresh segment's handle
        with self._lock:
            self._flush_locked()
            self._fh.close()


class RunTelemetry:
    """The per-run telemetry hub.

    Owns the JSONL writer, the :class:`CompileWatchdog`, the low-rate device
    poller, and the heartbeat assembly.  ``step`` is advanced by the training
    loops (:func:`telemetry_advance`) so asynchronous events (compiles,
    polls) are attributable to a policy step."""

    def __init__(
        self,
        jsonl_path: str,
        *,
        poll_interval: float = 30.0,
        poll_rtt: bool = False,
        max_bytes: int = 0,
        flightrec_events: int = _FLIGHTREC_EVENTS,
        profiler: Optional[TriggeredProfiler] = None,
    ) -> None:
        import jax

        self._jax = jax
        self.process_index = jax.process_index()
        self.step = 0
        self.poll_interval = float(poll_interval)
        self.poll_rtt = bool(poll_rtt)
        self.writer = TelemetryWriter(jsonl_path, max_bytes=max_bytes)
        self.watchdog = CompileWatchdog(self.emit)
        # flight recorder: bounded ring of the newest events, dumped to
        # flightrec.json on the abnormal-exit paths (newest event last)
        self._flightrec: Optional[deque] = (
            deque(maxlen=int(flightrec_events)) if int(flightrec_events or 0) > 0 else None
        )
        stem = "flightrec.json" if self.process_index == 0 else f"flightrec.{self.process_index}.json"
        self.flightrec_path = os.path.join(os.path.dirname(jsonl_path) or ".", stem)
        # triggered profiler (obs/profile.py): driven by advance()/emit_span
        self.profiler = profiler
        self.profile_captures: List[Dict[str, Any]] = []
        self._window_index = 0
        self._last_poll: Optional[float] = None
        self._hbm_peak_bytes = 0
        self._device_polls = 0
        self._flops_source: Optional[Callable[[], Optional[float]]] = None
        self._flops_per_train_step: Optional[float] = None
        self._flops_resolved = False
        # per-train-window dispatch accounting (fused-superstep observability):
        # "window_*" accumulates since the last heartbeat, "total_*" over the run
        self._window_train_windows = 0
        self._window_train_dispatches = 0
        self._window_train_gradient_steps = 0
        self._total_train_windows = 0
        self._total_train_dispatches = 0
        self._total_train_gradient_steps = 0
        # rollout-pool accounting (sheeprl_tpu.rollout): per-window env-step
        # latency/queue-wait reservoirs + run totals for restarts/masked slots
        self._env_step_durs: list = []
        self._env_queue_waits: list = []
        self._window_worker_restarts = 0
        self._total_worker_restarts = 0
        self._total_masked_slots = 0
        # why fused supersteps fell back to per-step dispatch (reason -> count)
        self._fused_fallbacks: Dict[str, int] = {}
        # what each `auto` (player/train/buffer placement) resolved to on this
        # machine, and from which measurement (name -> {value, ...})
        self._resolved: Dict[str, Dict[str, Any]] = {}
        # actor-learner accounting (sheeprl_tpu.actor_learner): staleness-
        # bounded slab admission (histogram keyed by staleness-in-updates),
        # dropped-stale/torn counters, ring occupancy samples, per-actor
        # restart totals — heartbeat windows + run_end totals
        self._window_slabs_admitted = 0
        self._window_dropped_stale = 0
        self._window_staleness_hist: Dict[str, int] = {}
        self._window_ring_occupancy: list = []
        self._total_slabs_admitted = 0
        self._total_dropped_stale = 0
        self._total_torn_slabs = 0
        self._total_staleness_hist: Dict[str, int] = {}
        self._actor_restarts: Dict[str, int] = {}
        # resilience accounting (sheeprl_tpu.resilience): committed/skipped
        # checkpoint saves, NaN rollbacks, preemption requests, auto-resume
        # fallbacks — events at each occurrence + run_end totals
        self._total_ckpt_commits = 0
        self._total_ckpt_skipped = 0
        self._total_nan_rollbacks = 0
        self._total_preemptions = 0
        self._total_crash_checkpoints = 0
        self._total_resume_fallbacks = 0
        # policy-serving accounting (sheeprl_tpu.serve): the server's own
        # counters are cumulative, so the run_end totals keep the LAST
        # serve_stats snapshot; supervision/swap events are counted by kind
        self._serve_last_stats: Optional[Dict[str, Any]] = None
        self._serve_events: Dict[str, int] = {}
        # multi-host data plane (sheeprl_tpu.net): sparse transport events
        # (reconnect, checksum_reject, heartbeat_gap, torn_frame) are counted
        # by kind here; the dense per-frame/byte counters accumulate in
        # net.stats and are snapshotted into the run_end `net` section
        self._net_events: Dict[str, int] = {}
        # AOT executable cache (sheeprl_tpu.ops.aotcache): deserialized-load
        # hits vs compile fallbacks plus staged-store outcomes — one
        # `aot_cache` event per action + run_end totals
        self._aot_cache_hits = 0
        self._aot_cache_misses = 0
        self._aot_cache_stores = 0
        self._aot_cache_errors = 0
        # trace-plane critical-path reservoirs (sheeprl_tpu.obs.trace): per-
        # slab lag decomposition (collect -> ring-wait -> train, µs) and
        # per-request latency decomposition (queue-wait -> batch-assembly ->
        # compute, ms) — rolled up to p50/p95 in run_end/run_summary
        self._slab_lags: list = []
        self._req_paths: list = []
        self._req_hedged = 0
        self._req_rerouted = 0
        # telemetry files of CHILD processes (actor trace recorders): the
        # learner declares them so the registry record names the run's full
        # file set and the trace merger never has to glob
        self._child_files: list = []
        # run-registry rollup: cumulative heartbeat-window sums (run-average
        # SPS/duty cycle survive the per-window resets above) + the latest
        # aggregator scalars (final losses/returns for the run record)
        self._cum_env_steps = 0.0
        self._cum_env_time = 0.0
        self._cum_train_steps = 0.0
        self._cum_train_time = 0.0
        # overlapped collection: time spent *blocked* on the previous async
        # train dispatch (Time/train_wait_time) — the overlap win is the gap
        # between this and window_train_time. The flag records that the loop
        # *measures* wait at all: a fully-hidden run legitimately reports
        # zero wait, which is overlap_fraction == 1.0, not "no overlap data".
        self._cum_train_wait_time = 0.0
        self._saw_train_wait = False
        self._last_mfu: Optional[float] = None
        self._last_train_flops_per_sec: Optional[float] = None
        self._final_metrics: Dict[str, float] = {}

    # -- core event plumbing -------------------------------------------------

    def emit(self, event: str, name: Optional[str] = None, **fields: Any) -> None:
        record: Dict[str, Any] = {
            "event": event,
            "t": time.time(),
            "step": self.step,
            "process_index": self.process_index,
        }
        if name is not None:
            record["name"] = name
        record.update(fields)
        self.writer.write(record)
        ring = self._flightrec
        if ring is not None:
            ring.append(record)

    def emit_span(
        self,
        name: str,
        t_start: Optional[float],
        dur: float,
        attrs: Mapping[str, Any],
        t_mono_ns: Optional[int] = None,
        parent: Any = _NO_PARENT,
    ) -> None:
        fields: Dict[str, Any] = {"t_start": t_start, "dur": dur}
        if t_mono_ns is not None:
            # the span's start on time.monotonic_ns(): the clock that env
            # stamps and a device trace share (t_start is the wall clock)
            fields["t_mono_ns"] = int(t_mono_ns)
        if parent is not _NO_PARENT:
            # the innermost span open on the same thread when this one began (None at the top)
            fields["parent"] = parent
        if attrs:
            fields["attrs"] = dict(attrs)
        self.emit("span", name=name, **fields)
        if self.profiler is not None:
            self.profiler.observe_span(name, dur)

    def trace_annotation(self, name: Optional[str]):
        if name is None:
            return None
        return self._jax.profiler.TraceAnnotation(name)

    # -- loop hooks ----------------------------------------------------------

    def advance(self, step: int) -> None:
        self.step = int(step)
        # every advance() is one loop update = one train window (1-based);
        # the triggered profiler keys its captures off this counter
        self._window_index += 1
        if self.profiler is not None:
            self.profiler.on_window(self._window_index)
        self.maybe_poll_devices()

    def mark_warm(self) -> None:
        self.watchdog.mark_warm()

    def set_flops_source(self, source: Callable[[], Optional[float]]) -> None:
        if not self._flops_resolved:
            self._flops_source = source

    def record_train_window(self, dispatches: int, gradient_steps: int) -> None:
        """One train window happened: the loop issued ``dispatches`` jitted
        calls (gathers + EMA refreshes + train/superstep calls) to run
        ``gradient_steps`` gradient steps.  The per-step path reports
        O(gradient_steps) dispatches, a fused superstep reports
        ceil(gradient_steps / K) — the O(K)→O(1) reduction the dispatch
        counters exist to make visible (``tools.report --dispatch-stats``)."""
        self._window_train_windows += 1
        self._window_train_dispatches += int(dispatches)
        self._window_train_gradient_steps += int(gradient_steps)
        self._total_train_windows += 1
        self._total_train_dispatches += int(dispatches)
        self._total_train_gradient_steps += int(gradient_steps)

    def record_env_step(self, dur_s: float, queue_wait_s: Optional[float] = None) -> None:
        """One pooled env step happened: ``dur_s`` wall seconds end to end,
        of which ``queue_wait_s`` were spent NOT stepping envs (dispatch +
        pipe wait beyond the slowest worker's busy time). Feeds the
        heartbeat's env_step_p50/p95 and queue_wait_p50/p95 fields."""
        if len(self._env_step_durs) < _ENV_STEP_RESERVOIR:
            self._env_step_durs.append(float(dur_s))
            if queue_wait_s is not None:
                self._env_queue_waits.append(float(queue_wait_s))

    def record_worker_restart(self, worker: int, reason: str, restarts: int, **fields: Any) -> None:
        """An env worker was restarted (crash or step timeout): one
        ``worker_restart`` event + heartbeat/run_end counters."""
        self._window_worker_restarts += 1
        self._total_worker_restarts += 1
        self.emit("worker_restart", worker=worker, reason=reason, restarts=restarts, **fields)
        self.writer.flush()

    def record_masked_slot(self, worker: int, slots: Any, reason: str, **fields: Any) -> None:
        """An env worker exhausted its restart budget and its slots were
        masked dead: one ``masked_slot`` event + run_end counter."""
        nslots = len(slots) if isinstance(slots, (list, tuple)) else 1
        self._total_masked_slots += nslots
        self.emit("masked_slot", worker=worker, slots=slots, reason=reason, **fields)
        self.writer.flush()

    def record_slab(self, *, staleness: int, occupancy: float, admitted: bool) -> None:
        """One trajectory slab reached the learner's admission check:
        ``staleness`` is ``param_version - slab.param_version`` in updates,
        ``occupancy`` the ring's committed-slot fraction at poll time.
        Per-slab events would be hot-path noise — this only feeds the
        heartbeat window aggregates and run_end totals."""
        key = str(int(staleness))
        self._window_staleness_hist[key] = self._window_staleness_hist.get(key, 0) + 1
        self._total_staleness_hist[key] = self._total_staleness_hist.get(key, 0) + 1
        self._window_ring_occupancy.append(float(occupancy))
        if admitted:
            self._window_slabs_admitted += 1
            self._total_slabs_admitted += 1
        else:
            self._window_dropped_stale += 1
            self._total_dropped_stale += 1

    def record_torn_slabs(self, count: int, source: str = "", **fields: Any) -> None:
        """``count`` torn writes were detected and reclaimed (reader checksum
        or supervisor restart sweep): one ``torn_slab`` event + run_end
        counter. Rare by construction — the event is worth its cost."""
        if count <= 0:
            return
        self._total_torn_slabs += int(count)
        self.emit("torn_slab", count=int(count), source=source, **fields)
        self.writer.flush()

    def record_actor_restart(self, actor: int, reason: str, restarts: int, **fields: Any) -> None:
        """A trajectory actor was restarted (crash, torn write, or heartbeat
        timeout): one ``actor_restart`` event, the per-actor total for
        heartbeats/run_end, and the shared worker_restarts counters (the
        regress gate's restart budget covers both worker kinds)."""
        self._actor_restarts[str(int(actor))] = int(restarts)
        self._window_worker_restarts += 1
        self._total_worker_restarts += 1
        self.emit("actor_restart", actor=int(actor), reason=reason, restarts=int(restarts), **fields)
        self.writer.flush()

    def record_fused_fallback(self, reason: str, detail: str = "", **fields: Any) -> None:
        """``algo.fused_gradient_steps`` was requested but this run dispatches
        per-step: one structured ``fused_fallback`` event + run_end counter,
        so ``tools.report --dispatch-stats`` can say *why* a run shows zero fused
        windows instead of silently reporting O(K) dispatches."""
        self._fused_fallbacks[reason] = self._fused_fallbacks.get(reason, 0) + 1
        self.emit("fused_fallback", reason=reason, detail=detail, **fields)
        self.writer.flush()

    def record_resolved(self, name: str, value: Any, **fields: Any) -> None:
        """A placement choice the program made from what it observed
        (``algo.player_device``, ``algo.train_device``, ``buffer.device``):
        one ``resolved`` event, and the last outcome per name in the run
        record's ``resolved`` section."""
        self._resolved[name] = {"value": value, **fields}
        self.emit("resolved", name=name, value=value, **fields)

    def record_ckpt_commit(self, path: str, step: int, backend: str, emergency: bool = False, **fields: Any) -> None:
        """A checkpoint committed (manifest landed): one ``ckpt_committed``
        event + run_end counter. ``emergency=True`` marks the preemption
        drain's final save."""
        self._total_ckpt_commits += 1
        self.emit("ckpt_committed", path=path, ckpt_step=int(step), backend=backend, emergency=bool(emergency), **fields)
        self.writer.flush()

    def record_ckpt_skipped(self, path: str, step: int, **fields: Any) -> None:
        """An async save request arrived while one was still in flight and
        was dropped: one ``ckpt_skipped`` event + run_end counter. The next
        checkpoint interval retries with fresher state, so nothing is lost
        beyond that interval's granularity."""
        self._total_ckpt_skipped += 1
        self.emit("ckpt_skipped", path=path, ckpt_step=int(step), **fields)
        self.writer.flush()

    def record_nan_rollback(self, path: Optional[str], reason: str, remaining: int, **fields: Any) -> None:
        """The non-finite sentinel tripped and the run restored from the last
        committed checkpoint: one ``nan_rollback`` event + run_end counter +
        a flight-record dump (the trigger event is the newest in the ring)."""
        self._total_nan_rollbacks += 1
        self.emit("nan_rollback", path=path, reason=reason, remaining=int(remaining), **fields)
        self.writer.flush()
        self.dump_flight_record("nan_rollback")

    def record_preemption(self, signum: int, **fields: Any) -> None:
        """A preemption signal (SIGTERM/SIGINT) reached the train-loop
        boundary: one ``preempt`` event + run_end counter + a flight-record
        dump before the drain exits the process."""
        self._total_preemptions += 1
        self.emit("preempt", signum=int(signum), **fields)
        self.writer.flush()
        self.dump_flight_record("preempt")

    def record_crash_checkpoint(self, path: str, error: str, **fields: Any) -> None:
        """An unhandled train-loop exception drained the async writer and
        committed an emergency checkpoint before re-raising: one
        ``crash_checkpoint`` event + run_end counter + a flight-record dump."""
        self._total_crash_checkpoints += 1
        self.emit("crash_checkpoint", path=path, error=error, **fields)
        self.writer.flush()
        self.dump_flight_record("crash")

    def record_run_metrics(self, metrics: Mapping[str, Any]) -> None:
        """Keep the newest numeric aggregator scalars (losses, returns,
        episode lengths): the LAST values at run end become the registry
        record's ``final_metrics``. No event is emitted — the logger already
        carries the per-interval scalars."""
        for key, value in dict(metrics).items():
            try:
                num = float(value)
            except (TypeError, ValueError):
                continue
            if num == num:  # drop NaN — a poisoned final metric is useless
                self._final_metrics[str(key)] = num

    def dump_flight_record(self, trigger: str) -> Optional[str]:
        """Write the ring to ``flightrec.json`` (atomic tmp+rename; events
        oldest→newest, so the abnormal-exit trigger event is LAST). Each dump
        overwrites the previous — the newest post-mortem wins. Returns the
        path, or ``None`` when the ring is disabled or the write failed."""
        ring, path = self._flightrec, self.flightrec_path
        if ring is None or path is None:
            return None
        from sheeprl_tpu.obs.trace import active_trace_ids, clock_offset, current_role

        payload = {
            "schema": 1,
            "trigger": trigger,
            "t": time.time(),
            "step": self.step,
            "process_index": self.process_index,
            # process identity + active trace ids: a crash dump is an orphan
            # artifact until the merger can place it on one process's track
            # of the cross-process timeline (tools/trace.py)
            "role": current_role(),
            "pid": os.getpid(),
            "clock_offset": clock_offset(),
            "active_traces": active_trace_ids(),
            "ring_capacity": ring.maxlen,
            "events": list(ring),
        }
        try:
            tmp = f"{path}.tmp"
            with open(tmp, "w") as f:
                json.dump(payload, f, default=str)
            os.replace(tmp, path)
        except Exception:
            return None
        return path

    def record_serve_stats(self, snapshot: Mapping[str, Any]) -> None:
        """One periodic serving-tier stats snapshot (QPS, queue depth, shed
        counts, p50/p95, replica/swap health): a ``serve_stats`` event; the
        last snapshot becomes the ``run_end`` serve totals."""
        snap = dict(snapshot)
        self._serve_last_stats = snap
        self.emit("serve_stats", **snap)
        self.writer.flush()

    def record_serve_event(self, kind: str, **fields: Any) -> None:
        """One serving supervision/swap event (``replica_restart``,
        ``replica_masked``, ``replica_hung``, ``swap``, ``swap_rejected``,
        ``rollback``): a ``serve_event`` line + run_end per-kind counters."""
        self._serve_events[kind] = self._serve_events.get(kind, 0) + 1
        self.emit("serve_event", kind=kind, **fields)
        self.writer.flush()

    def record_net_event(self, kind: str, **fields: Any) -> None:
        """One data-plane transport event (``reconnect``, ``checksum_reject``,
        ``heartbeat_gap``, ``torn_frame``, ``stale_slab``, ``disconnect``,
        ``transport_close``): a ``net_event`` line + run_end per-kind
        counters, mirroring the serve/rollout event pattern."""
        self._net_events[kind] = self._net_events.get(kind, 0) + 1
        self.emit("net_event", kind=kind, **fields)
        self.writer.flush()

    def _net_section(self) -> Dict[str, Any]:
        """The run_end/run_summary ``net`` section: per-kind sparse event
        counts plus every registered transport endpoint's frame/byte/reconnect
        counters (``tools.report --net-stats`` reads this path)."""
        section: Dict[str, Any] = {"events": dict(self._net_events)}
        try:
            from sheeprl_tpu.net.stats import net_stats_snapshot

            counters = net_stats_snapshot()
        except Exception:
            counters = {}
        if counters:
            section["transports"] = counters
        return section

    def _net_active(self) -> bool:
        if self._net_events:
            return True
        try:
            from sheeprl_tpu.net.stats import net_stats_snapshot

            return bool(net_stats_snapshot())
        except Exception:
            return False

    def record_aot_cache(self, action: str, tag: str = "", **fields: Any) -> None:
        """One executable-cache outcome (``hit`` / ``miss`` / ``store`` /
        ``store_failed`` / ``corrupt_gc`` / ``torn_gc`` / ``prewarm``): an
        ``aot_cache`` line + run_end totals. A ``hit`` means a cold path
        skipped its compile; ``miss`` and the error actions mean it fell back
        to the compile ladder (degraded, never failed)."""
        if action == "hit":
            self._aot_cache_hits += 1
        elif action == "miss":
            self._aot_cache_misses += 1
        elif action == "store":
            # "prewarm" is a rollup of the per-entry "store" events the
            # gauntlet's sync commits already emitted — not counted twice
            self._aot_cache_stores += 1
        elif action in ("store_failed", "corrupt_gc"):
            self._aot_cache_errors += 1
        self.emit("aot_cache", action=action, tag=tag, **fields)
        self.writer.flush()

    def _serve_section(self) -> Dict[str, Any]:
        """The run_end/run_summary ``serve`` section. Fleet runs (PR 12) get
        a dedicated ``fleet`` sub-section — router counters, scale events,
        per-replica rows — lifted out of the last stats snapshot so registry
        consumers (bench --serve-stats, regress) read it at a stable path."""
        section: Dict[str, Any] = {
            "stats": self._serve_last_stats or {},
            "events": dict(self._serve_events),
        }
        fleet = (self._serve_last_stats or {}).get("fleet")
        if fleet:
            section["fleet"] = fleet
        return section

    # -- trace-plane rollups -------------------------------------------------

    def record_child_file(self, path: str) -> None:
        """Declare a child process's telemetry/trace file (actor trace
        recorders): the path lands in ``run_summary()['telemetry_files']`` so
        the collector locates the run's full file set without globbing."""
        p = str(path)
        if p not in self._child_files:
            self._child_files.append(p)

    def record_slab_lag(self, *, collect_us: int, ring_wait_us: int, train_us: int) -> None:
        """One admitted slab's critical-path decomposition, in microseconds:
        actor collect wall time, commit→admission ring wait (epoch-aligned
        via the slab header's commit stamp), and the learner train window.
        Reservoir-sampled; rolled up as slab-age p50/p95 at run end."""
        if len(self._slab_lags) < _TRACE_PATH_RESERVOIR:
            self._slab_lags.append((int(collect_us), int(ring_wait_us), int(train_us)))

    def record_request_path(
        self,
        *,
        queue_wait_ms: float,
        assembly_ms: float,
        compute_ms: float,
        hedged: bool = False,
        rerouted: bool = False,
    ) -> None:
        """One completed request's critical-path decomposition, in
        milliseconds: enqueue→dispatch queue wait, batch assembly (staging),
        and compute. Hedged/re-routed requests are counted so the rollup can
        attribute fault/hedge overhead."""
        if len(self._req_paths) < _TRACE_PATH_RESERVOIR:
            self._req_paths.append((float(queue_wait_ms), float(assembly_ms), float(compute_ms)))
        if hedged:
            self._req_hedged += 1
        if rerouted:
            self._req_rerouted += 1

    def _slab_lag_section(self) -> Dict[str, Any]:
        rows = self._slab_lags
        if not rows:
            return {}
        ages = [(c + r + t) / 1e3 for c, r, t in rows]
        collect = [c / 1e3 for c, _, _ in rows]
        ring_wait = [r / 1e3 for _, r, _ in rows]
        train = [t / 1e3 for _, _, t in rows]
        return {
            "samples": len(rows),
            "age_p50_ms": _pct(ages, 0.50),
            "age_p95_ms": _pct(ages, 0.95),
            "collect_p50_ms": _pct(collect, 0.50),
            "collect_p95_ms": _pct(collect, 0.95),
            "ring_wait_p50_ms": _pct(ring_wait, 0.50),
            "ring_wait_p95_ms": _pct(ring_wait, 0.95),
            "train_p50_ms": _pct(train, 0.50),
            "train_p95_ms": _pct(train, 0.95),
        }

    def _request_path_section(self) -> Dict[str, Any]:
        rows = self._req_paths
        if not rows and not (self._req_hedged or self._req_rerouted):
            return {}
        totals = [q + a + c for q, a, c in rows]
        queue = [q for q, _, _ in rows]
        assembly = [a for _, a, _ in rows]
        compute = [c for _, _, c in rows]
        return {
            "samples": len(rows),
            "p50_ms": _pct(totals, 0.50),
            "p95_ms": _pct(totals, 0.95),
            "queue_wait_p50_ms": _pct(queue, 0.50),
            "queue_wait_p95_ms": _pct(queue, 0.95),
            "assembly_p50_ms": _pct(assembly, 0.50),
            "assembly_p95_ms": _pct(assembly, 0.95),
            "compute_p50_ms": _pct(compute, 0.50),
            "compute_p95_ms": _pct(compute, 0.95),
            "hedged": self._req_hedged,
            "rerouted": self._req_rerouted,
        }

    def record_resume_fallback(self, path: str, error: str, **fields: Any) -> None:
        """``resume_from=auto`` rejected a candidate checkpoint (load failure
        or mesh mismatch) and fell back to the next-newest: one
        ``resume_fallback`` event + run_end counter."""
        self._total_resume_fallbacks += 1
        self.emit("resume_fallback", path=path, error=error, **fields)
        self.writer.flush()

    def _resolve_flops(self) -> Optional[float]:
        if not self._flops_resolved and self._flops_source is not None:
            # the AOT cost-analysis compile is deliberate, not a retrace —
            # run it inside the watchdog's allowlist window
            with self.watchdog.deliberate("aot_cost_analysis"):
                try:
                    self._flops_per_train_step = self._flops_source()
                except Exception:
                    self._flops_per_train_step = None
            self._flops_source = None
            self._flops_resolved = True
        return self._flops_per_train_step

    # -- device poller -------------------------------------------------------

    def maybe_poll_devices(self, force: bool = False) -> None:
        now = time.time()
        if not force and self._last_poll is not None and now - self._last_poll < self.poll_interval:
            return
        self._last_poll = now
        devices = []
        for dev in self._jax.local_devices():
            entry: Dict[str, Any] = {
                "id": dev.id,
                "kind": getattr(dev, "device_kind", "unknown"),
                "platform": getattr(dev, "platform", "unknown"),
            }
            try:
                stats = dev.memory_stats()
            except Exception:
                stats = None
            if stats:
                in_use = stats.get("bytes_in_use")
                peak = stats.get("peak_bytes_in_use", in_use)
                if in_use is not None:
                    entry["bytes_in_use"] = int(in_use)
                if peak is not None:
                    entry["peak_bytes_in_use"] = int(peak)
                    self._hbm_peak_bytes = max(self._hbm_peak_bytes, int(peak))
            devices.append(entry)
        fields: Dict[str, Any] = {"devices": devices}
        if self.poll_rtt and self._jax.default_backend() != "cpu":
            # Dispatch round-trip probe. It is a real sync point, so it is
            # opt-in (metric.telemetry.poll_rtt) and rides the same low-rate
            # schedule as the memory poll.
            try:
                from sheeprl_tpu.utils.profiler import tiny_op_rtt_seconds

                fields["rtt_ms"] = tiny_op_rtt_seconds() * 1e3
            except Exception:
                pass
        self._device_polls += 1
        self.emit("device_poll", **fields)

    def device_kind(self) -> str:
        devs = self._jax.local_devices()
        return getattr(devs[0], "device_kind", "unknown") if devs else "unknown"

    # -- heartbeat -----------------------------------------------------------

    def heartbeat(
        self,
        logger,
        *,
        step: int,
        env_steps: float,
        train_steps: float,
        train_invocations: Optional[float],
        timer_window: Mapping[str, float],
    ) -> None:
        """Assemble the per-log-interval health summary: SPS, train/rollout
        duty cycle, MFU (via the registered ``compiled_flops`` source), HBM
        peak, recompile count — one JSONL event + ``Telemetry/*`` scalars."""
        env_t = float(timer_window.get("Time/env_interaction_time") or 0.0)
        train_t = float(timer_window.get("Time/train_time") or 0.0)
        train_wait_t = float(timer_window.get("Time/train_wait_time") or 0.0)
        # run-registry rollup: the window sums reset every heartbeat, these
        # cumulative mirrors survive to run_summary()
        self._cum_env_steps += float(env_steps or 0.0)
        self._cum_env_time += env_t
        self._cum_train_steps += float(train_steps or 0.0)
        self._cum_train_time += train_t
        self._cum_train_wait_time += train_wait_t
        fields: Dict[str, Any] = {
            "window_env_steps": env_steps,
            "window_train_steps": train_steps,
            "window_env_time": env_t,
            "window_train_time": train_t,
            "device_kind": self.device_kind(),
            "hbm_peak_bytes": self._hbm_peak_bytes,
            "recompiles": self.watchdog.recompiles,
            "compiles_total": self.watchdog.compiles,
        }
        scalars: Dict[str, float] = {"Counters/recompiles": float(self.watchdog.recompiles)}
        if self._window_train_windows:
            fields["window_train_windows"] = self._window_train_windows
            fields["window_train_dispatches"] = self._window_train_dispatches
            fields["window_train_gradient_steps"] = self._window_train_gradient_steps
            scalars["Telemetry/train_dispatches_per_window"] = (
                self._window_train_dispatches / self._window_train_windows
            )
            self._window_train_windows = 0
            self._window_train_dispatches = 0
            self._window_train_gradient_steps = 0
        if self._env_step_durs:
            import numpy as _np

            durs = _np.asarray(self._env_step_durs)
            fields["env_step_p50_ms"] = float(_np.percentile(durs, 50)) * 1e3
            fields["env_step_p95_ms"] = float(_np.percentile(durs, 95)) * 1e3
            fields["env_step_samples"] = int(durs.size)
            scalars["Telemetry/env_step_p95_ms"] = fields["env_step_p95_ms"]
            if self._env_queue_waits:
                waits = _np.asarray(self._env_queue_waits)
                fields["env_queue_wait_p50_ms"] = float(_np.percentile(waits, 50)) * 1e3
                fields["env_queue_wait_p95_ms"] = float(_np.percentile(waits, 95)) * 1e3
            self._env_step_durs = []
            self._env_queue_waits = []
        if self._window_worker_restarts:
            fields["window_worker_restarts"] = self._window_worker_restarts
            self._window_worker_restarts = 0
        if self._total_worker_restarts:
            fields["worker_restarts_total"] = self._total_worker_restarts
            scalars["Counters/worker_restarts"] = float(self._total_worker_restarts)
        if self._total_masked_slots:
            fields["masked_slots_total"] = self._total_masked_slots
            scalars["Counters/masked_slots"] = float(self._total_masked_slots)
        # actor-learner window: slab admission/staleness/ring health — only
        # present when the disaggregated topology actually moved slabs
        if self._window_staleness_hist or self._window_ring_occupancy:
            fields["window_slabs_admitted"] = self._window_slabs_admitted
            fields["window_dropped_stale_slabs"] = self._window_dropped_stale
            fields["window_staleness_hist"] = dict(self._window_staleness_hist)
            if self._window_ring_occupancy:
                occ = sum(self._window_ring_occupancy) / len(self._window_ring_occupancy)
                fields["ring_occupancy"] = occ
                scalars["Telemetry/ring_occupancy"] = occ
            if train_t + train_wait_t > 0:
                # the learner's duty cycle: fraction of its loop spent
                # training vs starved waiting for an admissible slab
                fields["learner_duty_cycle"] = train_t / (train_t + train_wait_t)
                scalars["Telemetry/learner_duty_cycle"] = fields["learner_duty_cycle"]
            self._window_slabs_admitted = 0
            self._window_dropped_stale = 0
            self._window_staleness_hist = {}
            self._window_ring_occupancy = []
        if self._total_dropped_stale:
            fields["dropped_stale_slabs_total"] = self._total_dropped_stale
            scalars["Counters/dropped_stale_slabs"] = float(self._total_dropped_stale)
        if self._total_torn_slabs:
            fields["torn_slabs_total"] = self._total_torn_slabs
            scalars["Counters/torn_slabs"] = float(self._total_torn_slabs)
        if self._actor_restarts:
            fields["actor_restarts"] = dict(self._actor_restarts)
        # checkpoint duty-cycle: only the snapshot span blocks the train loop
        # (the write happens on the background thread), so the heartbeat
        # reports them separately
        ckpt_snap_t = float(timer_window.get("ckpt/snapshot") or 0.0)
        ckpt_write_t = float(timer_window.get("ckpt/write") or 0.0)
        if ckpt_snap_t > 0:
            fields["window_ckpt_snapshot_time"] = ckpt_snap_t
            scalars["Telemetry/ckpt_snapshot_time"] = ckpt_snap_t
        if ckpt_write_t > 0:
            fields["window_ckpt_write_time"] = ckpt_write_t
        if self._total_ckpt_commits:
            fields["ckpt_commits_total"] = self._total_ckpt_commits
            scalars["Counters/ckpt_commits"] = float(self._total_ckpt_commits)
        if self._total_ckpt_skipped:
            fields["ckpt_skipped_total"] = self._total_ckpt_skipped
            scalars["Counters/ckpt_skipped"] = float(self._total_ckpt_skipped)
        if self._total_nan_rollbacks:
            fields["nan_rollbacks_total"] = self._total_nan_rollbacks
            scalars["Counters/nan_rollbacks"] = float(self._total_nan_rollbacks)
        if env_t > 0:
            fields["sps_env"] = env_steps / env_t
        if train_t > 0:
            fields["sps_train"] = train_steps / train_t
        if env_t + train_t > 0:
            fields["duty_cycle_train"] = train_t / (env_t + train_t)
            scalars["Telemetry/duty_cycle_train"] = fields["duty_cycle_train"]
        if "Time/train_wait_time" in timer_window:
            # overlapped collection: train_time is the (non-blocking) dispatch
            # span, train_wait_time the later block on its results — the env
            # loop ran in between, so the hidden fraction of the update cycle
            # is env / (env + wait).  1.0 = train fully hidden.
            self._saw_train_wait = True
            fields["window_train_wait_time"] = train_wait_t
            scalars["Telemetry/train_wait_time"] = train_wait_t
            if env_t + train_wait_t > 0:
                fields["overlap_fraction"] = env_t / (env_t + train_wait_t)
                scalars["Telemetry/overlap_fraction"] = fields["overlap_fraction"]
        if self._hbm_peak_bytes:
            scalars["Telemetry/hbm_peak_bytes"] = float(self._hbm_peak_bytes)
        flops = self._resolve_flops()
        if flops is not None:
            fields["flops_per_train_step"] = flops
            if train_invocations is not None:
                fields["window_train_invocations"] = train_invocations
                if train_t > 0 and train_invocations > 0:
                    fps = flops * train_invocations / train_t
                    fields["train_flops_per_sec"] = fps
                    scalars["Telemetry/train_flops_per_sec"] = fps
                    self._last_train_flops_per_sec = fps
                    from sheeprl_tpu.utils.profiler import PEAK_BF16_FLOPS

                    peak = PEAK_BF16_FLOPS.get(fields["device_kind"])
                    if peak:
                        fields["mfu"] = fps / peak
                        scalars["Telemetry/mfu"] = fields["mfu"]
                        self._last_mfu = fields["mfu"]
        self.emit("heartbeat", **fields)
        self.writer.flush()
        if logger is not None:
            try:
                logger.log_metrics(scalars, step)
            except Exception:
                pass

    # -- run-registry rollup -------------------------------------------------

    def run_summary(self) -> Dict[str, Any]:
        """Condense the run for the registry record (``RUNS.jsonl``): run-wide
        SPS/duty cycle from the cumulative heartbeat sums, the latest MFU,
        HBM peak, compile/recompile/dispatch/fallback and resilience totals,
        rollout restart/mask totals, the last serve snapshot, profile
        captures and the telemetry segments on disk."""
        summary: Dict[str, Any] = {
            "backend": self._jax.default_backend(),
            "device_kind": self.device_kind(),
            "local_device_count": self._jax.local_device_count(),
            "process_count": self._jax.process_count(),
            "hbm_peak_bytes": self._hbm_peak_bytes,
            "compiles_total": self.watchdog.compiles,
            "recompiles": self.watchdog.recompiles,
            "deliberate_compiles": dict(self.watchdog.deliberate_compiles),
            "train_windows": self._total_train_windows,
            "train_dispatches": self._total_train_dispatches,
            "train_gradient_steps": self._total_train_gradient_steps,
            "fused_fallbacks": dict(self._fused_fallbacks),
            "resolved": {k: dict(v) for k, v in self._resolved.items()},
            "native_gather": native_status(),
            "compile_cache_hits": self.watchdog.cache_hits,
            "compile_cache_misses": self.watchdog.cache_misses,
            "worker_restarts": self._total_worker_restarts,
            "masked_slots": self._total_masked_slots,
            "ckpt_commits": self._total_ckpt_commits,
            "ckpt_skipped": self._total_ckpt_skipped,
            "nan_rollbacks": self._total_nan_rollbacks,
            "preemptions": self._total_preemptions,
            "crash_checkpoints": self._total_crash_checkpoints,
            "resume_fallbacks": self._total_resume_fallbacks,
            "aot_cache_hits": self._aot_cache_hits,
            "aot_cache_misses": self._aot_cache_misses,
            "aot_cache_stores": self._aot_cache_stores,
            "aot_cache_errors": self._aot_cache_errors,
        }
        if self._cum_env_time > 0:
            summary["sps_env"] = self._cum_env_steps / self._cum_env_time
        if self._cum_train_time > 0:
            summary["sps_train"] = self._cum_train_steps / self._cum_train_time
        if self._cum_env_time + self._cum_train_time > 0:
            summary["duty_cycle_train"] = self._cum_train_time / (self._cum_env_time + self._cum_train_time)
        # env steps over the whole timed loop (collect + train + any train
        # wait): the number fused/overlap runs actually move, and the regress
        # gate cell for them
        loop_t = self._cum_env_time + self._cum_train_time + self._cum_train_wait_time
        if loop_t > 0 and self._cum_env_steps > 0:
            summary["sps_end_to_end"] = self._cum_env_steps / loop_t
        if self._saw_train_wait:
            summary["train_wait_time"] = self._cum_train_wait_time
            if self._cum_env_time + self._cum_train_wait_time > 0:
                summary["overlap_fraction"] = self._cum_env_time / (
                    self._cum_env_time + self._cum_train_wait_time
                )
        if self._total_slabs_admitted or self._total_dropped_stale or self._total_torn_slabs:
            summary["slabs_admitted"] = self._total_slabs_admitted
            summary["dropped_stale_slabs"] = self._total_dropped_stale
            summary["torn_slabs"] = self._total_torn_slabs
            summary["staleness_hist"] = dict(self._total_staleness_hist)
            if self._cum_train_time + self._cum_train_wait_time > 0:
                summary["learner_duty_cycle"] = self._cum_train_time / (
                    self._cum_train_time + self._cum_train_wait_time
                )
        if self._actor_restarts:
            summary["actor_restarts"] = dict(self._actor_restarts)
        if self._flops_per_train_step is not None:
            summary["flops_per_train_step"] = self._flops_per_train_step
        if self._last_train_flops_per_sec is not None:
            summary["train_flops_per_sec"] = self._last_train_flops_per_sec
        if self._last_mfu is not None:
            summary["mfu"] = self._last_mfu
        if self._serve_last_stats is not None or self._serve_events:
            summary["serve"] = self._serve_section()
        if self._net_active():
            summary["net"] = self._net_section()
        captures = self.profile_captures or (self.profiler.captures if self.profiler is not None else [])
        if captures:
            summary["profile_captures"] = [dict(c) for c in captures]
        if self._final_metrics:
            summary["final_metrics"] = dict(self._final_metrics)
        slab_lag = self._slab_lag_section()
        if slab_lag:
            summary["slab_lag"] = slab_lag
        req_path = self._request_path_section()
        if req_path:
            summary["request_critical_path"] = req_path
        summary["telemetry_jsonl"] = self.writer.path
        summary["telemetry_segments"] = [os.path.basename(p) for p in self.writer.segments()]
        # the run's FULL per-process file set (this process's segments,
        # oldest first, plus declared child trace files) — the trace
        # collector reads this instead of globbing the log dir
        summary["telemetry_files"] = list(self.writer.segments()) + list(self._child_files)
        return summary

    # -- lifecycle -----------------------------------------------------------

    def start(self, run_info: Optional[Mapping[str, Any]] = None) -> None:
        self.watchdog.start()
        self.emit("run_start", **dict(run_info or {}))
        # trace handshake at spawn: role/pid + the monotonic→epoch clock
        # offset the cross-process merger (tools/trace.py) aligns this
        # stream's t_mono stamps with
        from sheeprl_tpu.obs.trace import clock_offset, current_role

        self.emit(
            "trace_handshake",
            role=current_role(),
            pid=os.getpid(),
            clock_offset=clock_offset(),
            t_mono=time.monotonic(),
        )
        self.maybe_poll_devices(force=True)

    def close(self) -> None:
        if self.profiler is not None:
            # stop a capture straddling run end so the trace file is complete
            # BEFORE run_end reports it
            self.profile_captures = self.profiler.finish()
        extra_fields: Dict[str, Any] = {}
        # only serving runs grow a `serve` section: training-run run_end
        # consumers keep seeing exactly the fields they already parse
        if self._serve_last_stats is not None or self._serve_events:
            extra_fields["serve"] = self._serve_section()
        # likewise the `net` section: only runs that touched a transport
        if self._net_active():
            extra_fields["net"] = self._net_section()
        # same for the trace-plane critical-path rollups: only runs that
        # recorded slab/request decompositions carry them
        slab_lag = self._slab_lag_section()
        if slab_lag:
            extra_fields["slab_lag"] = slab_lag
        req_path = self._request_path_section()
        if req_path:
            extra_fields["request_critical_path"] = req_path
        self.emit(
            "run_end",
            **extra_fields,
            compiles_total=self.watchdog.compiles,
            recompiles=self.watchdog.recompiles,
            device_polls=self._device_polls,
            hbm_peak_bytes=self._hbm_peak_bytes,
            train_windows=self._total_train_windows,
            train_dispatches=self._total_train_dispatches,
            train_gradient_steps=self._total_train_gradient_steps,
            compile_cache_hits=self.watchdog.cache_hits,
            compile_cache_misses=self.watchdog.cache_misses,
            worker_restarts=self._total_worker_restarts,
            masked_slots=self._total_masked_slots,
            fused_fallbacks=dict(self._fused_fallbacks),
            slabs_admitted=self._total_slabs_admitted,
            dropped_stale_slabs=self._total_dropped_stale,
            torn_slabs=self._total_torn_slabs,
            staleness_hist=dict(self._total_staleness_hist),
            actor_restarts=dict(self._actor_restarts),
            ckpt_commits=self._total_ckpt_commits,
            ckpt_skipped=self._total_ckpt_skipped,
            nan_rollbacks=self._total_nan_rollbacks,
            preemptions=self._total_preemptions,
            crash_checkpoints=self._total_crash_checkpoints,
            resume_fallbacks=self._total_resume_fallbacks,
            aot_cache_hits=self._aot_cache_hits,
            aot_cache_misses=self._aot_cache_misses,
            aot_cache_stores=self._aot_cache_stores,
            aot_cache_errors=self._aot_cache_errors,
            aot_loads=dict(self.watchdog.aot_loads),
            deliberate_compiles=dict(self.watchdog.deliberate_compiles),
            profile_captures=[dict(c) for c in self.profile_captures],
            telemetry_rotations=self.writer.rotations,
            telemetry_segments=[os.path.basename(p) for p in self.writer.segments()],
        )
        self.watchdog.stop()
        self.writer.close()


# -- module-level accessors (cheap no-ops when telemetry is off) -------------


def get_telemetry() -> Optional[RunTelemetry]:
    return _active_telemetry


def configure_telemetry(cfg: Mapping[str, Any], log_dir: Optional[str] = None) -> Optional[RunTelemetry]:
    """Build the process-wide :class:`RunTelemetry` from
    ``cfg.metric.telemetry`` (``{enabled, jsonl, poll_interval, poll_rtt,
    max_bytes, flightrec_events, profile_windows, slow_window_factor,
    slow_window_min_history}``).  Returns ``None`` (and leaves the subsystem
    inert) unless enabled."""
    global _active_telemetry
    tel_cfg = ((cfg.get("metric") or {}).get("telemetry")) or {}
    if not bool(tel_cfg.get("enabled", False)):
        return None
    if _active_telemetry is not None:
        shutdown_telemetry()
    import jax

    path = tel_cfg.get("jsonl") or os.path.join(log_dir or ".", "telemetry.jsonl")
    proc = jax.process_index()
    if proc != 0:
        root, ext = os.path.splitext(path)
        path = f"{root}.{proc}{ext or '.jsonl'}"
    profiler: Optional[TriggeredProfiler] = None
    windows = tel_cfg.get("profile_windows") or []
    slow_factor = float(tel_cfg.get("slow_window_factor", 0.0) or 0.0)
    if proc == 0 and (windows or slow_factor > 0.0):
        # process-0 only, like the whole-run profiler: one Perfetto writer
        # per host is plenty and the traces already carry every local device
        profiler = TriggeredProfiler(
            os.path.join(os.path.dirname(path) or ".", "profile_triggered"),
            windows=[int(w) for w in windows],
            slow_factor=slow_factor,
            slow_min_history=int(tel_cfg.get("slow_window_min_history", 8) or 8),
        )
    tel = RunTelemetry(
        path,
        poll_interval=float(tel_cfg.get("poll_interval", 30.0) or 0.0),
        poll_rtt=bool(tel_cfg.get("poll_rtt", False)),
        max_bytes=int(tel_cfg.get("max_bytes", 0) or 0),
        flightrec_events=int(tel_cfg.get("flightrec_events", _FLIGHTREC_EVENTS) or 0),
        profiler=profiler,
    )
    tel.start(
        run_info={
            "backend": jax.default_backend(),
            "local_device_count": jax.local_device_count(),
            "process_count": jax.process_count(),
        }
    )
    _active_telemetry = tel
    return tel


def shutdown_telemetry() -> None:
    global _active_telemetry
    tel = _active_telemetry
    _active_telemetry = None
    if tel is not None:
        try:
            tel.close()
        except Exception:
            pass


def telemetry_advance(step: int) -> None:
    tel = _active_telemetry
    if tel is not None:
        tel.advance(step)


def telemetry_mark_warm() -> None:
    tel = _active_telemetry
    if tel is not None:
        tel.mark_warm()


def telemetry_mark_warm_after_warmup(update: int, learning_starts: int) -> None:
    tel = _active_telemetry
    if tel is not None:
        tel.watchdog.mark_warm_after_warmup(update, learning_starts)


@contextmanager
def telemetry_deliberate_compiles(reason: str):
    """Allowlist window for deliberate compiles (serve batch-ladder AOT,
    hot-swap revalidation, AOT cost analysis): inside the context, compiles
    on this thread never count as post-warmup recompiles (see
    :meth:`CompileWatchdog.deliberate`). Yields even when telemetry is off."""
    tel = _active_telemetry
    if tel is None:
        yield
    else:
        with tel.watchdog.deliberate(reason):
            yield


def telemetry_aot_cache(action: str, tag: str = "", **fields: Any) -> None:
    """Record an executable-cache outcome (see
    :meth:`RunTelemetry.record_aot_cache`); no-op when telemetry is off."""
    tel = _active_telemetry
    if tel is not None:
        tel.record_aot_cache(action, tag, **fields)


@contextmanager
def telemetry_aot_load(tag: str):
    """Executable-cache deserialization window: compile-monitoring events on
    this thread are classified as ``aot_load`` — neither recompiles nor
    ``deliberate:`` compiles (see :meth:`CompileWatchdog.aot_load`). Yields
    even when telemetry is off."""
    tel = _active_telemetry
    if tel is None:
        yield
    else:
        with tel.watchdog.aot_load(tag):
            yield


def telemetry_run_metrics(metrics: Mapping[str, Any]) -> None:
    """Capture the latest aggregator scalars for the run-registry record
    (see :meth:`RunTelemetry.record_run_metrics`); no-op when telemetry is
    off."""
    tel = _active_telemetry
    if tel is not None:
        tel.record_run_metrics(metrics)


def telemetry_dump_flight_record(trigger: str) -> Optional[str]:
    """Dump the flight-recorder ring now (see
    :meth:`RunTelemetry.dump_flight_record`); no-op when telemetry is off."""
    tel = _active_telemetry
    if tel is not None:
        return tel.dump_flight_record(trigger)
    return None


def telemetry_train_window(dispatches: int, gradient_steps: int) -> None:
    """Record one train window's dispatch count (see
    :meth:`RunTelemetry.record_train_window`); no-op when telemetry is off."""
    tel = _active_telemetry
    if tel is not None:
        tel.record_train_window(dispatches, gradient_steps)


def telemetry_counters(name: str, **fields: Any) -> None:
    """One ``counters`` event of ``name`` with ``fields`` (numbers a loop
    counts per update, such as routed pairs or padded positions) and the
    host's monotonic time; no-op when telemetry is off."""
    tel = _active_telemetry
    if tel is not None:
        tel.emit("counters", name=name, t_mono_ns=time.monotonic_ns(), **fields)


def telemetry_env_step(dur_s: float, queue_wait_s: Optional[float] = None) -> None:
    """Record one pooled env step's latency (see
    :meth:`RunTelemetry.record_env_step`); no-op when telemetry is off."""
    tel = _active_telemetry
    if tel is not None:
        tel.record_env_step(dur_s, queue_wait_s)


def telemetry_worker_restart(worker: int, reason: str, restarts: int, **fields: Any) -> None:
    """Record an env-worker restart (see
    :meth:`RunTelemetry.record_worker_restart`); no-op when telemetry is off."""
    tel = _active_telemetry
    if tel is not None:
        tel.record_worker_restart(worker, reason, restarts, **fields)


def telemetry_slab(*, staleness: int, occupancy: float, admitted: bool) -> None:
    """Record one ring-slab admission decision (see
    :meth:`RunTelemetry.record_slab`); no-op when telemetry is off."""
    tel = _active_telemetry
    if tel is not None:
        tel.record_slab(staleness=staleness, occupancy=occupancy, admitted=admitted)


def telemetry_torn_slabs(count: int, source: str = "", **fields: Any) -> None:
    """Record detected/reclaimed torn slabs (see
    :meth:`RunTelemetry.record_torn_slabs`); no-op when telemetry is off."""
    tel = _active_telemetry
    if tel is not None:
        tel.record_torn_slabs(count, source, **fields)


def telemetry_actor_restart(actor: int, reason: str, restarts: int, **fields: Any) -> None:
    """Record an actor-process restart (see
    :meth:`RunTelemetry.record_actor_restart`); no-op when telemetry is off."""
    tel = _active_telemetry
    if tel is not None:
        tel.record_actor_restart(actor, reason, restarts, **fields)


def telemetry_fused_fallback(reason: str, detail: str = "", **fields: Any) -> None:
    """Record a fused-superstep fallback on the active telemetry (see
    :meth:`RunTelemetry.record_fused_fallback`); no-op when telemetry is off."""
    tel = _active_telemetry
    if tel is not None:
        tel.record_fused_fallback(reason, detail, **fields)


def telemetry_resolved(name: str, value: Any, **fields: Any) -> None:
    """Record what an ``auto`` placement resolved to (see
    :meth:`RunTelemetry.record_resolved`); no-op when telemetry is off."""
    tel = _active_telemetry
    if tel is not None:
        tel.record_resolved(name, value, **fields)


def telemetry_masked_slot(worker: int, slots: Any, reason: str, **fields: Any) -> None:
    """Record env slots masked dead (see
    :meth:`RunTelemetry.record_masked_slot`); no-op when telemetry is off."""
    tel = _active_telemetry
    if tel is not None:
        tel.record_masked_slot(worker, slots, reason, **fields)


def telemetry_ckpt_commit(path: str, step: int, backend: str, emergency: bool = False, **fields: Any) -> None:
    """Record a committed checkpoint (see
    :meth:`RunTelemetry.record_ckpt_commit`); no-op when telemetry is off."""
    tel = _active_telemetry
    if tel is not None:
        tel.record_ckpt_commit(path, step, backend, emergency, **fields)


def telemetry_ckpt_skipped(path: str, step: int, **fields: Any) -> None:
    """Record a dropped async save request (see
    :meth:`RunTelemetry.record_ckpt_skipped`); no-op when telemetry is off."""
    tel = _active_telemetry
    if tel is not None:
        tel.record_ckpt_skipped(path, step, **fields)


def telemetry_nan_rollback(path: Optional[str], reason: str, remaining: int, **fields: Any) -> None:
    """Record a non-finite rollback (see
    :meth:`RunTelemetry.record_nan_rollback`); no-op when telemetry is off."""
    tel = _active_telemetry
    if tel is not None:
        tel.record_nan_rollback(path, reason, remaining, **fields)


def telemetry_preemption(signum: int, **fields: Any) -> None:
    """Record a preemption request (see
    :meth:`RunTelemetry.record_preemption`); no-op when telemetry is off."""
    tel = _active_telemetry
    if tel is not None:
        tel.record_preemption(signum, **fields)


def telemetry_crash_checkpoint(path: str, error: str, **fields: Any) -> None:
    """Record a crash-guard emergency save (see
    :meth:`RunTelemetry.record_crash_checkpoint`); no-op when telemetry is off."""
    tel = _active_telemetry
    if tel is not None:
        tel.record_crash_checkpoint(path, error, **fields)


def telemetry_resume_fallback(path: str, error: str, **fields: Any) -> None:
    """Record an auto-resume candidate rejection (see
    :meth:`RunTelemetry.record_resume_fallback`); no-op when telemetry is off."""
    tel = _active_telemetry
    if tel is not None:
        tel.record_resume_fallback(path, error, **fields)


def telemetry_serve_stats(snapshot: Mapping[str, Any]) -> None:
    """Record a serving-tier stats snapshot (see
    :meth:`RunTelemetry.record_serve_stats`); no-op when telemetry is off."""
    tel = _active_telemetry
    if tel is not None:
        tel.record_serve_stats(snapshot)


def telemetry_serve_event(kind: str, **fields: Any) -> None:
    """Record a serving supervision/swap event (see
    :meth:`RunTelemetry.record_serve_event`); no-op when telemetry is off."""
    tel = _active_telemetry
    if tel is not None:
        tel.record_serve_event(kind, **fields)


def telemetry_net_event(kind: str, **fields: Any) -> None:
    """Record a data-plane transport event (see
    :meth:`RunTelemetry.record_net_event`); no-op when telemetry is off."""
    tel = _active_telemetry
    if tel is not None:
        tel.record_net_event(kind, **fields)


def telemetry_child_file(path: str) -> None:
    """Declare a child process's telemetry/trace file for the registry
    record (see :meth:`RunTelemetry.record_child_file`); no-op when
    telemetry is off."""
    tel = _active_telemetry
    if tel is not None:
        tel.record_child_file(path)


def telemetry_slab_lag(*, collect_us: int, ring_wait_us: int, train_us: int) -> None:
    """Record one admitted slab's critical-path decomposition (see
    :meth:`RunTelemetry.record_slab_lag`); no-op when telemetry is off."""
    tel = _active_telemetry
    if tel is not None:
        tel.record_slab_lag(collect_us=collect_us, ring_wait_us=ring_wait_us, train_us=train_us)


def telemetry_request_path(
    *,
    queue_wait_ms: float,
    assembly_ms: float,
    compute_ms: float,
    hedged: bool = False,
    rerouted: bool = False,
) -> None:
    """Record one completed request's critical-path decomposition (see
    :meth:`RunTelemetry.record_request_path`); no-op when telemetry is off."""
    tel = _active_telemetry
    if tel is not None:
        tel.record_request_path(
            queue_wait_ms=queue_wait_ms,
            assembly_ms=assembly_ms,
            compute_ms=compute_ms,
            hedged=hedged,
            rerouted=rerouted,
        )


def telemetry_register_flops(jitted_fn: Any, *args: Any, scale: float = 1.0) -> None:
    """Register a lazy ``compiled_flops`` source for MFU: shapes are captured
    eagerly (so no device buffers are pinned), the AOT cost analysis runs at
    most once, at the first heartbeat that needs it.  ``scale`` converts the
    analyzed program's cost to per-train-step flops — a fused superstep over K
    gradient steps registers ``scale=1/K`` so the heartbeat's MFU arithmetic
    (flops × gradient-step invocations / train time) stays consistent across
    fused and per-step paths."""
    tel = _active_telemetry
    if tel is None:
        return
    import jax

    def as_shape(x: Any) -> Any:
        if not (hasattr(x, "shape") and hasattr(x, "dtype")):
            return x
        # keep a committed array's sharding: the analysis then lowers the
        # very program the loop dispatched, and its compile is a persistent
        # cache hit instead of a second full compile of the train step
        committed = isinstance(x, jax.Array) and getattr(x, "committed", False)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding if committed else None)

    shapes = jax.tree.map(as_shape, args)

    def source() -> Optional[float]:
        from sheeprl_tpu.utils.profiler import compiled_flops

        flops = compiled_flops(jitted_fn, *shapes)
        return flops * float(scale) if flops else flops

    tel.set_flops_source(source)
