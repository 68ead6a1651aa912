"""Readers for a run's telemetry stream and run registry.

``python -m tools.report --<flag> PATH`` prints one JSON document digested
from a run's ``telemetry.jsonl`` (schema in howto/telemetry.md; sheeprl_tpu/obs
writes it): ``--telemetry`` (SPS/MFU/spans/compiles), ``--dispatch-stats``,
``--env-stats``, ``--resilience-stats``, ``--compile-stats``,
``--serve-stats`` (also a RUNS.jsonl registry), ``--net-stats`` and
``--trace`` (per-process streams merged by tools/trace.py).

Stdlib only: this module NEVER imports jax, so it runs beside a process
that holds the chip (and in a parent that starts chip children) without
touching the device. MFU arrives precomputed in the heartbeat fields, so no
peak-FLOPS table is needed here. The repo's benchmark is ``BENCHMARK.json``
and ``perfbench/run.py``; nothing here measures.
"""

from __future__ import annotations

import json
import os
import sys


def telemetry_segments(path: str) -> list:
    """A stream's on-disk segments, oldest first: size-capped rotation
    renames the overflowing file to ``<path>.1`` (obs/telemetry.py
    TelemetryWriter), so a soak run's early events — run_start, warmup
    compiles, the first heartbeats — live in the ``.1`` segment."""
    return [p for p in (path + ".1", path) if os.path.exists(p)]


def read_telemetry(path: str) -> list:
    """Parse a telemetry stream into a list of event dicts, reading rotated
    segments oldest-first (the old single-file reader silently dropped the
    ``.1`` segment, i.e. the entire first half of any rotated soak run). A
    torn final line (run killed mid-flush) is dropped, not fatal."""
    paths = telemetry_segments(path)
    if not paths:
        # preserve the old contract: a nonexistent stream raises
        raise FileNotFoundError(path)
    events = []
    for p in paths:
        with open(p) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    events.append(json.loads(line))
                except ValueError:
                    continue
    return events


def telemetry_summary(events_or_path) -> dict:
    """Aggregate a run's telemetry stream into its headline numbers:
    SPS from the heartbeat windows, time-weighted MFU, per-span totals,
    compile/recompile counts, device-poll count and HBM peak."""
    summary: dict = {}
    if isinstance(events_or_path, str):
        events = read_telemetry(events_or_path)
        summary["segments"] = len(telemetry_segments(events_or_path))
    else:
        events = list(events_or_path)
    summary["events"] = len(events)

    heartbeats = [e for e in events if e.get("event") == "heartbeat"]
    env_steps = sum(e.get("window_env_steps", 0) for e in heartbeats)
    env_time = sum(e.get("window_env_time", 0.0) for e in heartbeats)
    train_steps = sum(e.get("window_train_steps", 0) for e in heartbeats)
    train_time = sum(e.get("window_train_time", 0.0) for e in heartbeats)
    train_wait = sum(e.get("window_train_wait_time", 0.0) for e in heartbeats)
    summary["heartbeats"] = len(heartbeats)
    if env_time > 0:
        summary["sps_env"] = env_steps / env_time
    if train_time > 0:
        summary["sps_train"] = train_steps / train_time
    if env_time + train_time > 0:
        summary["duty_cycle_train"] = train_time / (env_time + train_time)
    loop_time = env_time + train_time + train_wait
    if loop_time > 0 and env_steps > 0:
        summary["sps_end_to_end"] = env_steps / loop_time
    if any("window_train_wait_time" in e for e in heartbeats):
        # overlapped collection (algo.overlap_collection): train_time is the
        # non-blocking dispatch span, train_wait the later block on its
        # result — collection ran in between, so env/(env+wait) is the hidden
        # fraction of each update cycle (1.0 = train fully overlapped)
        summary["train_wait_time"] = train_wait
        if env_time + train_wait > 0:
            summary["overlap_fraction"] = env_time / (env_time + train_wait)
    # train_time-weighted averages: a long window's MFU should count more
    weighted = [
        (e["window_train_time"], e[k])
        for k in ("mfu",)
        for e in heartbeats
        if k in e and e.get("window_train_time")
    ]
    if weighted:
        total_w = sum(w for w, _ in weighted)
        summary["mfu"] = sum(w * v for w, v in weighted) / total_w
    fps = [
        (e["window_train_time"], e["train_flops_per_sec"])
        for e in heartbeats
        if "train_flops_per_sec" in e and e.get("window_train_time")
    ]
    if fps:
        total_w = sum(w for w, _ in fps)
        summary["train_flops_per_sec"] = sum(w * v for w, v in fps) / total_w

    spans: dict = {}
    for e in events:
        if e.get("event") == "span":
            s = spans.setdefault(e.get("name", "<unnamed>"), {"count": 0, "total_s": 0.0})
            s["count"] += 1
            s["total_s"] += float(e.get("dur", 0.0))
    if spans:
        summary["spans"] = spans

    compiles = [e for e in events if e.get("event") == "compile" and e.get("phase") == "lower"]
    summary["compiles"] = len(compiles)
    summary["recompiles_post_warm"] = sum(1 for e in compiles if e.get("post_warm"))
    summary["device_polls"] = sum(1 for e in events if e.get("event") == "device_poll")
    hbm = [
        d.get("peak_bytes_in_use", 0)
        for e in events
        if e.get("event") == "device_poll"
        for d in e.get("devices", [])
    ]
    if any(hbm):
        summary["hbm_peak_bytes"] = max(hbm)
    ds = dispatch_stats(events)
    if ds.get("train_windows"):
        summary["dispatch_stats"] = ds
    return summary


def dispatch_stats(events_or_path) -> dict:
    """Per-train-window dispatch counts from the run-telemetry counters
    (obs/telemetry.py record_train_window): how many device programs one
    train window of G gradient steps issued. The fused superstep path
    (algo.fused_gradient_steps, howto/fused_training.md) should report
    dispatches_per_window == ceil(G / K); the per-step path reports ~G (x2
    with the device replay buffer's separate gather program). Prefers the
    run_end totals (they include the trailing unflushed heartbeat window),
    falls back to summing heartbeat windows for a still-running stream."""
    events = (
        read_telemetry(events_or_path) if isinstance(events_or_path, str) else list(events_or_path)
    )
    windows = dispatches = gradient_steps = 0
    fallbacks: dict = {}
    slabs_admitted = dropped_stale = torn_slabs = 0
    duty_cycle = None
    for e in events:
        if e.get("event") == "run_end":
            windows = int(e.get("train_windows", 0) or 0)
            dispatches = int(e.get("train_dispatches", 0) or 0)
            gradient_steps = int(e.get("train_gradient_steps", 0) or 0)
            fallbacks = dict(e.get("fused_fallbacks", {}) or {})
            slabs_admitted = int(e.get("slabs_admitted", 0) or 0)
            dropped_stale = int(e.get("dropped_stale_slabs", 0) or 0)
            torn_slabs = int(e.get("torn_slabs", 0) or 0)
            break
    else:
        for e in events:
            if e.get("event") == "heartbeat":
                windows += int(e.get("window_train_windows", 0) or 0)
                dispatches += int(e.get("window_train_dispatches", 0) or 0)
                gradient_steps += int(e.get("window_train_gradient_steps", 0) or 0)
                slabs_admitted += int(e.get("window_slabs_admitted", 0) or 0)
                dropped_stale += int(e.get("window_dropped_stale_slabs", 0) or 0)
                torn_slabs = int(e.get("torn_slabs_total", torn_slabs) or 0)
            elif e.get("event") == "fused_fallback":
                reason = str(e.get("reason", "<unknown>"))
                fallbacks[reason] = fallbacks.get(reason, 0) + 1
    # actor-learner learner duty cycle is a heartbeat-only field; the last
    # heartbeat's value is the steady-state one either way
    for e in reversed(events):
        if e.get("event") == "heartbeat" and "learner_duty_cycle" in e:
            duty_cycle = float(e["learner_duty_cycle"])
            break
    out = {
        "train_windows": windows,
        "train_dispatches": dispatches,
        "train_gradient_steps": gradient_steps,
    }
    if windows:
        out["dispatches_per_window"] = round(dispatches / windows, 3)
    if dispatches:
        out["gradient_steps_per_dispatch"] = round(gradient_steps / dispatches, 3)
    if fallbacks:
        # WHY a run dispatched per-step instead of fusing (ops/superstep.py
        # fused_fallback): reason -> count, e.g. {"host_buffer": 1}
        out["fused_fallbacks"] = fallbacks
    if slabs_admitted or dropped_stale or torn_slabs:
        # disaggregated actor-learner runs (howto/actor_learner.md): slab
        # admission/drop/torn totals plus the learner's train-vs-starved
        # duty cycle
        out["slabs_admitted"] = slabs_admitted
        out["dropped_stale_slabs"] = dropped_stale
        out["torn_slabs"] = torn_slabs
        if duty_cycle is not None:
            out["learner_duty_cycle"] = round(duty_cycle, 4)
    return out


def compile_stats(events_or_path) -> dict:
    """Compile-economy rollup from a run's telemetry stream: where this
    process's compiles came from and which cold paths skipped them. Counts
    lowered variants (total / deliberate-by-reason / post-warm recompiles /
    aot-load classified), the persistent trace-cache outcomes
    (``compile_cache`` events, fabric.configure_compilation_cache) and the AOT
    *executable* cache outcomes (``aot_cache`` events, ops/aotcache.py —
    hits are whole compiles that never ran). Prefers run_end totals, falls
    back to counting the event stream for a killed/still-running run."""
    events = (
        read_telemetry(events_or_path) if isinstance(events_or_path, str) else list(events_or_path)
    )
    compiles = [e for e in events if e.get("event") == "compile" and e.get("phase") == "lower"]
    out: dict = {
        "compiles": len(compiles),
        "recompiles_post_warm": sum(1 for e in compiles if e.get("post_warm")),
        "aot_load_classified": sum(1 for e in compiles if e.get("aot_load")),
        "compile_time_s": round(
            sum(
                float(e.get("dur", 0.0) or 0.0)
                for e in events
                if e.get("event") == "compile"
            ),
            3,
        ),
    }
    deliberate: dict = {}
    for e in compiles:
        reason = e.get("deliberate")
        if reason:
            deliberate[str(reason)] = deliberate.get(str(reason), 0) + 1
    trace_cache = {
        "hits": sum(1 for e in events if e.get("event") == "compile_cache" and e.get("hit")),
        "misses": sum(1 for e in events if e.get("event") == "compile_cache" and not e.get("hit")),
    }
    aot: dict = {}
    aot_tags: dict = {}
    for e in events:
        if e.get("event") != "aot_cache":
            continue
        action = str(e.get("action", "<unknown>"))
        aot[action] = aot.get(action, 0) + 1
        if action == "hit" and e.get("tag"):
            aot_tags[str(e["tag"])] = aot_tags.get(str(e["tag"]), 0) + 1
    for e in events:
        if e.get("event") == "run_end":
            # run_end totals cover windows the event scan above already saw,
            # but survive stream rotation truncating early events
            out["compiles"] = max(out["compiles"], int(e.get("compiles_total", 0) or 0))
            out["recompiles_post_warm"] = max(
                out["recompiles_post_warm"], int(e.get("recompiles", 0) or 0)
            )
            for reason, n in (e.get("deliberate_compiles") or {}).items():
                deliberate[str(reason)] = max(deliberate.get(str(reason), 0), int(n))
            trace_cache["hits"] = max(trace_cache["hits"], int(e.get("compile_cache_hits", 0) or 0))
            trace_cache["misses"] = max(
                trace_cache["misses"], int(e.get("compile_cache_misses", 0) or 0)
            )
            aot["hit"] = max(aot.get("hit", 0), int(e.get("aot_cache_hits", 0) or 0))
            aot["miss"] = max(aot.get("miss", 0), int(e.get("aot_cache_misses", 0) or 0))
            if e.get("aot_loads"):
                out["aot_loads"] = dict(e["aot_loads"])
            break
    if deliberate:
        out["deliberate_compiles"] = deliberate
    if trace_cache["hits"] or trace_cache["misses"]:
        out["trace_cache"] = trace_cache
    if aot:
        out["aot_cache"] = aot
    if aot_tags:
        out["aot_cache_hit_tags"] = aot_tags
    return out


def _percentile(sorted_values: list, q: float) -> float:
    """Linear-interpolation percentile over an already-sorted list (matches
    numpy's default method without importing numpy)."""
    n = len(sorted_values)
    if n == 1:
        return float(sorted_values[0])
    pos = (q / 100.0) * (n - 1)
    lo = int(pos)
    hi = min(lo + 1, n - 1)
    frac = pos - lo
    return float(sorted_values[lo] * (1.0 - frac) + sorted_values[hi] * frac)


def env_stats_summary(events_or_path) -> dict:
    """Rollout-pool health from a run's telemetry stream (env.backend=pool,
    sheeprl_tpu/rollout): env step/reset latency percentiles from the
    ``rollout/env_step``/``rollout/env_reset`` spans (with the queue-wait
    share — dispatch + pipe wait beyond the slowest worker's busy time),
    every ``worker_restart`` event (worker, reason, restart count) and the
    ``masked_slot`` events for workers that exhausted their retry budget.
    Totals prefer run_end (they cover the trailing unflushed window), falling
    back to the event stream for a still-running run."""
    events = (
        read_telemetry(events_or_path) if isinstance(events_or_path, str) else list(events_or_path)
    )
    out: dict = {}

    for span_name, key in (("rollout/env_step", "env_step"), ("rollout/env_reset", "env_reset")):
        durs, waits = [], []
        for e in events:
            if e.get("event") == "span" and e.get("name") == span_name:
                durs.append(float(e.get("dur", 0.0)))
                wait = (e.get("attrs") or {}).get("queue_wait_s")
                if wait is not None:
                    waits.append(float(wait))
        if not durs:
            continue
        durs.sort()
        stats = {
            "count": len(durs),
            "total_s": round(sum(durs), 3),
            "p50_ms": round(_percentile(durs, 50) * 1e3, 3),
            "p95_ms": round(_percentile(durs, 95) * 1e3, 3),
            "max_ms": round(durs[-1] * 1e3, 3),
        }
        if waits:
            waits.sort()
            stats["queue_wait_p50_ms"] = round(_percentile(waits, 50) * 1e3, 3)
            stats["queue_wait_p95_ms"] = round(_percentile(waits, 95) * 1e3, 3)
        out[key] = stats

    restarts = [e for e in events if e.get("event") == "worker_restart"]
    if restarts:
        out["worker_restarts"] = [
            {
                "worker": e.get("worker"),
                "reason": e.get("reason"),
                "restarts": e.get("restarts"),
                "step": e.get("step"),
            }
            for e in restarts
        ]
    masked = [e for e in events if e.get("event") == "masked_slot"]
    if masked:
        out["masked_slots"] = [
            {"worker": e.get("worker"), "slots": e.get("slots"), "reason": e.get("reason")}
            for e in masked
        ]

    totals = {"worker_restarts": len(restarts)}
    totals["masked_slots"] = sum(
        len(e.get("slots") or []) if isinstance(e.get("slots"), (list, tuple)) else 1 for e in masked
    )
    for e in events:
        if e.get("event") == "run_end":
            totals["worker_restarts"] = int(e.get("worker_restarts", 0) or 0)
            totals["masked_slots"] = int(e.get("masked_slots", 0) or 0)
            break
    out["totals"] = totals
    return out


def net_stats_report(events_or_path) -> dict:
    """Multi-host data-plane health from a run's telemetry stream
    (sheeprl_tpu/net, howto/multihost.md): per-transport-endpoint counters
    (frames/bytes sent+received, reconnects, checksum rejects, heartbeat
    gaps, torn frames, stale slabs) from the run_end ``net`` section, the
    sparse ``net_event`` lines (reconnect / disconnect / checksum_reject /
    remote_timeout / transport_close, with their transport+peer fields), and
    the cross-host clock-skew observations from ``net_handshake`` trace
    events. Counter totals prefer run_end (they cover the trailing
    unflushed window), falling back to summing the event stream for a
    still-running run."""
    events = (
        read_telemetry(events_or_path) if isinstance(events_or_path, str) else list(events_or_path)
    )
    out: dict = {}

    run_end_net = None
    for e in events:
        if e.get("event") == "run_end" and isinstance(e.get("net"), dict):
            run_end_net = e["net"]
            break

    net_events = [e for e in events if e.get("event") == "net_event"]
    by_kind: dict = {}
    for e in net_events:
        kind = str(e.get("kind", "?"))
        by_kind[kind] = by_kind.get(kind, 0) + 1
    if run_end_net and isinstance(run_end_net.get("events"), dict):
        # run_end counted every event, including any in the unflushed tail
        by_kind = {str(k): int(v) for k, v in run_end_net["events"].items()}
    if by_kind:
        out["events"] = dict(sorted(by_kind.items()))
    if net_events:
        out["event_log"] = [
            {
                k: e.get(k)
                for k in ("kind", "transport", "peer", "actor", "replica", "generation", "reason")
                if e.get(k) is not None
            }
            for e in net_events
        ]

    transports = None
    if run_end_net and isinstance(run_end_net.get("transports"), dict):
        transports = run_end_net["transports"]
    if transports:
        out["transports"] = {name: dict(counters) for name, counters in sorted(transports.items())}
        totals: dict = {}
        for counters in transports.values():
            for k, v in counters.items():
                if isinstance(v, (int, float)):
                    totals[k] = totals.get(k, 0) + v
        out["totals"] = totals

    handshakes = [
        e
        for e in events
        if e.get("event") == "trace" and e.get("kind") == "net_handshake"
    ]
    if handshakes:
        skews: dict = {}
        for e in handshakes:
            peer = str(e.get("peer", "?"))
            if isinstance(e.get("skew_s"), (int, float)):
                skews.setdefault(peer, []).append(float(e["skew_s"]))
        out["handshakes"] = {
            "count": len(handshakes),
            "peers": sorted({str(e.get("peer", "?")) for e in handshakes}),
        }
        if skews:
            out["handshakes"]["skew_s"] = {
                peer: round(sorted(vals)[len(vals) // 2], 6) for peer, vals in sorted(skews.items())
            }

    if not out:
        out["note"] = (
            "no net telemetry in this stream (no run_end net section, net_event "
            "or net_handshake lines). The data plane only reports when a TCP/shm "
            "transport or remote replica was active — see howto/multihost.md."
        )
    return out


def resilience_stats(events_or_path) -> dict:
    """Checkpoint/rollback health from a run's telemetry stream
    (sheeprl_tpu/resilience, howto/resilience.md): ``ckpt/snapshot`` (the only
    part that blocks the train loop under ``checkpoint.async_save``) and
    ``ckpt/write`` span percentiles with the async/sync dispatch split,
    every ``ckpt_committed``/``ckpt_skipped`` step, the ``nan_rollback``
    events (restored path, remaining budget), ``preempt`` signals and
    ``resume_fallback``/``auto_resume`` decisions. Totals prefer run_end
    (they cover the trailing unflushed window), falling back to the event
    stream for a still-running or preempted run."""
    events = (
        read_telemetry(events_or_path) if isinstance(events_or_path, str) else list(events_or_path)
    )
    out: dict = {}

    for span_name, key in (("ckpt/snapshot", "snapshot"), ("ckpt/write", "write")):
        durs, sync_count = [], 0
        for e in events:
            if e.get("event") == "span" and e.get("name") == span_name:
                durs.append(float(e.get("dur", 0.0)))
                if (e.get("attrs") or {}).get("sync"):
                    sync_count += 1
        if not durs:
            continue
        durs.sort()
        stats = {
            "count": len(durs),
            "total_s": round(sum(durs), 3),
            "p50_ms": round(_percentile(durs, 50) * 1e3, 3),
            "p95_ms": round(_percentile(durs, 95) * 1e3, 3),
            "max_ms": round(durs[-1] * 1e3, 3),
        }
        if key == "write":
            stats["sync_count"] = sync_count
            stats["async_count"] = len(durs) - sync_count
        out[key] = stats

    commits = [e for e in events if e.get("event") == "ckpt_committed"]
    if commits:
        out["committed_steps"] = [int(e.get("ckpt_step", 0) or 0) for e in commits]
        if any(e.get("emergency") for e in commits):
            out["emergency_steps"] = [
                int(e.get("ckpt_step", 0) or 0) for e in commits if e.get("emergency")
            ]
    skipped = [e for e in events if e.get("event") == "ckpt_skipped"]
    if skipped:
        out["skipped_steps"] = [int(e.get("ckpt_step", 0) or 0) for e in skipped]
    rollbacks = [e for e in events if e.get("event") == "nan_rollback"]
    if rollbacks:
        out["nan_rollbacks"] = [
            {
                "update": e.get("update"),
                "path": e.get("path"),
                "reason": e.get("reason"),
                "remaining": e.get("remaining"),
            }
            for e in rollbacks
        ]
    preempts = [e for e in events if e.get("event") == "preempt"]
    if preempts:
        out["preempts"] = [{"signum": e.get("signum"), "step": e.get("step")} for e in preempts]
    fallbacks = [e for e in events if e.get("event") == "resume_fallback"]
    if fallbacks:
        out["resume_fallbacks"] = [
            {"path": e.get("path"), "error": e.get("error")} for e in fallbacks
        ]
    resumed = [e for e in events if e.get("event") == "auto_resume"]
    if resumed:
        out["auto_resume"] = [
            {"path": e.get("path"), "ckpt_step": e.get("ckpt_step")} for e in resumed
        ]

    totals = {
        "ckpt_commits": len(commits),
        "ckpt_skipped": len(skipped),
        "nan_rollbacks": len(rollbacks),
        "preemptions": len(preempts),
        "resume_fallbacks": len(fallbacks),
    }
    for e in events:
        if e.get("event") == "run_end":
            for k in totals:
                totals[k] = int(e.get(k, 0) or 0)
            break
    out["totals"] = totals
    return out


def trace_summary(paths: list) -> dict:
    """Merge the given per-process trace/telemetry streams (tools/trace.py)
    and return the critical-path attribution: the per-slab lag decomposition
    (collect -> ring-wait -> train with slab-age p50/p95) and the per-request
    latency decomposition (queue-wait -> assembly -> compute with hedge
    dedup). Both sections are always present — empty runs report zero traces
    rather than omitting the section."""
    # by file path: tests load this module the same way, without the tools
    # package on sys.path
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "trace.py")
    spec = importlib.util.spec_from_file_location("_sheeprl_tpu_trace", path)
    trace_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_mod)
    merged = trace_mod.merge(paths)
    return trace_mod.summarize(merged)


def _slo_goodput(stats: dict):
    """``qps@p95`` for one serve snapshot: completed QPS while p95 <= SLO,
    else 0.0; a ramp report's ``max_good_qps`` already encodes the
    conditioning. Mirrors ``tools/regress.py slo_goodput`` (kept local so
    this module stays importable by file path, without the tools package
    on sys.path)."""
    report = stats.get("load_report")
    if isinstance(report, dict):
        if report.get("mode") == "ramp":
            value = report.get("max_good_qps")
            return float(value) if isinstance(value, (int, float)) else None
        qps, p95, slo = report.get("qps"), report.get("p95_ms"), report.get("slo_ms")
        if isinstance(qps, (int, float)):
            met = isinstance(p95, (int, float)) and isinstance(slo, (int, float)) and p95 <= slo
            return float(qps) if met else 0.0
    qps, p95, slo = stats.get("qps"), stats.get("p95_ms"), stats.get("slo_ms")
    if isinstance(qps, (int, float)) and isinstance(p95, (int, float)) and isinstance(slo, (int, float)):
        return float(qps) if p95 <= slo else 0.0
    return None


def _record_serve_section(rec: dict) -> dict:
    """A registry record's serve snapshot: the telemetry ``serve.stats``
    section when the run had telemetry, else the raw ``serve_stats`` extra
    ``cli_serve`` attaches (same fallback order as tools/regress.py)."""
    serve = rec.get("serve")
    if isinstance(serve, dict) and isinstance(serve.get("stats"), dict):
        return serve["stats"]
    if isinstance(rec.get("serve_stats"), dict):
        return rec["serve_stats"]
    return {}


_REPLICA_ROW_KEYS = (
    "index", "kind", "device", "active", "alive", "masked", "retiring",
    "restarts", "health", "depth", "outstanding", "requests", "failures",
)

_ROUTER_COUNTER_KEYS = (
    "routed", "shed", "hedged", "hedged_won", "rerouted_requests", "blackholed", "spilled",
)


def serve_registry_stats(records) -> dict:
    """Aggregate EVERY ``kind=serve`` record in a RUNS.jsonl registry —
    one row per serve run (QPS, p95 vs SLO, sheds, ``qps@p95`` goodput),
    per-replica rows lifted from each fleet snapshot, and a fleet rollup
    (scale events, summed router counters, best goodput). A fleet
    acceptance sweep registers several serve runs back-to-back; digesting
    only the newest record — the old behaviour — hid every earlier run."""
    serve_recs = [r for r in records if r.get("kind") in ("serve", "serve_train")]
    if not serve_recs:
        return {
            "error": (
                "no serve records in this registry (kind=serve/serve_train). Serve sessions "
                "append one on exit via register_run; run `python -m sheeprl_tpu serve ...` "
                "first (see howto/serving.md)"
            )
        }
    rows: list = []
    replica_rows: list = []
    fleet_sections: list = []
    for idx, rec in enumerate(serve_recs):
        stats = _record_serve_section(rec)
        row: dict = {
            "record": idx,
            "t": rec.get("t"),
            "kind": rec.get("kind"),
            "algo": rec.get("algo"),
            "env": rec.get("env"),
            "variant": rec.get("variant"),
            "outcome": rec.get("outcome"),
        }
        # serve_train records carry the online-learning bridge counters
        # (eval improvement, shed experience, hook/publish/swap books)
        if isinstance(rec.get("online"), dict):
            row["online"] = dict(rec["online"])
        for k in ("qps", "p50_ms", "p95_ms", "slo_ms", "completed",
                  "shed_overloaded", "shed_expired", "failed"):
            if isinstance(stats.get(k), (int, float)):
                row[k] = stats[k]
        goodput = _slo_goodput(stats)
        if goodput is not None:
            row["qps@p95"] = goodput
        report = stats.get("load_report")
        if isinstance(report, dict) and report.get("mode") == "ramp":
            row["knee_rate_hz"] = report.get("knee_rate_hz")
            row["max_good_qps"] = report.get("max_good_qps")
        fleet = stats.get("fleet")
        if isinstance(fleet, dict):
            fleet_sections.append((idx, fleet, goodput))
            for rep in fleet.get("replicas") or []:
                if isinstance(rep, dict):
                    replica_rows.append(
                        {"record": idx, **{k: rep[k] for k in _REPLICA_ROW_KEYS if k in rep}}
                    )
        rows.append(row)
    out: dict = {"source": "runs_registry", "serve_records": len(serve_recs), "records": rows}
    if fleet_sections:
        newest = fleet_sections[-1][1]
        router_totals = {k: 0 for k in _ROUTER_COUNTER_KEYS}
        for _, fleet, _ in fleet_sections:
            router = fleet.get("router") or {}
            for k in _ROUTER_COUNTER_KEYS:
                if isinstance(router.get(k), (int, float)):
                    router_totals[k] += int(router[k])
        goodputs = [g for _, _, g in fleet_sections if isinstance(g, (int, float))]
        out["fleet"] = {
            "rollup": {
                "fleet_records": len(fleet_sections),
                "active_device_replicas": newest.get("active_device_replicas"),
                "cpu_spill_replicas": newest.get("cpu_spill_replicas"),
                "scale_ups": sum(
                    int(f.get("scale_ups", 0) or 0) for _, f, _ in fleet_sections
                ),
                "scale_downs": sum(
                    int(f.get("scale_downs", 0) or 0) for _, f, _ in fleet_sections
                ),
                "router": router_totals,
                **({"best_qps@p95": max(goodputs)} if goodputs else {}),
            },
            "replicas": replica_rows,
        }
    return out


def serve_stats(events_or_path) -> dict:
    """Policy-serving health from a serve session's telemetry stream
    (sheeprl_tpu/serve, howto/serving.md): sustained QPS, p50/p95 end-to-end
    latency vs the SLO, queue depth, shed counts (admission rejections +
    deadline expiries), replica restarts/masks, swap promotions/rejections
    and the load-generator report when one ran. Totals prefer the run_end
    ``serve`` section, falling back to the last ``serve_stats`` event for a
    still-running server. Also accepts a RUNS.jsonl run registry (lines with
    ``kind`` instead of ``event``) and then aggregates across ALL serve
    records — see :func:`serve_registry_stats`. Degrades with a targeted
    ``error`` key — not a traceback — when the stream has no serve telemetry
    at all."""
    try:
        events = (
            read_telemetry(events_or_path) if isinstance(events_or_path, str) else list(events_or_path)
        )
    except OSError as e:
        return {"error": f"cannot read telemetry stream: {e}"}

    # a run registry instead of a telemetry stream: registry records carry
    # ``kind`` (train/eval/serve/...) and never ``event``
    if events and not any("event" in e for e in events) and any("kind" in e for e in events):
        return serve_registry_stats(events)

    snapshots = [e for e in events if e.get("event") == "serve_stats"]
    serve_events = [e for e in events if e.get("event") == "serve_event"]
    run_end_serve = None
    for e in reversed(events):
        if e.get("event") == "run_end" and isinstance(e.get("serve"), dict):
            run_end_serve = e["serve"]
            break
    if not snapshots and not serve_events and not run_end_serve:
        return {
            "error": (
                "no serve telemetry in this stream (no serve_stats/serve_event events). "
                "Serve sessions emit them when started with metric.telemetry.enabled=True: "
                "`python -m sheeprl_tpu serve checkpoint_path=... metric.telemetry.enabled=True` "
                "(see howto/serving.md)"
            )
        }

    # totals prefer run_end (covers the trailing window); a still-running or
    # killed server falls back to its last periodic snapshot
    last = dict((run_end_serve or {}).get("stats") or (snapshots[-1] if snapshots else {}))
    for drop in ("event", "t", "step", "process_index"):
        last.pop(drop, None)
    out: dict = {"snapshots": len(snapshots), "totals": last}
    load_report = last.pop("load_report", None)
    if load_report:
        out["load_report"] = load_report
        slo = load_report.get("slo_ms")
        p95 = load_report.get("p95_ms")
        if slo is not None and p95 is not None:
            out["slo_met"] = bool(p95 <= slo)

    by_kind: dict = {}
    for e in serve_events:
        by_kind[e.get("kind", "?")] = by_kind.get(e.get("kind", "?"), 0) + 1
    if run_end_serve and run_end_serve.get("events"):
        by_kind = dict(run_end_serve["events"])
    if by_kind:
        out["events"] = by_kind
    restarts = [e for e in serve_events if e.get("kind") == "replica_restart"]
    if restarts:
        out["replica_restarts"] = [
            {"replica": e.get("replica"), "reason": e.get("reason"), "backoff_s": e.get("backoff_s")}
            for e in restarts
        ]
    masked = [e for e in serve_events if e.get("kind") == "replica_masked"]
    if masked:
        out["replicas_masked"] = [
            {"replica": e.get("replica"), "reason": e.get("reason")} for e in masked
        ]
    swaps = [e for e in serve_events if e.get("kind") in ("swap", "swap_rejected", "rollback")]
    if swaps:
        out["swap_events"] = [
            {
                "kind": e.get("kind"),
                "step": e.get("step"),
                **({"reason": e.get("reason")} if e.get("reason") else {}),
            }
            for e in swaps
        ]
    # online-learning bridge fold: every serve_event the bridge emits is
    # prefixed ``online_`` (exp_slab/exp_slab_shed/hook_hang/publish_*/...);
    # a run_end ``online`` section (bridge+learner+publisher snapshot with
    # shed_experience and the feedback-hook books) wins when present
    online_events = {
        k[len("online_"):]: n for k, n in sorted(by_kind.items()) if k.startswith("online_")
    }
    run_end_online = None
    for e in reversed(events):
        if e.get("event") == "run_end" and isinstance(e.get("online"), dict):
            run_end_online = e["online"]
            break
    if online_events or run_end_online:
        out["online"] = {**(run_end_online or {})}
        if online_events:
            out["online"]["events"] = online_events
    return out


_REPORTS = {
    "telemetry": (telemetry_summary, "summarize a run's telemetry.jsonl (SPS/MFU/spans/compiles)"),
    "dispatch-stats": (
        dispatch_stats,
        "per-train-window device dispatch counts from a run's telemetry.jsonl "
        "(fused supersteps should show ceil(G/K) per window)",
    ),
    "env-stats": (
        env_stats_summary,
        "rollout-pool health from a run's telemetry.jsonl (env step latency "
        "percentiles, worker restarts, masked slots)",
    ),
    "resilience-stats": (
        resilience_stats,
        "checkpoint/rollback health from a run's telemetry.jsonl (ckpt "
        "snapshot/write span percentiles, skipped saves, NaN rollbacks, "
        "preemptions, auto-resume decisions)",
    ),
    "compile-stats": (
        compile_stats,
        "the compile economy from a run's telemetry.jsonl (lowered variants, "
        "deliberate-by-reason, post-warm recompiles, trace-cache hit/miss, AOT "
        "executable-cache hit/miss/store/GC by tag — a hit is a whole compile "
        "that never ran)",
    ),
    "serve-stats": (
        serve_stats,
        "policy-serving health from a serve session's telemetry.jsonl (QPS, "
        "p50/p95 vs SLO, queue depth, shed counts, replica restarts/masks, swap "
        "promotions/rejections, load-generator report); also accepts a "
        "RUNS.jsonl registry and then aggregates every serve record (per-run "
        "rows, per-replica rows, fleet rollup)",
    ),
    "net-stats": (
        net_stats_report,
        "multi-host data-plane health from a run's telemetry.jsonl "
        "(per-transport frames/bytes/reconnects/checksum-rejects/heartbeat-gaps "
        "from the run_end net section, the net_event log, and cross-host "
        "handshake clock skews)",
    ),
}


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(prog="tools.report", description=__doc__.splitlines()[0])
    group = parser.add_mutually_exclusive_group(required=True)
    for flag, (_, text) in _REPORTS.items():
        group.add_argument(f"--{flag}", metavar="PATH", help=text)
    group.add_argument(
        "--trace",
        metavar="PATH",
        nargs="+",
        help="merge per-process trace/telemetry streams (tools/trace.py) and "
        "print the critical-path attribution: per-slab lag decomposition "
        "(collect -> ring-wait -> train, slab-age p50/p95) and per-request "
        "latency decomposition (queue-wait -> assembly -> compute, hedge "
        "dedup) — pass the run's telemetry_files set from RUNS.jsonl",
    )
    args = parser.parse_args(argv)
    doc = trace_summary(args.trace) if args.trace else None
    for flag, (reader, _) in _REPORTS.items():
        path = getattr(args, flag.replace("-", "_"))
        if path:
            doc = reader(path)
    print(json.dumps(doc, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
