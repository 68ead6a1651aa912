"""Benchmark harness: prints ONE JSON line, or nothing and a non-zero exit.

PRIMARY metric (the north star in BASELINE.json): **Dreamer-V3
env-steps/sec/chip** on the reference's benchmark model sizes
(configs/exp/dreamer_v3_benchmarks.yaml:27-45 — tiny nets, 64x64 pixels)
with the NORTH-STAR training shape (walker-walk recipe: 4 envs,
replay_ratio 0.5 — dreamer_v3_dmc_walker_walk.yaml:27-51), driven end to end through the CLI (player
forward + buffer + fused train step). The pixel source is the dummy env —
the recipe's MsPacman needs ale_py, absent in this image — so both sides of
the comparison step identical 64x64x3 frames.

``vs_baseline`` divides by a MEASURED baseline: the same workload implemented
in torch (the reference's compute path; the reference itself cannot run here
— lightning/hydra are not installed) timed on the host CPU with
``python benchmarks/dv3_torch_baseline.py``.

A secondary PPO number (the reference's other benchmark workload) rides in
the same JSON object under ``secondary``.

The numbers are device numbers, so they exist only where there is a device:
``main()`` probes the platform first and exits non-zero, printing no number,
unless it is ``tpu``; a workload that fails or times out ends the run the
same way. This process NEVER imports jax itself — every workload (and the
probe) runs in a timeout-guarded subprocess, one after another, because a
chip belongs to one process at a time: a parent that had touched jax would
hold it and starve its own children.
"""

from __future__ import annotations

import json
import os
import sys
import time

# torch-CPU baselines, measured 2026-07-30 on the one-core host of that round
# (not this machine; not re-measured since) with:
# python benchmarks/dv3_torch_baseline.py 2048
_DV3_TORCH_CPU_SPS = 4.16
# python benchmarks/ppo_torch_baseline.py 32768 (same workload shape as
# bench_ppo: 64 envs, rollout 128, 10 epochs, 512 minibatch, 2x64 MLP)
_PPO_TORCH_CPU_SPS = 12912.91

DV3_STEPS = 2048
PPO_STEPS = 32768

def _dv3_args(total_steps: int, learning_starts: int = 512):
    return [
        "exp=dreamer_v3",
        "env=dummy",
        "env.id=dummy_discrete",
        # sync envs: on this 1-core host AsyncVectorEnv's worker pipes are
        # pure overhead (measured 4.4 s of pipe I/O per 256 vector steps —
        # benchmarks/ppo_floor.py investigation), and the torch baseline
        # steps synchronously too
        "env.sync_env=True",
        "env.num_envs=4",
        "env.screen_size=64",
        "env.capture_video=False",
        f"algo.total_steps={total_steps}",
        f"algo.learning_starts={learning_starts}",
        "algo.replay_ratio=0.5",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.world_model.discrete_size=4",
        "algo.world_model.stochastic_size=4",
        "algo.world_model.encoder.cnn_channels_multiplier=2",
        "algo.world_model.recurrent_model.recurrent_state_size=8",
        "algo.world_model.transition_model.hidden_size=8",
        "algo.world_model.representation_model.hidden_size=8",
        "algo.cnn_keys.encoder=[rgb]",
        "algo.mlp_keys.encoder=[]",
        "algo.run_test=False",
        "buffer.size=16384",
        "buffer.memmap=False",
        "checkpoint.every=10000000",
        "checkpoint.save_last=False",
        "metric.log_level=0",
    ]


def bench_dv3() -> dict:
    import tempfile

    from sheeprl_tpu.cli import run

    # ONE process, one run: the training loop itself records steady-state
    # throughput from update ``learning_starts + 64`` (everything compiled
    # and warm) to the last update via SHEEPRL_TPU_BENCH_JSON — no persistent
    # compile cache, no second run whose jits must round-trip a cache
    with tempfile.TemporaryDirectory() as d:
        probe = os.path.join(d, "dv3_bench.json")
        os.environ["SHEEPRL_TPU_BENCH_JSON"] = probe
        try:
            run(_dv3_args(DV3_STEPS))
        finally:
            os.environ.pop("SHEEPRL_TPU_BENCH_JSON", None)
        rec = _read_probe(probe, "dreamer_v3")
    # single-chip MFU at the bench shape: FLOPs of one fused train step (XLA
    # cost analysis, recorded by the loop post-window) x gradient steps in
    # the steady-state window / window seconds / chip bf16 peak. The bench
    # nets are tiny, so this MFU states how much of the chip the bench
    # workload can even use — benchmarks/mfu_probe.py holds the model-size
    # sweep (S size and up) where the MFU ceiling is meaningful. Computed
    # HERE (not in the parent) so the parent process stays jax-free.
    import jax

    from sheeprl_tpu.utils.profiler import PEAK_BF16_FLOPS

    rec["device_kind"] = jax.devices()[0].device_kind
    flops, train_steps = rec.get("flops_per_train_step"), rec.get("train_steps")
    if flops and train_steps:
        rec["train_flops_per_sec"] = round(flops * train_steps / rec["seconds"], 1)
        peak = PEAK_BF16_FLOPS.get(rec["device_kind"])
        if peak:
            rec["mfu"] = round(flops * train_steps / rec["seconds"] / peak, 6)
            rec["mfu_peak_flops_assumed"] = peak
    return rec


def _read_probe(path, workload):
    if not os.path.exists(path):
        raise RuntimeError(
            f"the {workload} run finished without reaching its steady-state mark "
            "(SteadyStateProbe never fired) — the workload is too short to measure; "
            "raise total_steps or lower learning_starts"
        )
    with open(path) as f:
        rec = json.load(f)
    if rec.get("error") == "window_never_opened":
        # the probe ran to finish() but the warmup gate never opened — a
        # configuration problem (run shorter than the warmup): say so
        raise RuntimeError(
            f"the {workload} run ended before its steady-state window opened: "
            f"{rec.get('detail', 'run shorter than warmup')}"
        )
    return rec


# ------------------------------------------------------------ telemetry ----
# Readers for the run-telemetry JSONL stream (sheeprl_tpu/obs, schema in
# howto/telemetry.md): the run's own heartbeat/span/compile events replace
# log scraping as the source of SPS/MFU. Pure python — the bench parent
# NEVER imports jax (see module docstring), and MFU arrives precomputed in
# the heartbeat fields, so no peak-FLOPS table is needed here.


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
    """Aggregate a run's telemetry stream into the bench-facing numbers:
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
    numpy's default method without importing numpy into the bench parent)."""
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


def _load_tool(name: str):
    """Load a tools/ module by file path so this parent stays jax-free and
    importable without the tools package on sys.path (same reason --regress
    loads tools/regress.py this way)."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_sheeprl_tpu_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def trace_summary(paths: list) -> dict:
    """Merge the given per-process trace/telemetry streams (tools/trace.py)
    and return the critical-path attribution: the per-slab lag decomposition
    (collect -> ring-wait -> train with slab-age p50/p95) and the per-request
    latency decomposition (queue-wait -> assembly -> compute with hedge
    dedup). Both sections are always present — empty runs report zero traces
    rather than omitting the section."""
    trace_mod = _load_tool("trace")
    merged = trace_mod.merge(paths)
    return trace_mod.summarize(merged)


def _slo_goodput(stats: dict):
    """``qps@p95`` for one serve snapshot: completed QPS while p95 <= SLO,
    else 0.0; a ramp report's ``max_good_qps`` already encodes the
    conditioning. Mirrors ``tools/regress.py slo_goodput`` (kept local so
    this parent stays importable without the tools package on sys.path)."""
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


def _ppo_args(total_steps: int):
    return [
        "exp=ppo",
        f"algo.total_steps={total_steps}",
        "env.num_envs=64",
        # SyncVectorEnv for parity with the torch baseline (its loop is
        # sync); 64 async workers on one core spend more time in
        # multiprocessing pipes than in the envs
        "env.sync_env=True",
        "algo.per_rank_batch_size=512",
        "env.capture_video=False",
        "buffer.memmap=False",
        "algo.run_test=False",
        "checkpoint.every=10000000",
        "checkpoint.save_last=False",
        "metric.log_level=0",
    ]


def bench_ppo() -> dict:
    import tempfile

    from sheeprl_tpu.cli import run

    with tempfile.TemporaryDirectory() as d:
        probe = os.path.join(d, "ppo_bench.json")
        os.environ["SHEEPRL_TPU_BENCH_JSON"] = probe
        try:
            run(_ppo_args(PPO_STEPS))
        finally:
            os.environ.pop("SHEEPRL_TPU_BENCH_JSON", None)
        rec = _read_probe(probe, "ppo")
    return rec


def bench_ppo_fused() -> dict:
    """The fused-rollout PPO workload (algo.fused_rollout=True, howto/
    fused_training.md "On-policy collection"): the whole update — device
    rollout + GAE + train — is ONE dispatch. Same steps/shape as bench_ppo,
    so the two records quantify the host-loop gap directly. The CLI run
    registers itself in RUNS.jsonl with variant=fused_rollout, which is the
    regress cell the acceptance gate watches."""
    import tempfile

    from sheeprl_tpu.cli import run

    with tempfile.TemporaryDirectory() as d:
        probe = os.path.join(d, "ppo_fused_bench.json")
        os.environ["SHEEPRL_TPU_BENCH_JSON"] = probe
        try:
            run(_ppo_args(PPO_STEPS) + ["algo.fused_rollout=True"])
        finally:
            os.environ.pop("SHEEPRL_TPU_BENCH_JSON", None)
        rec = _read_probe(probe, "ppo_fused")
    return rec


def bench_ppo_actor_learner() -> dict:
    """The disaggregated actor–learner PPO workload (exp=ppo_decoupled on a
    single process, howto/actor_learner.md): supervised CPU actor processes
    stream trajectory slabs through the shared-memory ring while the learner
    trains continuously and broadcasts versioned params back. Same env count
    and step budget as bench_ppo, so the three records (host loop, fused,
    actor-learner) quantify the dispatch strategies directly. The CLI run
    registers itself in RUNS.jsonl with variant=actor_learner — the regress
    cell the acceptance gate watches (sps + overlap_fraction)."""
    import tempfile

    from sheeprl_tpu.cli import run

    args = ["exp=ppo_decoupled" if a == "exp=ppo" else a for a in _ppo_args(PPO_STEPS)]
    with tempfile.TemporaryDirectory() as d:
        probe = os.path.join(d, "ppo_actor_learner_bench.json")
        os.environ["SHEEPRL_TPU_BENCH_JSON"] = probe
        try:
            run(
                args
                + [
                    "algo.per_rank_batch_size=512",
                    # two actors overprovision collection, so slabs queue:
                    # one slot each bounds the queue by backpressure instead
                    # of staleness drops, and the admission bound covers the
                    # full in-flight depth (one queued + one collecting per
                    # actor) — see howto/actor_learner.md "Staleness"
                    "algo.actor_learner.num_actors=2",
                    "algo.actor_learner.slots_per_actor=1",
                    "algo.actor_learner.max_staleness=3",
                ]
            )
        finally:
            os.environ.pop("SHEEPRL_TPU_BENCH_JSON", None)
        rec = _read_probe(probe, "ppo_actor_learner")
    return rec


def bench_ppo_floor() -> dict:
    """The benchmarks/ppo_floor.py stage ladder as a bench workload: bare
    vector env -> noop policy -> jitted player -> player+bookkeeping. The
    parent folds each stage into the run registry (kind=floor, variant=stage)
    so the floor itself is regression-gated alongside the training cells."""
    import benchmarks.ppo_floor as floor

    steps = int(os.environ.get("SHEEPRL_TPU_FLOOR_STEPS", "16384"))
    n_envs = int(os.environ.get("SHEEPRL_TPU_FLOOR_ENVS", "64"))
    envs = floor.make_envs(n_envs)
    rec: dict = {"workload": "ppo_floor", "envs": n_envs, "steps": steps, "stages": {}}
    try:
        rec["stages"]["random"] = round(floor.stage_random(envs, steps), 1)
        rec["stages"]["noop_policy"] = round(floor.stage_noop_policy(envs, steps), 1)
        rec["stages"]["player"] = round(floor.stage_player(envs, steps), 1)
        rec["stages"]["bookkeeping"] = round(floor.stage_bookkeeping(envs, steps), 1)
    finally:
        envs.close()
    return rec


def append_floor_runs(rec: dict, runs_path: str) -> int:
    """Fold a ppo_floor workload record into the run registry: one JSONL
    line per stage, keyed so tools/regress.py gates each stage as its own
    ``floor:ppo:CartPole-v1:hostx1p1:<stage>`` cell. Stdlib-only — runs in
    the jax-free bench parent."""
    stages = rec.get("stages") or {}
    written = 0
    with open(runs_path, "a") as f:
        for stage, sps in sorted(stages.items()):
            if not isinstance(sps, (int, float)):
                continue
            f.write(
                json.dumps(
                    {
                        "schema": 1,
                        "t": time.time(),
                        "kind": "floor",
                        "algo": "ppo",
                        "env": "CartPole-v1",
                        "backend": "host",
                        "local_device_count": 1,
                        "process_count": 1,
                        "outcome": "completed",
                        "variant": stage,
                        "sps_env": float(sps),
                        "envs": rec.get("envs"),
                        "steps": rec.get("steps"),
                    }
                )
                + "\n"
            )
            written += 1
    return written


def bench_serve_cold_start() -> dict:
    """The benchmarks/serve_cold_start.py A/B as a bench workload: one
    compile-path server boot on an empty AOT executable cache, then N cached
    boots that deserialize the batch ladder. Stdlib-only here — every timed
    boot is its own subprocess (the grandchildren import jax), so this child
    stays as jax-free as the parent."""
    import benchmarks.serve_cold_start as coldstart

    return coldstart.measure(
        repeats=int(os.environ.get("SHEEPRL_TPU_COLDSTART_REPEATS", "3")),
        depth=int(os.environ.get("SHEEPRL_TPU_COLDSTART_DEPTH", "384")),
        width=int(os.environ.get("SHEEPRL_TPU_COLDSTART_WIDTH", "64")),
        rungs=tuple(
            int(r)
            for r in os.environ.get("SHEEPRL_TPU_COLDSTART_RUNGS", "1,2,4,8,16,32,64,128").split(",")
            if r
        ),
    )


# ---------------------------------------------------------------- queue ----

_QUEUE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks", "QUEUE.json")


def load_queue() -> list:
    """Entries of ``benchmarks/QUEUE.json``: workloads that need a given
    backend (``requires``, default ``tpu``) and are skipped on any other.
    Standing workloads: draining one records its evidence (its own
    ``--record`` flag appends RUNS.jsonl cells) and keeps the entry."""
    try:
        with open(_QUEUE_PATH) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return []
    entries = doc.get("entries") if isinstance(doc, dict) else None
    return [e for e in entries or [] if isinstance(e, dict) and e.get("argv")]


def probe_backend() -> str:
    """``jax.devices()[0].platform`` probed in a subprocess that has exited
    (and so released the chip) before any workload starts; this parent stays
    jax-free. ``unreachable`` when the probe fails or times out."""
    import subprocess

    timeout = float(os.environ.get("SHEEPRL_TPU_BENCH_PROBE_TIMEOUT", "180"))
    try:
        proc = subprocess.run(
            [sys.executable, "-c", "import jax; print(jax.devices()[0].platform)"],
            capture_output=True,
            text=True,
            timeout=timeout,
        )
    except subprocess.TimeoutExpired:
        return "unreachable"
    if proc.returncode != 0:
        return "unreachable"
    return (proc.stdout or "").strip() or "unreachable"


def drain_queue(budget_fn=None, backend: str | None = None) -> list:
    """Run every backend-eligible queue entry within the remaining budget.

    Each entry runs as a subprocess from the repo root so its own
    ``--record`` flags land in ``./RUNS.jsonl`` where ``--regress`` gates
    them, one child at a time (one process per chip). Returns one
    ``{id, outcome, ...}`` dict per entry."""
    import subprocess

    entries = load_queue()
    if not entries:
        return []
    if backend is None:
        backend = probe_backend()
    results = []
    for entry in entries:
        requires = entry.get("requires", "tpu")
        res = {"id": entry.get("id") or entry["argv"][0], "requires": requires}
        if requires != backend:
            res["outcome"] = f"skipped (backend={backend})"
            results.append(res)
            continue
        cap = float(entry.get("timeout_s", 1800))
        if budget_fn is not None:
            cap = budget_fn(cap)
        if cap < 60.0:
            res["outcome"] = "skipped (budget exhausted)"
            results.append(res)
            continue
        t0 = time.time()
        try:
            proc = subprocess.run(
                [sys.executable] + list(entry["argv"]),
                timeout=cap,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
            res["outcome"] = "completed" if proc.returncode == 0 else f"failed rc={proc.returncode}"
        except subprocess.TimeoutExpired:
            res["outcome"] = f"timeout after {cap:.0f}s"
        res["wall_s"] = round(time.time() - t0, 1)
        results.append(res)
    return results


# ------------------------------------------------------- child dispatch ----

_WORKLOADS = {
    "dv3": bench_dv3,
    "ppo": bench_ppo,
    "ppo_fused": bench_ppo_fused,
    "ppo_actor_learner": bench_ppo_actor_learner,
    "ppo_floor": bench_ppo_floor,
    "serve_cold_start": bench_serve_cold_start,
}


def _run_child(workload: str, out_path: str) -> None:
    rec = _WORKLOADS[workload]()
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(rec, f)
    os.replace(tmp, out_path)


def _spawn_workload(workload: str, timeout_s: float) -> dict | None:
    """Run one workload in a subprocess; return its JSON record or None on
    any failure (non-zero exit, timeout, unreadable output). Stdout/stderr
    pass through so the driver tail stays informative."""
    import subprocess
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        out_path = os.path.join(d, "out.json")
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--workload", workload, "--out", out_path],
                timeout=timeout_s,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
        except subprocess.TimeoutExpired:
            print(f"# workload {workload!r} timed out after {timeout_s:.0f}s", file=sys.stderr)
            return None
        if proc.returncode != 0:
            print(f"# workload {workload!r} failed rc={proc.returncode}", file=sys.stderr)
            return None
        try:
            with open(out_path) as f:
                return json.load(f)
        except (OSError, ValueError) as e:
            print(f"# workload {workload!r} wrote no readable record: {e}", file=sys.stderr)
            return None


# ---------------------------------------------------------------- parent ----


def _assemble(dv3: dict, ppo: dict) -> dict:
    """Build the one-line record from the two workload results."""
    dv3_sps = dv3["steps"] / dv3["seconds"]
    record = {
        "metric": "dreamer_v3_env_steps_per_sec_per_chip",
        "value": round(dv3_sps, 2),
        "unit": "steps/sec",
        "vs_baseline": round(dv3_sps / _DV3_TORCH_CPU_SPS, 3),
    }
    for k in ("train_flops_per_sec", "flops_per_train_step", "mfu", "mfu_peak_flops_assumed"):
        if k in dv3:
            record[k] = dv3[k]
    ppo_sps = ppo["steps"] / ppo["seconds"]
    record["secondary"] = {
        "metric": "ppo_cartpole_env_steps_per_sec",
        "value": round(ppo_sps, 2),
        "unit": "steps/sec",
        "vs_baseline": round(ppo_sps / _PPO_TORCH_CPU_SPS, 3),
    }
    return record


def main() -> None:
    backend = probe_backend()
    if backend != "tpu":
        sys.exit(f"bench.py: these are device numbers and the platform found is {backend!r}, not 'tpu'; no number is printed")
    record = {"platform": backend}
    deadline_min = float(os.environ.get("SHEEPRL_TPU_BENCH_DEADLINE_MINUTES", "50"))
    deadline = time.time() + deadline_min * 60.0

    def budget(cap: float) -> float:
        return max(1.0, min(cap, deadline - time.time()))

    results = {}
    for workload, cap in (("dv3", 1800), ("ppo", 1500)):
        results[workload] = _spawn_workload(workload, budget(cap))
        if results[workload] is None:
            sys.exit(f"bench.py: workload {workload!r} failed or timed out; no number is printed")

    # drain benchmarks/QUEUE.json in whatever budget the two workloads left.
    # Each entry records its own evidence (RUNS.jsonl cells via --record,
    # stdout above the record); its outcome never touches the record below.
    for qr in drain_queue(budget_fn=budget, backend=backend):
        print(f"# queue {qr['id']}: {qr['outcome']}", file=sys.stderr, flush=True)

    record.update(_assemble(results["dv3"], results["ppo"]))
    print(json.dumps(record))


if __name__ == "__main__":
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", choices=sorted(_WORKLOADS))
    parser.add_argument("--out")
    parser.add_argument(
        "--telemetry",
        metavar="PATH",
        help="summarize a run's telemetry.jsonl (SPS/MFU/spans/compiles) and exit",
    )
    parser.add_argument(
        "--dispatch-stats",
        metavar="PATH",
        help="report per-train-window device dispatch counts from a run's "
        "telemetry.jsonl (fused supersteps should show ceil(G/K) per window) and exit",
    )
    parser.add_argument(
        "--env-stats",
        metavar="PATH",
        help="report rollout-pool health from a run's telemetry.jsonl "
        "(env step latency percentiles, worker restarts, masked slots) and exit",
    )
    parser.add_argument(
        "--resilience-stats",
        metavar="PATH",
        help="report checkpoint/rollback health from a run's telemetry.jsonl "
        "(ckpt snapshot/write span percentiles, skipped saves, NaN rollbacks, "
        "preemptions, auto-resume decisions) and exit",
    )
    parser.add_argument(
        "--compile-stats",
        metavar="PATH",
        help="report the compile economy from a run's telemetry.jsonl "
        "(lowered variants, deliberate-by-reason, post-warm recompiles, "
        "trace-cache hit/miss, AOT executable-cache hit/miss/store/GC by "
        "tag — a hit is a whole compile that never ran) and exit",
    )
    parser.add_argument(
        "--serve-stats",
        metavar="PATH",
        help="report policy-serving health from a serve session's telemetry.jsonl "
        "(QPS, p50/p95 vs SLO, queue depth, shed counts, replica restarts/masks, "
        "swap promotions/rejections, load-generator report) and exit; also accepts "
        "a RUNS.jsonl registry and then aggregates every serve record (per-run "
        "rows, per-replica rows, fleet rollup)",
    )
    parser.add_argument(
        "--net-stats",
        metavar="PATH",
        help="report multi-host data-plane health from a run's telemetry.jsonl "
        "(per-transport frames/bytes/reconnects/checksum-rejects/heartbeat-gaps "
        "from the run_end net section, the net_event log, and cross-host "
        "handshake clock skews) and exit",
    )
    parser.add_argument(
        "--trace",
        metavar="PATH",
        nargs="+",
        help="merge per-process trace/telemetry streams (tools/trace.py) and "
        "print the critical-path attribution: per-slab lag decomposition "
        "(collect -> ring-wait -> train, slab-age p50/p95) and per-request "
        "latency decomposition (queue-wait -> assembly -> compute, hedge "
        "dedup) — pass the run's telemetry_files set from RUNS.jsonl",
    )
    parser.add_argument(
        "--regress",
        action="store_true",
        help="regression gate: compare the newest run-registry record per "
        "scenario cell against its tolerance-banded history, write the "
        "verdict grid to SCENARIOS.json, exit nonzero on regression "
        "(tools/regress.py)",
    )
    parser.add_argument("--runs", default="RUNS.jsonl", help="run-registry path for --regress")
    parser.add_argument("--scenarios-out", default="SCENARIOS.json", help="verdict-grid path for --regress")
    parser.add_argument(
        "--bench-glob", default="BENCH_r*.json", help="driver bench records folded into --regress ('' disables)"
    )
    parser.add_argument(
        "--floor",
        action="store_true",
        help="run the benchmarks/ppo_floor.py stage ladder (bare env / noop "
        "policy / jitted player / player+bookkeeping) in a subprocess, fold "
        "each stage into the run registry (kind=floor, variant=stage) for "
        "--regress gating, print the stage JSON",
    )
    parser.add_argument(
        "--cold-start",
        action="store_true",
        help="run the benchmarks/serve_cold_start.py replica cold-start A/B "
        "(compile-path boot on an empty AOT executable cache, then cached "
        "boots that deserialize the batch ladder) in a subprocess, fold each "
        "cached boot into the run registry (kind=serve, variant=cold_start, "
        "metric cold_start_s lower-better) for --regress gating, print the "
        "A/B JSON",
    )
    parser.add_argument(
        "--queue",
        choices=("list", "drain"),
        help="workloads that need a given backend (benchmarks/QUEUE.json): "
        "'list' prints entries with eligibility against the probed "
        "backend, 'drain' runs every eligible entry now, one child at a time",
    )
    parser.add_argument(
        "--drills",
        action="store_true",
        help="chaos-drill registry (tools/drills.py): every registered fault "
        "kind cross-referenced against the tests that drill it, with pytest "
        "markers and last cached verdicts; exit nonzero if any registered "
        "fault kind has no drill",
    )
    parser.add_argument(
        "--drills-json",
        action="store_true",
        help="with --drills: print the full registry JSON instead of the summary",
    )
    parser.add_argument(
        "--static",
        action="store_true",
        help="static gate: run the jaxcheck rule scan + config-matrix "
        "validation (tools/jaxcheck) in a subprocess, print a one-line "
        "summary, exit nonzero on any new finding or failed config cell",
    )
    parser.add_argument(
        "--sweep",
        action="store_true",
        help="executed scenario grid (tools/sweep.py): drain the curated "
        "scenario cells through fake-backend smoke -> CPU learning-check "
        "tiers (each cell is a subprocess CLI run), fold executed verdicts "
        "into SCENARIOS.json as executed_cells/executed_summary, defer "
        "chip-tier cells into benchmarks/QUEUE.json; exit nonzero on any "
        "failed cell",
    )
    parser.add_argument(
        "--sweep-only", metavar="GLOB", help="cell-key filter for --sweep (fnmatch)"
    )
    parser.add_argument(
        "--sweep-budget-s",
        type=float,
        default=0.0,
        help="wall-clock budget for --sweep; cells past it report skipped_budget (0 = unlimited)",
    )
    parser.add_argument(
        "--sweep-stats",
        action="store_true",
        help="summarize executed scenario cells (tier reached, verdict, sps) "
        "from SCENARIOS.json and exit (tools/sweep.py stats)",
    )
    args = parser.parse_args()
    if args.sweep or args.sweep_stats:
        # the runner is stdlib-only (every cell runs as a subprocess), so the
        # parent stays jax-free — same file-path load as --regress
        sweep_mod = _load_tool("sweep")
        if args.sweep_stats:
            print(json.dumps(sweep_mod.stats(args.scenarios_out), indent=1))
            sys.exit(0)
        sweep_argv = ["--scenarios-out", args.scenarios_out]
        if args.sweep_only:
            sweep_argv += ["--only", args.sweep_only]
        if args.sweep_budget_s:
            sweep_argv += ["--budget-s", str(args.sweep_budget_s)]
        sys.exit(sweep_mod.main(sweep_argv))
    if args.queue:
        backend = probe_backend()
        if args.queue == "list":
            for entry in load_queue():
                print(
                    json.dumps(
                        {
                            "id": entry.get("id") or entry["argv"][0],
                            "requires": entry.get("requires", "tpu"),
                            "eligible": entry.get("requires", "tpu") == backend,
                            "argv": entry["argv"],
                            "note": entry.get("note"),
                        }
                    )
                )
            print(f"# probed backend: {backend}", file=sys.stderr)
            sys.exit(0)
        results = drain_queue(backend=backend)
        print(json.dumps(results, indent=1))
        ran = [r for r in results if not r["outcome"].startswith("skipped")]
        sys.exit(0 if all(r["outcome"] == "completed" for r in ran) else 1)
    if args.drills:
        # the scanner imports the fault-domain modules (registration happens
        # at import), so it runs in a child and this parent stays jax-free
        import subprocess

        proc = subprocess.run(
            [sys.executable, "-m", "tools.drills", "--json"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True,
            text=True,
            timeout=600,
        )
        try:
            registry = json.loads(proc.stdout)
        except ValueError:
            sys.stderr.write(proc.stdout + proc.stderr)
            sys.exit(proc.returncode or 2)
        if args.drills_json:
            print(json.dumps(registry, indent=1))
        else:
            totals = registry["totals"]
            print(
                f"drills: {totals['drills']} tests exercise "
                f"{totals['kinds_covered']}/{totals['kinds']} registered fault kinds"
            )
            for drill in registry["drills"]:
                marks = ",".join(drill["markers"]) or "-"
                kinds = ",".join(drill["fault_kinds"])
                print(f"  [{drill['verdict']:>7}] {drill['nodeid']} marks={marks} faults={kinds}")
            for domain, kinds in sorted(registry.get("uncovered", {}).items()):
                print(f"  UNDRILLED {domain}: {', '.join(kinds)}")
        sys.exit(0 if not registry.get("uncovered") else 1)
    if args.static:
        # jaxcheck imports the config plane with algo imports gated off, so
        # the child never loads jax; a subprocess keeps this parent identical
        # to the --regress path (jax-free, timeout-safe)
        import subprocess

        env = dict(os.environ, SHEEPRL_TPU_SKIP_ALGO_IMPORTS="1")
        proc = subprocess.run(
            [sys.executable, "-m", "tools.jaxcheck", "--json", "--scenarios", args.scenarios_out],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            env=env,
            capture_output=True,
            text=True,
            timeout=600,
        )
        try:
            report = json.loads(proc.stdout)
        except ValueError:
            sys.stderr.write(proc.stdout + proc.stderr)
            sys.exit(proc.returncode or 2)
        by_rule = ", ".join(f"{k}:{v}" for k, v in report["counts_by_rule"].items()) or "none"
        by_family = ", ".join(
            f"{k}:{v}" for k, v in (report.get("counts_by_family") or {}).items()
        ) or "none"
        cfg = report.get("config") or {}
        print(
            f"static: {report['findings_total']} findings ({by_rule}), "
            f"families ({by_family}), "
            f"{report['baseline_suppressed']} baseline-suppressed, {len(report['new'])} new; "
            f"config cells {cfg.get('pass', 0)}/{cfg.get('cells', 0)} pass "
            f"({cfg.get('fail', 0)} fail, {cfg.get('warnings', 0)} warnings)"
        )
        for line in report["new"]:
            print(f"  NEW {line}")
        sys.exit(proc.returncode)
    if args.floor:
        # the stages run in a child (they import jax); the parent stays
        # jax-free and does the stdlib-only registry fold
        rec = _spawn_workload("ppo_floor", 1200)
        if rec is None:
            sys.exit(1)
        written = append_floor_runs(rec, args.runs)
        print(json.dumps({**rec, "registry_records": written, "runs_path": args.runs}))
        sys.exit(0)
    if args.cold_start:
        # each timed boot is its own grandchild process; the fold is the
        # stdlib-only append_runs from the benchmark module itself
        import benchmarks.serve_cold_start as coldstart

        rec = _spawn_workload("serve_cold_start", 3600)
        if rec is None:
            sys.exit(1)
        written = coldstart.append_runs(rec, args.runs)
        print(json.dumps({**rec, "registry_records": written, "runs_path": args.runs}))
        sys.exit(0)
    if args.regress:
        # the gate is stdlib-only; load it by file path so this parent
        # process stays jax-free (same reason main() shells out workloads)
        import importlib.util

        regress_path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools", "regress.py")
        spec = importlib.util.spec_from_file_location("_sheeprl_tpu_regress", regress_path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.exit(
            mod.run_gate(
                args.runs,
                args.scenarios_out,
                bench_pattern=args.bench_glob or None,
            )
        )
    elif args.compile_stats:
        print(json.dumps(compile_stats(args.compile_stats), indent=1))
    elif args.serve_stats:
        print(json.dumps(serve_stats(args.serve_stats), indent=1))
    elif args.resilience_stats:
        print(json.dumps(resilience_stats(args.resilience_stats), indent=1))
    elif args.env_stats:
        print(json.dumps(env_stats_summary(args.env_stats), indent=1))
    elif args.net_stats:
        print(json.dumps(net_stats_report(args.net_stats), indent=1))
    elif args.dispatch_stats:
        print(json.dumps(dispatch_stats(args.dispatch_stats)))
    elif args.trace:
        print(json.dumps(trace_summary(args.trace), indent=1))
    elif args.telemetry:
        print(json.dumps(telemetry_summary(args.telemetry)))
    elif args.workload:
        if not args.out:
            parser.error("--workload requires --out")
        _run_child(args.workload, args.out)
    else:
        main()
