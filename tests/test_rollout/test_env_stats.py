"""Rollout telemetry plumbing end to end, without worker processes:
RunTelemetry's env-step reservoir / restart / mask counters → the JSONL
stream → the ``tools.report --env-stats`` reader."""

import json

import numpy as np
import pytest

from sheeprl_tpu.obs import configure_telemetry, shutdown_telemetry, span
from sheeprl_tpu.rollout import EnvPool, PoolConfig
from tools import report


@pytest.fixture()
def telemetry(tmp_path):
    saved_timers, saved_disabled = dict(span.timers), span.disabled
    span.timers, span.disabled = {}, False
    cfg = {"metric": {"telemetry": {"enabled": True, "poll_interval": 0.0}}}
    tel = configure_telemetry(cfg, log_dir=str(tmp_path))
    assert tel is not None
    yield tel
    shutdown_telemetry()
    span.timers, span.disabled = saved_timers, saved_disabled


def _events(tel):
    tel.writer.flush()
    with open(tel.writer.path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _heartbeat(tel):
    tel.heartbeat(
        None, step=1, env_steps=10, train_steps=0, train_invocations=None, timer_window={}
    )


def test_env_step_latency_lands_in_heartbeat(telemetry):
    for dur in (0.010, 0.020, 0.100):
        telemetry.record_env_step(dur, queue_wait_s=dur / 2)
    _heartbeat(telemetry)
    (hb,) = [e for e in _events(telemetry) if e["event"] == "heartbeat"]
    assert hb["env_step_samples"] == 3
    assert hb["env_step_p50_ms"] == pytest.approx(20.0, rel=0.01)
    assert hb["env_step_p95_ms"] == pytest.approx(92.0, rel=0.01)
    assert hb["env_queue_wait_p50_ms"] == pytest.approx(10.0, rel=0.01)
    # the reservoir is per-window: a second heartbeat reports no env fields
    _heartbeat(telemetry)
    hb2 = [e for e in _events(telemetry) if e["event"] == "heartbeat"][-1]
    assert "env_step_p50_ms" not in hb2


def test_restart_and_mask_events_and_run_end_totals(telemetry):
    telemetry.record_worker_restart(worker=1, reason="timeout", restarts=1)
    telemetry.record_worker_restart(worker=1, reason="crash", restarts=2)
    telemetry.record_masked_slot(worker=1, slots=[2, 3], reason="crash")
    _heartbeat(telemetry)
    events = _events(telemetry)
    restarts = [e for e in events if e["event"] == "worker_restart"]
    assert [e["reason"] for e in restarts] == ["timeout", "crash"]
    (mask,) = [e for e in events if e["event"] == "masked_slot"]
    assert mask["slots"] == [2, 3]
    (hb,) = [e for e in events if e["event"] == "heartbeat"]
    assert hb["window_worker_restarts"] == 2
    assert hb["worker_restarts_total"] == 2
    assert hb["masked_slots_total"] == 2

    path = telemetry.writer.path
    shutdown_telemetry()
    events = report.read_telemetry(path)
    (end,) = [e for e in events if e["event"] == "run_end"]
    assert end["worker_restarts"] == 2
    assert end["masked_slots"] == 2


def test_bench_env_stats_summary(telemetry):
    telemetry.emit_span("rollout/env_reset", None, 0.050, {"busy_s": 0.045, "queue_wait_s": 0.005})
    for dur in (0.010, 0.012, 0.300):
        telemetry.emit_span("rollout/env_step", None, dur, {"busy_s": dur * 0.9, "queue_wait_s": dur * 0.1})
        telemetry.record_env_step(dur, queue_wait_s=dur * 0.1)
    telemetry.record_worker_restart(worker=0, reason="crash during step", restarts=1)
    telemetry.record_masked_slot(worker=0, slots=[0, 1], reason="crash")
    path = telemetry.writer.path
    shutdown_telemetry()

    stats = report.env_stats_summary(path)
    assert stats["env_step"]["count"] == 3
    assert stats["env_step"]["p50_ms"] == pytest.approx(12.0, rel=0.01)
    assert stats["env_step"]["max_ms"] == pytest.approx(300.0, rel=0.01)
    assert stats["env_step"]["queue_wait_p50_ms"] == pytest.approx(1.2, rel=0.01)
    assert stats["env_reset"]["count"] == 1
    assert stats["worker_restarts"] == [
        {"worker": 0, "reason": "crash during step", "restarts": 1, "step": 0}
    ]
    assert stats["masked_slots"][0]["slots"] == [0, 1]
    # totals prefer run_end (emitted by the shutdown above)
    assert stats["totals"] == {"worker_restarts": 1, "masked_slots": 2}


def test_bench_env_stats_empty_stream(tmp_path):
    path = str(tmp_path / "telemetry.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"event": "heartbeat", "t": 0.0}) + "\n")
    stats = report.env_stats_summary(path)
    assert "env_step" not in stats
    assert stats["totals"] == {"worker_restarts": 0, "masked_slots": 0}


def test_bench_percentile_matches_numpy():
    vals = sorted([0.3, 1.0, 2.5, 9.0, 4.2, 0.01])
    for q in (50, 95, 99):
        assert report._percentile(vals, q) == pytest.approx(float(np.percentile(vals, q)))


def test_pool_step_emits_spans_and_latency(telemetry, tmp_path):
    """One real pool under live telemetry: step/reset spans land in the
    stream and tools.report --env-stats can read the run."""
    from sheeprl_tpu.envs.toy import PixelCatcher

    def thunk():
        return PixelCatcher(seed=3, size=16, paddle_width=4)

    envs = EnvPool([thunk, thunk], config=PoolConfig(num_workers=1))
    try:
        envs.reset(seed=5)
        for _ in range(3):
            envs.step(np.zeros(2, dtype=np.int64))
    finally:
        envs.close()
    events = _events(telemetry)
    step_spans = [e for e in events if e["event"] == "span" and e["name"] == "rollout/env_step"]
    reset_spans = [e for e in events if e["event"] == "span" and e["name"] == "rollout/env_reset"]
    assert len(step_spans) == 3 and len(reset_spans) == 1
    for e in step_spans:
        assert e["attrs"]["queue_wait_s"] >= 0.0
        assert e["dur"] >= e["attrs"]["busy_s"]
    stats = report.env_stats_summary(events)
    assert stats["env_step"]["count"] == 3
    assert stats["totals"] == {"worker_restarts": 0, "masked_slots": 0}
