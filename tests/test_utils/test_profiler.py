"""XLA profiler hook + run-telemetry (sheeprl_tpu.obs) tests."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.obs import configure_telemetry, get_telemetry, shutdown_telemetry, span
from sheeprl_tpu.obs.recompile import RecompileWarning
from sheeprl_tpu.utils.profiler import maybe_profile


def test_disabled_is_noop():
    with maybe_profile({"metric": {}}) as trace_dir:
        assert trace_dir is None
    with maybe_profile({}) as trace_dir:
        assert trace_dir is None


def test_enabled_writes_trace(tmp_path):
    cfg = {"metric": {"profiler": {"enabled": True, "trace_dir": str(tmp_path / "prof")}}}
    with maybe_profile(cfg) as trace_dir:
        assert trace_dir == str(tmp_path / "prof")
        jax.block_until_ready(jax.jit(lambda x: x * 2)(jnp.ones((8, 8))))
    found = []
    for root, _, files in os.walk(trace_dir):
        found += files
    assert found, "profiler trace produced no files"


def test_default_dir_from_log_dir(tmp_path):
    cfg = {"metric": {"profiler": {"enabled": True}}}
    with maybe_profile(cfg, log_dir=str(tmp_path)) as trace_dir:
        assert trace_dir == os.path.join(str(tmp_path), "profile")
        jax.block_until_ready(jnp.ones(4) + 1)


# ------------------------------------------------- run telemetry (obs/) ----


@pytest.fixture()
def telemetry(tmp_path):
    """Fresh RunTelemetry with fast polling; restores the span registry and
    guarantees shutdown so no listener leaks into later tests."""
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


def test_telemetry_disabled_is_inert(tmp_path):
    assert configure_telemetry({"metric": {"telemetry": {"enabled": False}}}, str(tmp_path)) is None
    assert configure_telemetry({"metric": {}}, str(tmp_path)) is None
    assert get_telemetry() is None
    assert not os.path.exists(os.path.join(str(tmp_path), "telemetry.jsonl"))


def test_span_emits_jsonl_event_with_schema(telemetry):
    telemetry.advance(7)
    with span("Time/test_section", kind="unit"):
        jax.block_until_ready(jnp.ones(4) + 1)
    events = _events(telemetry)
    spans = [e for e in events if e["event"] == "span"]
    assert len(spans) == 1
    (ev,) = spans
    assert ev["name"] == "Time/test_section"
    assert ev["step"] == 7
    assert ev["process_index"] == jax.process_index()
    assert ev["attrs"] == {"kind": "unit"}
    assert ev["dur"] > 0 and ev["t_start"] <= ev["t"]
    # the SAME name feeds the timer metric registry — spans and Time/*
    # scalars agree by construction
    assert "Time/test_section" in span.timers
    assert abs(span.compute()["Time/test_section"] - ev["dur"]) < 0.5


def test_span_without_telemetry_is_the_old_timer(tmp_path):
    saved_timers, saved_disabled = dict(span.timers), span.disabled
    span.timers, span.disabled = {}, False
    try:
        assert get_telemetry() is None
        with span("Time/plain"):
            pass
        assert span.compute()["Time/plain"] >= 0
    finally:
        span.timers, span.disabled = saved_timers, saved_disabled


def test_recompile_watchdog_counts_deliberate_retraces(telemetry):
    x = jnp.ones((3,))
    jax.block_until_ready(jax.jit(lambda v: v * 3 + 1)(x))  # pre-warm compile
    pre = telemetry.watchdog.compiles
    assert pre >= 1
    assert telemetry.watchdog.recompiles == 0
    telemetry.mark_warm()
    with pytest.warns(RecompileWarning):
        for _ in range(2):
            # a FRESH lambda per iteration defeats the jit cache: each call
            # re-traces and re-lowers, which is exactly a silent recompile
            jax.block_until_ready(jax.jit(lambda v: v * 3 + 1)(x))
    assert telemetry.watchdog.recompiles >= 2
    post_warm = [
        e
        for e in _events(telemetry)
        if e["event"] == "compile" and e["phase"] == "lower" and e["post_warm"]
    ]
    assert len(post_warm) >= 2
    assert all("dur" in e for e in post_warm)
    # each post-warm retrace also emits a dedicated `recompile` event naming
    # the offending function, for cross-referencing against jaxcheck's
    # static JX05 findings
    recompile_events = [e for e in _events(telemetry) if e["event"] == "recompile"]
    assert len(recompile_events) >= 2
    assert all(e["qualname"] for e in recompile_events)
    assert recompile_events[-1]["count"] == telemetry.watchdog.recompiles


class _FakeLogger:
    def __init__(self):
        self.logged = []

    def log_metrics(self, metrics, step):
        self.logged.append((dict(metrics), step))


def test_heartbeat_assembly_on_fake_logger(telemetry):
    telemetry.set_flops_source(lambda: 2.0e9)
    logger = _FakeLogger()
    telemetry.heartbeat(
        logger,
        step=1000,
        env_steps=200,
        train_steps=600,
        train_invocations=10,
        timer_window={"Time/env_interaction_time": 2.0, "Time/train_time": 6.0},
    )
    (hb,) = [e for e in _events(telemetry) if e["event"] == "heartbeat"]
    assert hb["sps_env"] == pytest.approx(100.0)
    assert hb["sps_train"] == pytest.approx(100.0)
    assert hb["duty_cycle_train"] == pytest.approx(0.75)
    assert hb["flops_per_train_step"] == pytest.approx(2.0e9)
    assert hb["train_flops_per_sec"] == pytest.approx(2.0e9 * 10 / 6.0)
    assert hb["recompiles"] == telemetry.watchdog.recompiles
    assert hb["device_kind"]
    scalars, step = logger.logged[-1]
    assert step == 1000
    assert scalars["Counters/recompiles"] == float(telemetry.watchdog.recompiles)
    assert scalars["Telemetry/duty_cycle_train"] == pytest.approx(0.75)
    assert scalars["Telemetry/train_flops_per_sec"] == pytest.approx(2.0e9 * 10 / 6.0)


def test_device_poll_rides_advance(telemetry):
    telemetry.advance(5)
    telemetry.advance(9)
    polls = [e for e in _events(telemetry) if e["event"] == "device_poll"]
    # one forced poll at start + one per advance (poll_interval=0)
    assert len(polls) >= 3
    assert polls[-1]["step"] == 9
    for entry in polls[-1]["devices"]:
        assert {"id", "kind", "platform"} <= set(entry)
    assert len(polls[-1]["devices"]) == jax.local_device_count()


def test_run_lifecycle_events(telemetry):
    shutdown_telemetry()
    with open(telemetry.writer.path) as f:
        events = [json.loads(line) for line in f if line.strip()]
    assert events[0]["event"] == "run_start"
    assert events[0]["backend"] == "cpu"
    assert events[-1]["event"] == "run_end"
    assert "compiles_total" in events[-1] and "device_polls" in events[-1]
    assert get_telemetry() is None


def test_watchdog_counts_compile_cache_events(telemetry):
    """Persistent-compilation-cache outcomes arrive as plain jax.monitoring
    events; the watchdog counts them and mirrors each as a compile_cache
    telemetry event (persistent compilation cache observability)."""
    pre_hits, pre_misses = telemetry.watchdog.cache_hits, telemetry.watchdog.cache_misses
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    jax.monitoring.record_event("/jax/unrelated_event")  # ignored
    assert telemetry.watchdog.cache_hits == pre_hits + 1
    assert telemetry.watchdog.cache_misses == pre_misses + 2
    cache_events = [e for e in _events(telemetry) if e["event"] == "compile_cache"]
    assert [e["hit"] for e in cache_events[-3:]] == [True, False, False]


def test_watchdog_stop_unregisters_cache_listener():
    from sheeprl_tpu.obs.recompile import CompileWatchdog

    wd = CompileWatchdog(lambda name, **kw: None)
    wd.start()
    try:
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        assert wd.cache_hits == 1
    finally:
        wd.stop()
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    assert wd.cache_hits == 1, "stop() left the plain-event listener registered"


def test_train_window_counters_roll_into_heartbeat(telemetry):
    from sheeprl_tpu.obs import telemetry_train_window

    telemetry_train_window(1, 4)
    telemetry_train_window(2, 6)
    logger = _FakeLogger()
    telemetry.heartbeat(
        logger,
        step=10,
        env_steps=4,
        train_steps=10,
        train_invocations=2,
        timer_window={"Time/train_time": 1.0},
    )
    hb = [e for e in _events(telemetry) if e["event"] == "heartbeat"][-1]
    assert hb["window_train_windows"] == 2
    assert hb["window_train_dispatches"] == 3
    assert hb["window_train_gradient_steps"] == 10
    scalars, _ = logger.logged[-1]
    assert scalars["Telemetry/train_dispatches_per_window"] == pytest.approx(1.5)
    # the window counters reset; the run totals land in run_end (see the
    # distributed run_end assertions and bench.dispatch_stats)
    logger2 = _FakeLogger()
    telemetry.heartbeat(
        logger2,
        step=11,
        env_steps=4,
        train_steps=0,
        train_invocations=0,
        timer_window={},
    )
    hb2 = [e for e in _events(telemetry) if e["event"] == "heartbeat"][-1]
    assert "window_train_windows" not in hb2


def test_device_peaks_come_from_the_table_and_unknown_kinds_raise():
    from sheeprl_tpu.utils.profiler import PEAK_BF16_FLOPS, device_peaks

    assert device_peaks("TPU v5 lite") == {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert PEAK_BF16_FLOPS["TPU v5e"] == 197e12
    with pytest.raises(ValueError, match="no published peaks recorded for device kind 'cpu'"):
        device_peaks("cpu")


def test_watchdog_emits_one_warm_event():
    """The stream says when the warm point was reached, so "no recompile" can
    be told from "never warm"."""
    from sheeprl_tpu.obs.recompile import CompileWatchdog

    events = []
    dog = CompileWatchdog(lambda kind, **f: events.append((kind, f)))
    dog.compiles = 7
    dog.mark_warm()
    dog.mark_warm()
    assert events == [("warm", {"compiles": 7})] and dog.warm
