"""``span``: nested leaf spans, the monotonic stamp a device trace is aligned
to, and what a span costs with telemetry off (the old timer, nothing more)."""

import json
import time

import pytest

from sheeprl_tpu.obs import configure_telemetry, get_telemetry, shutdown_telemetry, span

WINDOW = ("Time/env_interaction_time", "Time/train_time")
LEAVES = ("player/get_actions", "ring/add", "env/step", "loop/store_step", "replay/draw", "train/dispatch", "train/block")


@pytest.fixture()
def registry():
    saved_timers, saved_disabled = dict(span.timers), span.disabled
    span.timers, span.disabled = {}, False
    yield
    shutdown_telemetry()
    span.timers, span.disabled = saved_timers, saved_disabled


def _a_loop_turn():
    """The nesting the Dreamer-V3 loop has: leaf spans inside the two window spans."""
    with span(WINDOW[0]):
        for leaf in LEAVES[:3]:
            with span(leaf):
                time.sleep(0.001)
    with span(LEAVES[3]):
        with span(LEAVES[1]):  # the reset add nests one level deeper
            time.sleep(0.001)
    with span(WINDOW[1]):
        for leaf in LEAVES[4:]:
            with span(leaf):
                time.sleep(0.001)


def test_a_nested_leaf_span_is_stamped_on_the_monotonic_clock(registry, tmp_path):
    tel = configure_telemetry({"metric": {"telemetry": {"enabled": True, "poll_interval": 0.0}}}, log_dir=str(tmp_path))
    before = time.monotonic_ns()
    _a_loop_turn()
    after = time.monotonic_ns()
    tel.writer.flush()
    with open(tel.writer.path) as f:
        spans = [e for e in map(json.loads, f) if e["event"] == "span"]
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e)
    assert set(by_name) == set(WINDOW) | set(LEAVES) and len(by_name["ring/add"]) == 2
    for e in spans:
        assert isinstance(e["t_mono_ns"], int) and before <= e["t_mono_ns"] <= after
        assert before <= e["t_mono_ns"] + int(e["dur"] * 1e9) <= after + 1_000_000
        assert e["t_start"] <= e["t"]  # the wall-clock stamp stays beside it
    # a leaf lies inside its window span on that clock, and spans close inner first
    outer = by_name[WINDOW[0]][0]
    for leaf in LEAVES[:3]:
        inner = by_name[leaf][0]
        assert outer["t_mono_ns"] <= inner["t_mono_ns"]
        assert inner["t_mono_ns"] + inner["dur"] * 1e9 <= outer["t_mono_ns"] + outer["dur"] * 1e9 + 1e5
    assert [e["name"] for e in spans][:4] == [*LEAVES[:3], WINDOW[0]]


def test_with_telemetry_off_a_span_writes_nothing_and_the_window_timers_accumulate(registry, tmp_path, monkeypatch):
    assert configure_telemetry({"metric": {"telemetry": {"enabled": False}}}, str(tmp_path)) is None
    assert get_telemetry() is None
    reads = []
    real = time.monotonic_ns
    monkeypatch.setattr(time, "monotonic_ns", lambda: reads.append(1) or real())
    t0 = time.perf_counter()
    _a_loop_turn()
    _a_loop_turn()
    wall = time.perf_counter() - t0
    monkeypatch.undo()
    assert not reads, "a span read the monotonic clock with telemetry off"
    assert not list(tmp_path.iterdir()), "a span wrote an event with telemetry off"
    totals = span.compute()
    # the two window timers hold their leaves' time, twice over, as before
    assert 2 * 3 * 0.001 <= totals[WINDOW[0]] <= wall
    assert 2 * 3 * 0.001 <= totals[WINDOW[1]] <= wall
    for leaf in LEAVES:
        assert 0.0 < totals[leaf] <= wall
    assert totals["ring/add"] >= 4 * 0.001 and totals[WINDOW[0]] >= sum(totals[n] for n in ("player/get_actions", "env/step"))
    # disabled timers (log_level 0) register nothing, leaf or window
    span.timers, span.disabled = {}, True
    _a_loop_turn()
    assert span.compute() == {}


def test_emit_span_without_a_monotonic_stamp_leaves_the_field_out(registry, tmp_path):
    tel = configure_telemetry({"metric": {"telemetry": {"enabled": True, "poll_interval": 0.0}}}, log_dir=str(tmp_path))
    tel.emit_span("rollout/env_step", None, 0.01, {"busy_s": 0.009})
    tel.emit_span("x", 1.0, 0.5, {}, t_mono_ns=123)
    tel.writer.flush()
    with open(tel.writer.path) as f:
        spans = [e for e in map(json.loads, f) if e["event"] == "span"]
    assert "t_mono_ns" not in spans[0] and spans[1]["t_mono_ns"] == 123
