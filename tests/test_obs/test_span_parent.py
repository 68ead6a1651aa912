"""``span`` names its parent: the innermost span open on the same thread when
it began, ``None`` at the top and on a thread of its own; with telemetry off
it keeps no stack and writes nothing."""

import json
import sys
import threading

import pytest

from sheeprl_tpu.obs import configure_telemetry, shutdown_telemetry, span

SPAN_MODULE = sys.modules["sheeprl_tpu.obs.span"]  # the package exports the class under the module's name


@pytest.fixture()
def registry():
    saved_timers, saved_disabled = dict(span.timers), span.disabled
    span.timers, span.disabled = {}, False
    yield
    shutdown_telemetry()
    span.timers, span.disabled = saved_timers, saved_disabled


def _events(tel):
    tel.writer.flush()
    with open(tel.writer.path) as f:
        return [e for e in map(json.loads, f) if e["event"] == "span"]


def _open_stack():
    return SPAN_MODULE._open.__dict__.get("stack")


def test_nested_spans_name_their_parent(registry, tmp_path):
    tel = configure_telemetry({"metric": {"telemetry": {"enabled": True, "poll_interval": 0.0}}}, log_dir=str(tmp_path))
    with span("loop/head"):
        pass
    with span("Time/train_time"):
        with span("train/dispatch"):
            with span("update/bootstrap"):
                pass
            with span("update/sequences"):
                pass
        with span("train/block"):
            pass
    with span("loop/tail"):
        pass
    parents = [(e["name"], e["parent"]) for e in _events(tel)]
    assert parents == [("loop/head", None), ("update/bootstrap", "train/dispatch"), ("update/sequences", "train/dispatch"),
                       ("train/dispatch", "Time/train_time"), ("train/block", "Time/train_time"), ("Time/train_time", None),
                       ("loop/tail", None)]  # fmt: skip
    assert _open_stack() == []


def test_a_span_on_another_thread_starts_from_none(registry, tmp_path):
    tel = configure_telemetry({"metric": {"telemetry": {"enabled": True, "poll_interval": 0.0}}}, log_dir=str(tmp_path))

    def work():
        with span("ckpt/write"):
            with span("ckpt/flush"):
                pass

    with span("Time/env_interaction_time"):
        with span("env/step"):
            worker = threading.Thread(target=work)
            worker.start()
            worker.join()
    parents = {e["name"]: e["parent"] for e in _events(tel)}
    assert parents == {"ckpt/flush": "ckpt/write", "ckpt/write": None, "env/step": "Time/env_interaction_time",
                       "Time/env_interaction_time": None}  # fmt: skip


def test_a_span_that_raises_leaves_the_stack_as_it_found_it(registry, tmp_path):
    tel = configure_telemetry({"metric": {"telemetry": {"enabled": True, "poll_interval": 0.0}}}, log_dir=str(tmp_path))
    with span("loop/tail"):
        with pytest.raises(ValueError):
            with span("train/dispatch"):
                raise ValueError("the dispatch failed")
        with span("train/block"):
            pass
    assert [(e["name"], e["parent"]) for e in _events(tel)] == [("train/dispatch", "loop/tail"), ("train/block", "loop/tail"), ("loop/tail", None)]
    assert _open_stack() == []


def test_with_telemetry_off_no_stack_is_kept_and_no_event_written(registry, tmp_path):
    assert configure_telemetry({"metric": {"telemetry": {"enabled": False}}}, str(tmp_path)) is None
    seen = {}

    def work():  # a fresh thread: whatever stack it has, these spans made
        with span("loop/head"):
            with span("player/to_env"):
                seen["inside"] = _open_stack()
        seen["after"] = _open_stack()

    worker = threading.Thread(target=work)
    worker.start()
    worker.join()
    assert seen == {"inside": None, "after": None}
    assert not list(tmp_path.iterdir())
    assert span.compute()["player/to_env"] > 0.0  # the timers still run


def test_a_span_event_from_outside_span_states_no_parent(registry, tmp_path):
    """The env pool's own ``rollout/env_step`` events carry no ``parent``:
    a reader tells "no parent stated" from "at the top"."""
    tel = configure_telemetry({"metric": {"telemetry": {"enabled": True, "poll_interval": 0.0}}}, log_dir=str(tmp_path))
    with span("env/step"):
        tel.emit_span("rollout/env_step", None, 0.01, {"busy_s": 0.009})
    pool, loop = _events(tel)
    assert "parent" not in pool and loop["parent"] is None
