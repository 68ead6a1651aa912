"""Fused training supersteps through the real CLI entry points: with
``algo.fused_gradient_steps=K`` and the device replay buffer, one train
window of K gradient steps issues a single jitted dispatch — asserted via
the telemetry dispatch counters (the ISSUE's acceptance criterion) — plus
the documented warn-fallbacks and the Dreamer host-buffer pregather path."""

import json
import os

import pytest

from sheeprl_tpu.cli import run
from tests.test_algos.test_a2c_droq import droq_args
from tests.test_algos.test_dreamer_v3 import dv3_args, find_checkpoints
from tests.test_algos.test_sac import sac_args
from tools import report

TELEMETRY = ["metric.telemetry.enabled=True", "metric.telemetry.poll_interval=0.0"]


def _run_end(tmp_path):
    jsonls = []
    for root, _, files in os.walk(tmp_path):
        jsonls += [os.path.join(root, f) for f in files if f == "telemetry.jsonl"]
    assert len(jsonls) == 1, f"expected exactly one telemetry.jsonl, found {jsonls}"
    events = [json.loads(line) for line in open(jsonls[0]) if line.strip()]
    (end,) = [e for e in events if e["event"] == "run_end"]
    return end, jsonls[0]


def test_dreamer_v3_fused_device_buffer_single_dispatch_per_window(tmp_path, monkeypatch):
    """ISSUE acceptance: K >= G, device ring -> every train window is exactly
    ONE device program (the per-step device-buffer path would record 2G:
    a gather program + a train program per gradient step)."""
    monkeypatch.chdir(tmp_path)
    args = [
        a
        for a in dv3_args(tmp_path)
        if a != "dry_run=True" and not a.startswith("buffer.size=")
    ]
    run(
        args
        + [
            "fabric.devices=1",
            "buffer.device=True",
            "buffer.size=64",
            "algo.total_steps=8",
            "algo.learning_starts=2",
            "algo.fused_gradient_steps=256",
        ]
        + TELEMETRY
    )
    assert find_checkpoints(tmp_path)

    end, path = _run_end(tmp_path)
    assert end["train_windows"] >= 2
    # the single-dispatch claim itself
    assert end["train_dispatches"] == end["train_windows"]
    # ... and the windows really fused MULTIPLE gradient steps (the Ratio's
    # first call always yields 1; later windows carry replay_ratio * steps)
    assert end["train_gradient_steps"] > end["train_windows"]

    ds = report.dispatch_stats(path)
    assert ds["dispatches_per_window"] == 1.0
    assert ds["train_gradient_steps"] == end["train_gradient_steps"]


def test_dreamer_v3_fused_host_buffer_pregathers(tmp_path, monkeypatch):
    """Without the device ring Dreamer still fuses: K host batches are
    pre-gathered and scanned in one dispatch (bit-identical sampling)."""
    monkeypatch.chdir(tmp_path)
    run(dv3_args(tmp_path) + ["fabric.devices=1", "algo.fused_gradient_steps=2"])
    assert find_checkpoints(tmp_path)


@pytest.mark.slow
def test_dreamer_v3_fused_multi_device_single_dispatch_per_window(tmp_path, monkeypatch, recwarn):
    """ISSUE acceptance: on a pure data-parallel mesh the fused path no
    longer falls back — the whole K-step scan runs under shard_map over the
    sharded device ring, each window is ONE dispatch, and no fallback
    warning or ``fused_fallback`` telemetry event is emitted."""
    monkeypatch.chdir(tmp_path)
    args = [
        a
        for a in dv3_args(tmp_path)
        if a != "dry_run=True" and not a.startswith("buffer.size=")
    ]
    run(
        args
        + [
            "fabric.devices=2",
            "buffer.device=True",
            "buffer.size=64",
            "algo.total_steps=8",
            "algo.learning_starts=2",
            "algo.fused_gradient_steps=256",
        ]
        + TELEMETRY
    )
    assert find_checkpoints(tmp_path)
    assert not [
        w for w in recwarn if "falling back" in str(w.message)
    ], [str(w.message) for w in recwarn]

    end, path = _run_end(tmp_path)
    assert end["train_windows"] >= 2
    assert end["train_dispatches"] == end["train_windows"]
    assert end["train_gradient_steps"] > end["train_windows"]
    assert not end.get("fused_fallbacks")

    ds = report.dispatch_stats(path)
    assert ds["dispatches_per_window"] == 1.0
    assert "fused_fallbacks" not in ds


def test_dreamer_v3_fused_multi_device_host_buffer_pregathers(tmp_path, monkeypatch, recwarn):
    """The host-buffer pregather fallback fuses on a mesh too: the stacked
    [K, T, B] batches go up batch-axis sharded and the shard_map'd scan
    slices them without warning or falling back."""
    monkeypatch.chdir(tmp_path)
    run(dv3_args(tmp_path) + ["fabric.devices=2", "algo.fused_gradient_steps=2"])
    assert find_checkpoints(tmp_path)
    assert not [w for w in recwarn if "falling back" in str(w.message)]


def test_sac_fused_device_buffer_single_dispatch_per_window(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    args = [a for a in sac_args(tmp_path) if a != "dry_run=True"]
    run(
        args
        + [
            "fabric.devices=1",
            "buffer.device=True",
            "buffer.size=64",
            "algo.total_steps=8",
            "algo.learning_starts=2",
            "algo.fused_gradient_steps=8",
        ]
        + TELEMETRY
    )
    assert find_checkpoints(tmp_path)
    end, _ = _run_end(tmp_path)
    assert end["train_windows"] >= 2
    assert end["train_dispatches"] == end["train_windows"]
    assert end["train_gradient_steps"] > end["train_windows"]


def test_sac_fused_host_buffer_falls_back_with_warning(tmp_path, monkeypatch):
    """SAC's host-buffer path already scans each chunk in one jit, so
    fused_gradient_steps without buffer.device warns (once) and is ignored —
    and the reason lands in run_end / ``tools.report --dispatch-stats`` so a
    per-step run is diagnosable after the fact."""
    monkeypatch.chdir(tmp_path)
    with pytest.warns(UserWarning, match="device replay buffer"):
        run(
            sac_args(tmp_path)
            + ["fabric.devices=1", "algo.fused_gradient_steps=4"]
            + TELEMETRY
        )
    assert find_checkpoints(tmp_path)
    end, path = _run_end(tmp_path)
    assert end["fused_fallbacks"] == {"host_buffer": 1}
    assert report.dispatch_stats(path)["fused_fallbacks"] == {"host_buffer": 1}


def test_droq_fused_device_buffer_dispatch_budget(tmp_path, monkeypatch):
    """DroQ windows = fused critic chunks + the separate actor update. With
    K >= G that is 1 (critic superstep) + 2 (actor gather + actor program)
    device dispatches per window — the per-step device path records 2G + 2."""
    monkeypatch.chdir(tmp_path)
    args = [a for a in droq_args(tmp_path) if a != "dry_run=True"]
    run(
        args
        + [
            "fabric.devices=1",
            "buffer.device=True",
            "buffer.size=64",
            "algo.total_steps=8",
            "algo.learning_starts=2",
            "algo.replay_ratio=1",
            "algo.fused_gradient_steps=8",
        ]
        + TELEMETRY
    )
    assert find_checkpoints(tmp_path)
    end, _ = _run_end(tmp_path)
    assert end["train_windows"] >= 2
    assert end["train_dispatches"] == 3 * end["train_windows"]
    # gradient_steps counts the actor step too (G critic + 1 actor per window)
    assert end["train_gradient_steps"] > end["train_windows"]
