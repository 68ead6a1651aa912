"""CLI acceptance drills (ISSUE acceptance criteria): SIGTERM mid-run lands a
committed emergency checkpoint and exits 77, resume_from=auto continues at the
saved step; an injected NaN triggers exactly one rollback and the run still
completes; async saves block the loop for the snapshot span only."""

import json
import os
import subprocess
import sys

from sheeprl_tpu.cli import run
from sheeprl_tpu.resilience import PREEMPTED_EXIT_CODE, committed_checkpoints, read_manifest
from sheeprl_tpu.utils.checkpoint import load_checkpoint
from tools import report

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# 4 updates of 64 policy steps each (2 envs x 32 rollout steps) on tiny nets;
# run_name is PINNED because the default carries a ${now:...} timestamp and
# auto-resume scans <log_base_dir>/<root_dir>/<run_name>
def drill_args(tmp_path):
    return [
        "exp=ppo",
        "env.capture_video=False",
        "buffer.memmap=False",
        "algo.total_steps=256",
        "algo.rollout_steps=32",
        "algo.per_rank_batch_size=8",
        "algo.update_epochs=1",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.encoder.cnn_features_dim=16",
        "algo.encoder.mlp_features_dim=8",
        "env.num_envs=2",
        "algo.run_test=False",
        "checkpoint.save_last=True",
        "metric.log_level=0",
        "metric.telemetry.enabled=True",
        "metric.telemetry.poll_interval=0.0",
        "run_name=drill",
        f"log_base_dir={tmp_path}/logs",
    ]


def _telemetry_events(tmp_path):
    for root, _, files in os.walk(tmp_path):
        if "telemetry.jsonl" in files:
            with open(os.path.join(root, "telemetry.jsonl")) as f:
                return [json.loads(line) for line in f if line.strip()], os.path.join(
                    root, "telemetry.jsonl"
                )
    return [], None


def _ckpt_dirs(tmp_path):
    out = []
    for root, dirs, _ in os.walk(tmp_path):
        out += [os.path.join(root, d) for d in dirs if d == "checkpoint"]
    return out


def test_sigterm_drill_and_auto_resume(tmp_path, monkeypatch):
    """Preemption end to end, in a real subprocess: SIGTERM at the update-2
    boundary -> drained async saves, committed emergency checkpoint of update
    1, exit code 77; then resume_from=auto finds it and finishes the run."""
    args = drill_args(tmp_path) + ["checkpoint.every=0"]
    # deliver a REAL SIGTERM to the child at its second train-loop boundary:
    # the handler sets the flag, the poll returns True, and the run drains
    child = f"""
import os, signal
import sheeprl_tpu.resilience.manager as M
orig = M.RunResilience.preempt_requested
count = [0]
def patched(self):
    count[0] += 1
    if count[0] == 2:
        os.kill(os.getpid(), signal.SIGTERM)
    return orig(self)
M.RunResilience.preempt_requested = patched
from sheeprl_tpu.cli import run
run({args!r})
raise SystemExit(0)
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", child],
        cwd=str(tmp_path),
        env=env,
        capture_output=True,
        text=True,
        timeout=540,
    )
    assert proc.returncode == PREEMPTED_EXIT_CODE, (
        f"expected exit {PREEMPTED_EXIT_CODE}, got {proc.returncode}\n"
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )

    (ckpt_dir,) = _ckpt_dirs(tmp_path)
    (emergency,) = committed_checkpoints(ckpt_dir)
    assert emergency.step == 64  # policy step at the update-2 boundary
    assert read_manifest(emergency.path)["emergency"] is True
    saved = load_checkpoint(emergency.path)
    assert saved["update"] == 1  # update 2 never ran

    events, _ = _telemetry_events(tmp_path)
    assert any(e["event"] == "preempt" for e in events)
    commits = [e for e in events if e["event"] == "ckpt_committed"]
    assert len(commits) == 1 and commits[0]["emergency"]

    # --- auto-resume: same invocation + resume_from=auto picks the emergency
    # checkpoint (same pinned run_name) and continues from update 2
    monkeypatch.chdir(tmp_path)
    run(args + ["checkpoint.resume_from=auto"])

    finals = [
        c for d in _ckpt_dirs(tmp_path) for c in committed_checkpoints(d) if c.step == 256
    ]
    assert finals, "resumed run did not reach the final checkpoint"
    assert load_checkpoint(finals[0].path)["update"] == 4

    events, jsonl = _telemetry_events(tmp_path)
    resumed = [e for e in events if e["event"] == "auto_resume"]
    assert len(resumed) == 1
    assert resumed[0]["path"] == emergency.path and resumed[0]["ckpt_step"] == 64

    # tools.report --resilience-stats digests the drill without log scraping
    stats = report.resilience_stats(jsonl)
    assert stats["totals"]["preemptions"] == 1
    assert 64 in stats["emergency_steps"]
    assert stats["auto_resume"][0]["ckpt_step"] == 64


def test_nan_drill_one_rollback_run_completes(tmp_path, monkeypatch):
    """Deterministic NaN injection at update 3: exactly one nan_rollback
    event, the state restored from the update-2 checkpoint, and the run still
    completes all 4 updates (ISSUE acceptance)."""
    monkeypatch.chdir(tmp_path)
    args = drill_args(tmp_path) + [
        "checkpoint.every=64",
        "checkpoint.async_save=False",  # the rollback point must be committed before update 3
        "resilience.fault_injection.enabled=True",
        "resilience.fault_injection.faults=[{kind: nan, at_update: 3}]",
    ]
    run(args)  # must not raise: the rollback keeps the run alive

    events, jsonl = _telemetry_events(tmp_path)
    rollbacks = [e for e in events if e["event"] == "nan_rollback"]
    assert len(rollbacks) == 1
    assert rollbacks[0]["update"] == 3
    assert rollbacks[0]["remaining"] == 2  # default budget 3, one spent
    restored_step = read_manifest(rollbacks[0]["path"])["step"]
    assert restored_step == 128  # the update-2 checkpoint

    run_end = [e for e in events if e["event"] == "run_end"][-1]
    assert run_end["nan_rollbacks"] == 1

    # evidence engine (ISSUE acceptance): the rollback dumped the flight
    # recorder next to telemetry.jsonl — one valid JSON document, bounded
    # ring, the nan_rollback trigger event LAST among its events
    flight_path = os.path.join(os.path.dirname(jsonl), "flightrec.json")
    assert os.path.exists(flight_path)
    with open(flight_path) as f:
        flight = json.load(f)
    assert flight["trigger"] == "nan_rollback"
    assert len(flight["events"]) <= flight["ring_capacity"]
    assert flight["events"][-1]["event"] == "nan_rollback"
    assert flight["events"][-1]["update"] == 3

    # the run completed: the save_last checkpoint carries the final update
    finals = [
        c for d in _ckpt_dirs(tmp_path) for c in committed_checkpoints(d) if c.step == 256
    ]
    assert finals and load_checkpoint(finals[0].path)["update"] == 4

    stats = report.resilience_stats(jsonl)
    assert stats["totals"]["nan_rollbacks"] == 1
    assert stats["nan_rollbacks"][0]["update"] == 3


def test_async_save_blocks_snapshot_only(tmp_path, monkeypatch):
    """checkpoint.async_save=True: every periodic save shows up as a blocking
    ckpt/snapshot span plus a background ckpt/write span (async: no sync
    attr), and commits equal the checkpoints on disk (ISSUE acceptance: the
    loop pays snapshot time only, asserted via span durations)."""
    monkeypatch.chdir(tmp_path)
    run(drill_args(tmp_path) + ["checkpoint.every=64", "checkpoint.async_save=True"])

    events, jsonl = _telemetry_events(tmp_path)
    snapshots = [e for e in events if e["event"] == "span" and e["name"] == "ckpt/snapshot"]
    writes = [e for e in events if e["event"] == "span" and e["name"] == "ckpt/write"]
    assert snapshots, "async saves must emit the blocking ckpt/snapshot span"
    assert writes, "async saves must emit the background ckpt/write span"
    assert all(e["dur"] >= 0 for e in snapshots + writes)
    # the loop-blocking part is the snapshot; the write rode the background
    # thread (async writes carry no sync attr)
    assert any(not (e.get("attrs") or {}).get("sync") for e in writes)

    committed = [c for d in _ckpt_dirs(tmp_path) for c in committed_checkpoints(d)]
    commits = [e for e in events if e["event"] == "ckpt_committed"]
    skips = [e for e in events if e["event"] == "ckpt_skipped"]
    assert len(commits) == len(committed) and commits
    # every periodic boundary either committed or was accounted as skipped
    assert len(commits) + len(skips) == 4

    stats = report.resilience_stats(jsonl)
    assert stats["snapshot"]["count"] == len(snapshots)
    assert stats["write"]["async_count"] >= 1
    assert stats["totals"]["ckpt_commits"] == len(commits)
