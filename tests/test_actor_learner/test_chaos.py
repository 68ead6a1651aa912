"""Deterministic chaos drills (ISSUE acceptance) + the decoupled-PPO
learning-parity smoke. Each drill runs the real CLI entrypoint with scripted
``algo.actor_learner.fault_injection`` faults and asserts on the durable
evidence (RUNS.jsonl rollup, checkpoint files, process/shm hygiene). Marked
``slow``: each spawns real actor processes (jax imports) and the parity smoke
trains two runs to completion."""

import json
import os

import pytest

from sheeprl_tpu.cli import run

pytestmark = [pytest.mark.actor_learner, pytest.mark.slow]


def base_args(tmp_path):
    return [
        "exp=ppo_decoupled",
        "env.capture_video=False",
        "buffer.memmap=False",
        "algo.rollout_steps=32",
        "algo.per_rank_batch_size=8",
        "algo.update_epochs=2",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.encoder.cnn_features_dim=16",
        "algo.encoder.mlp_features_dim=8",
        "env.num_envs=2",
        "algo.run_test=False",
        "checkpoint.save_last=True",
        "metric.log_level=1",
        "metric.telemetry.enabled=True",
        "algo.actor_learner.num_actors=1",
        "algo.actor_learner.slots_per_actor=2",
        "algo.actor_learner.fault_injection.enabled=True",
        f"log_base_dir={tmp_path}/logs",
    ]


def read_runs(path):
    return [json.loads(line) for line in open(path) if line.strip()]


def find_checkpoints(tmp_path):
    ckpts = []
    for root, _, files in os.walk(tmp_path):
        ckpts += [os.path.join(root, f) for f in files if f.endswith(".ckpt")]
    return ckpts


def assert_clean_process_and_shm_state():
    import multiprocessing as mp

    from sheeprl_tpu.rollout.shm import _OWNED_SEGMENTS

    assert not _OWNED_SEGMENTS, f"leaked shm segments: {list(_OWNED_SEGMENTS)}"
    orphans = [p for p in mp.active_children() if p.name.startswith("al-actor")]
    assert not orphans, f"orphaned actors: {orphans}"


def test_actor_crash_mid_write_drill(tmp_path, monkeypatch):
    """Actor killed mid-write (after payload+meta, before the commit marker):
    the learner must admit ZERO torn slabs, the supervisor charges exactly one
    restart, and the run completes (acceptance drill #1)."""
    monkeypatch.chdir(tmp_path)
    runs = tmp_path / "RUNS.jsonl"
    run(
        base_args(tmp_path)
        + [
            "dry_run=True",
            "algo.actor_learner.fault_injection.faults=[{kind: actor_crash_mid_write, actor: 0, at_slab: 0}]",
            f"metric.telemetry.runs_jsonl={runs}",
        ]
    )
    assert_clean_process_and_shm_state()
    (rec,) = read_runs(runs)
    assert rec["outcome"] == "completed"
    # the torn slab was detected/reclaimed, never admitted
    assert rec.get("torn_slabs", 0) >= 1
    assert rec.get("slabs_admitted", 0) >= 1
    # exactly one restart charged for the scripted crash
    assert rec.get("actor_restarts") == {"0": 1}
    assert find_checkpoints(tmp_path)

    # -- merged end-to-end trace (acceptance): the registry record names
    # every per-process stream (no globbing), and joining them yields one
    # causal chain per admitted slab plus a torn-terminated victim chain
    files = rec["telemetry_files"]
    assert any(p.endswith("telemetry.jsonl") for p in files)
    assert any("trace.actor0" in p for p in files)
    assert all(os.path.isfile(p) for p in files), files

    from tools import trace as trace_tool

    merged = trace_tool.merge(files)
    roles = {p["role"] for p in merged["processes"]}
    assert "learner" in roles and any(r.startswith("actor") for r in roles)
    summary = trace_tool.summarize(merged)
    slabs = summary["slabs"]
    # every admitted slab's chain is complete across the process boundary:
    # collect+commit in the actor child, admit+train in the learner
    assert slabs["complete_chains"] >= rec["slabs_admitted"]
    assert slabs["terminals"].get("slab_train", 0) >= 1
    # the crash victim: its chain keeps the actor-side slab_collect (the
    # flush-per-event recorder survives os._exit) and terminates at `torn`
    torn_chains = [
        evs
        for evs in merged["traces"].values()
        if trace_tool.slab_terminal(evs) == "torn"
    ]
    assert len(torn_chains) >= 1
    assert any(
        trace_tool.trace_kinds(evs)[0] == "slab_collect" for evs in torn_chains
    )
    # lag decomposition present for the trained population
    for key in ("age_ms", "collect_ms", "ring_wait_ms", "train_ms"):
        assert "p50" in slabs[key] and "p95" in slabs[key]

    # tools.report --trace prints the same decomposition without importing jax
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    proc = subprocess.run(
        [sys.executable, "-m", "tools.report", "--trace", *files],
        cwd=repo,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    printed = json.loads(proc.stdout)
    assert printed["slabs"]["complete_chains"] == slabs["complete_chains"]
    assert "p95" in printed["slabs"]["age_ms"]


def test_actor_hang_drill(tmp_path, monkeypatch):
    """A wedged (non-heartbeating) actor trips the supervision deadline and is
    restarted within budget; the run still completes."""
    monkeypatch.chdir(tmp_path)
    runs = tmp_path / "RUNS.jsonl"
    run(
        base_args(tmp_path)
        + [
            "dry_run=True",
            "algo.actor_learner.step_timeout_s=3",
            "algo.actor_learner.heartbeat_grace_s=3",
            "algo.actor_learner.fault_injection.faults=[{kind: actor_hang, actor: 0, at_slab: 0, duration_s: 3600}]",
            f"metric.telemetry.runs_jsonl={runs}",
        ]
    )
    assert_clean_process_and_shm_state()
    (rec,) = read_runs(runs)
    assert rec["outcome"] == "completed"
    assert rec.get("actor_restarts") == {"0": 1}


def test_learner_kill_drill(tmp_path, monkeypatch):
    """learner_kill (self-SIGTERM after the first admitted slab) must drive
    the resilience drain verbatim: emergency checkpoint, quiesced actors, no
    leaked shm, the distinct preemption exit code, and a `preempted` registry
    record (acceptance drill #2)."""
    from sheeprl_tpu.resilience import PREEMPTED_EXIT_CODE

    monkeypatch.chdir(tmp_path)
    runs = tmp_path / "RUNS.jsonl"
    # num_updates > 1 so the loop re-enters its preemption poll after the
    # admitted slab whose fault pulled the trigger
    with pytest.raises(SystemExit) as exc:
        run(
            base_args(tmp_path)
            + [
                "algo.total_steps=128",
                "algo.actor_learner.fault_injection.faults=[{kind: learner_kill, at_slab: 0}]",
                f"metric.telemetry.runs_jsonl={runs}",
            ]
        )
    assert exc.value.code == PREEMPTED_EXIT_CODE
    assert_clean_process_and_shm_state()
    assert find_checkpoints(tmp_path), "no emergency checkpoint written"
    (rec,) = read_runs(runs)
    assert rec["outcome"] == "preempted"
    assert rec.get("slabs_admitted", 0) >= 1


def test_param_lane_stall_drives_staleness_drops(tmp_path, monkeypatch):
    """param_lane_stall with max_staleness=0: while the publish is suppressed
    the actor keeps refilling against the stalled version, so the learner must
    count+drop stale slabs and train only on refreshed ones."""
    monkeypatch.chdir(tmp_path)
    runs = tmp_path / "RUNS.jsonl"
    run(
        base_args(tmp_path)
        + [
            "algo.total_steps=192",  # 3 updates of 64 rows
            "algo.actor_learner.max_staleness=0",
            "algo.actor_learner.fault_injection.faults=[{kind: param_lane_stall, at_slab: 0, duration_s: 1.5}]",
            f"metric.telemetry.runs_jsonl={runs}",
        ]
    )
    assert_clean_process_and_shm_state()
    (rec,) = read_runs(runs)
    assert rec["outcome"] == "completed"
    assert rec.get("dropped_stale_slabs", 0) >= 1
    assert rec.get("slabs_admitted", 0) >= 3
    # no restarts, no torn slabs — staleness is a clean drop/refill path
    assert "actor_restarts" not in rec
    assert rec.get("torn_slabs", 0) == 0


def test_decoupled_learning_parity_smoke(tmp_path, monkeypatch):
    """Satellite: async (actor-learner) PPO vs sync PPO at equal env steps,
    fixed seeds, CartPole CPU — the final return must be within tolerance.
    Admission order makes the async path nondeterministic, so the tolerance
    is a did-it-learn band, not bitwise parity."""
    monkeypatch.chdir(tmp_path)
    runs = tmp_path / "RUNS.jsonl"
    common = [
        "seed=42",
        "env.capture_video=False",
        "buffer.memmap=False",
        "algo.total_steps=24576",
        "algo.rollout_steps=64",
        # the async slab is per-actor (64*4 rows / 8 devices = 32 per device),
        # so the shared batch size must fit the smaller of the two layouts
        "algo.per_rank_batch_size=32",
        "env.num_envs=8",
        "algo.run_test=False",
        "checkpoint.save_last=False",
        "metric.log_level=1",
        "metric.telemetry.enabled=True",
        f"metric.telemetry.runs_jsonl={runs}",
        f"log_base_dir={tmp_path}/logs",
    ]
    run(["exp=ppo"] + common)
    run(["exp=ppo_decoupled"] + common + ["algo.actor_learner.num_actors=2"])
    assert_clean_process_and_shm_state()

    sync_rec, async_rec = read_runs(runs)
    assert sync_rec.get("variant") is None and async_rec["variant"] == "actor_learner"
    sync_ret = sync_rec["final_metrics"]["Rewards/rew_avg"]
    async_ret = async_rec["final_metrics"]["Rewards/rew_avg"]
    # both clearly above CartPole's ~20-step random baseline...
    assert sync_ret > 40, f"sync PPO failed to learn: {sync_ret}"
    assert async_ret > 40, f"async PPO failed to learn: {async_ret}"
    # ...and the async path within tolerance of the sync path
    assert async_ret >= 0.25 * sync_ret, f"async={async_ret} vs sync={sync_ret}"
    # every admitted slab stayed within the staleness bound; nothing torn
    assert async_rec.get("torn_slabs", 0) == 0
    assert async_rec.get("slabs_admitted", 0) >= 1
