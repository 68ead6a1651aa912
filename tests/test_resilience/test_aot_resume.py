"""Preemption-resume with the AOT executable cache (ISSUE 17 acceptance):
SIGTERM a fused Dreamer-V3 run AFTER its superstep executable has been
committed to ``fabric.aot_cache_dir``, auto-resume the run, and prove the
resumed process deserialized the fused-window executable — ``aot_cache_hits
>= 1`` and ``recompiles == 0`` in its run_end telemetry — instead of paying
the compile again."""

import glob
import json
import os
import subprocess
import sys

import pytest

from sheeprl_tpu.resilience import PREEMPTED_EXIT_CODE

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def drill_args(tmp_path):
    """A tiny fused Dreamer-V3 run (the make_fused_train_fn path — the one
    wired to fabric.aot_cache): 4 train windows on dummy envs, run_name
    pinned for auto-resume."""
    return [
        "exp=dreamer_v3",
        "env=dummy",
        "env.id=dummy_discrete",
        "env.capture_video=False",
        "buffer.memmap=False",
        "algo.per_rank_batch_size=1",
        "algo.per_rank_sequence_length=1",
        "algo.learning_starts=2",
        "algo.replay_ratio=1",
        "algo.horizon=8",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.world_model.encoder.cnn_channels_multiplier=2",
        "algo.world_model.recurrent_model.recurrent_state_size=8",
        "algo.world_model.representation_model.hidden_size=8",
        "algo.world_model.transition_model.hidden_size=8",
        "algo.world_model.discrete_size=4",
        "algo.world_model.stochastic_size=4",
        "algo.cnn_keys.encoder=[rgb]",
        "algo.mlp_keys.encoder=[state]",
        "env.num_envs=2",
        "env.screen_size=16",
        "algo.run_test=False",
        "checkpoint.save_last=True",
        "metric.log_level=0",
        "metric.telemetry.enabled=True",
        "metric.telemetry.poll_interval=0.0",
        "run_name=aot_drill",
        f"log_base_dir={tmp_path}/logs",
        "fabric.devices=1",
        "buffer.device=True",
        "buffer.size=64",
        "algo.total_steps=16",
        "algo.fused_gradient_steps=256",
        f"fabric.aot_cache_dir={tmp_path}/aotcache",
    ]


def _child_env():
    """Subprocess env with REAL compiles: the suite-wide XLA persistent
    trace cache (tests/conftest.py) would make every compiled executable
    serialize into an unloadable payload (CPU backend), which AotCache's
    store-time verification rejects — the drill needs committed entries."""
    env = dict(os.environ)
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _run_end_events(tmp_path):
    events = []
    for path in glob.glob(os.path.join(str(tmp_path), "logs", "**", "telemetry.jsonl"), recursive=True):
        with open(path) as f:
            for line in f:
                if line.strip():
                    e = json.loads(line)
                    if e.get("event") in ("run_end", "auto_resume", "preempt"):
                        events.append(e)
    return events


@pytest.mark.slow
def test_preemption_resume_reuses_cached_superstep(tmp_path):
    cache_dir = f"{tmp_path}/aotcache"
    args = drill_args(tmp_path)
    # SIGTERM only once BOTH fused-window signatures (the ratio bookkeeping
    # compiles two window lengths) are COMMITTED to the cache — whichever
    # window length the resumed run opens with, its executable is there.
    # The async writer promotes entries moments after each window's compile,
    # well before the 16-step run can finish.
    child = f"""
import glob, os, signal
import sheeprl_tpu.resilience.manager as M
orig = M.RunResilience.preempt_requested
fired = [False]
def patched(self):
    if not fired[0] and len(glob.glob(os.path.join({cache_dir!r}, "*.aotx"))) >= 2:
        fired[0] = True
        os.kill(os.getpid(), signal.SIGTERM)
    return orig(self)
M.RunResilience.preempt_requested = patched
from sheeprl_tpu.cli import run
run({args!r})
raise SystemExit(0)
"""
    proc = subprocess.run(
        [sys.executable, "-c", child],
        cwd=str(tmp_path),
        env=_child_env(),
        capture_output=True,
        text=True,
        timeout=540,
    )
    assert proc.returncode == PREEMPTED_EXIT_CODE, (
        f"expected exit {PREEMPTED_EXIT_CODE}, got {proc.returncode}\n"
        f"stdout:\n{proc.stdout[-3000:]}\nstderr:\n{proc.stderr[-3000:]}"
    )
    entries = glob.glob(os.path.join(cache_dir, "*.aotx"))
    assert len(entries) >= 2, f"preempted run committed {entries}, expected both signatures"
    assert any(e["event"] == "preempt" for e in _run_end_events(tmp_path))

    # --- resume: same invocation + resume_from=auto, fresh process — the
    # cold path the cache exists for. It must deserialize, not recompile.
    resume = f"""
from sheeprl_tpu.cli import run
run({args!r} + ["checkpoint.resume_from=auto"])
"""
    proc = subprocess.run(
        [sys.executable, "-c", resume],
        cwd=str(tmp_path),
        env=_child_env(),
        capture_output=True,
        text=True,
        timeout=540,
    )
    assert proc.returncode == 0, (
        f"resume failed rc={proc.returncode}\n"
        f"stdout:\n{proc.stdout[-3000:]}\nstderr:\n{proc.stderr[-3000:]}"
    )
    events = _run_end_events(tmp_path)
    assert any(e["event"] == "auto_resume" for e in events)
    run_ends = [e for e in events if e["event"] == "run_end"]
    assert run_ends, "resumed run wrote no run_end telemetry"
    resumed = run_ends[-1]
    # the acceptance bar: the fused-window executable came from the cache,
    # and the resumed run never recompiled anything post-warmup
    assert resumed.get("aot_cache_hits", 0) >= 1, resumed
    assert resumed.get("aot_cache_errors", 0) == 0, resumed
    assert resumed.get("recompiles") == 0, resumed
