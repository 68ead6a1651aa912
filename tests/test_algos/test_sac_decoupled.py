"""Decoupled SAC 2-process test (reference: tests/test_algos/test_algos.py::
test_sac_decoupled). Process 0 plays and owns the replay buffer; process 1
trains on its own mesh and ships the actor back."""

import os

import pytest

from tests.conftest import find_checkpoints, run_two_process

RUNNER = """
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address=os.environ["TEST_COORD"],
    num_processes=int(os.environ["TEST_NPROC"]),
    process_id=int(os.environ["TEST_PID"]),
)
from sheeprl_tpu.cli import run
run(sys.argv[1:])
"""


def test_sac_decoupled_two_process(tmp_path):
    args = [
        "exp=sac_decoupled",
        "env=dummy",
        "env.id=dummy_continuous",
        "env.sync_env=True",
        "dry_run=True",
        "env.capture_video=False",
        "buffer.memmap=False",
        "algo.per_rank_batch_size=2",
        "buffer.size=10",
        "algo.learning_starts=0",
        "algo.replay_ratio=1",
        "algo.per_rank_pretrain_steps=1",
        "algo.hidden_size=8",
        "algo.mlp_keys.encoder=[state]",
        "env.num_envs=2",
        "algo.run_test=True",
        "checkpoint.save_last=True",
        "metric.log_level=1",
        f"log_base_dir={tmp_path}/logs",
    ]
    run_two_process(RUNNER, argv=args, cwd=str(tmp_path))
    assert find_checkpoints(tmp_path), "player did not write a checkpoint from the trainer state"


@pytest.mark.slow
def test_sac_decoupled_resume(tmp_path):
    """Decoupled SAC restores agent, optimizers, replay buffer and counters
    from a player-written checkpoint."""
    base = [
        "exp=sac_decoupled",
        "env=dummy",
        "env.id=dummy_continuous",
        "env.sync_env=True",
        "env.capture_video=False",
        "buffer.memmap=False",
        "algo.per_rank_batch_size=2",
        "buffer.size=64",
        "buffer.checkpoint=True",
        "algo.learning_starts=2",
        "algo.replay_ratio=1",
        "algo.per_rank_pretrain_steps=1",
        "algo.hidden_size=8",
        "algo.mlp_keys.encoder=[state]",
        "env.num_envs=2",
        "algo.run_test=False",
        "checkpoint.save_last=False",
        "metric.log_level=0",
        f"log_base_dir={tmp_path}/logs",
    ]
    # resume reloads the config stored beside the checkpoint, so the resumed
    # run continues the SAME total_steps=16 from the mid-run checkpoint
    run_two_process(
        RUNNER,
        argv=base + ["algo.total_steps=16", "checkpoint.every=8"],
        cwd=str(tmp_path),
    )
    ckpts = find_checkpoints(tmp_path)
    midway = [c for c in ckpts if os.path.basename(c).startswith("ckpt_8_")]
    assert midway, ckpts
    # resume keeps the CURRENT run's checkpoint settings (reference
    # semantics), so the cadence must be restated
    run_two_process(
        RUNNER,
        argv=base + ["checkpoint.every=8", f"checkpoint.resume_from={midway[0]}"],
        cwd=str(tmp_path),
    )
    resumed = [c for c in find_checkpoints(tmp_path) if c not in ckpts]
    assert resumed, "resumed run did not write its own checkpoint"

    from sheeprl_tpu.utils.checkpoint import load_checkpoint

    state = load_checkpoint(resumed[-1])
    assert state["update"] == 8, f"resumed run should end at update 8, got {state['update']}"
    assert "player_rng_key" in state and "agent" in state
