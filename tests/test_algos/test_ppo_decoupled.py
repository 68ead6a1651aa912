"""Decoupled PPO 2-process test (reference: tests/test_algos/test_algos.py::
test_ppo_decoupled, which launches 2 gloo ranks).

Spawns two real processes connected via ``jax.distributed`` on the CPU
backend: process 0 plays (owns the envs, ships the rollout), process 1
trains (fused PPO update on its own trainer mesh) and ships the params
back. Also exercises the host-object collectives cross-process — the
multi-process path that the in-process 8-device mesh tests cannot reach.
"""

import os

import pytest

from tests.conftest import find_checkpoints, run_multi_process, run_two_process

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


def test_ppo_decoupled_two_process(tmp_path):
    args = [
        "exp=ppo_decoupled",
        "env=dummy",
        "env.id=dummy_discrete",
        # forked AsyncVectorEnv workers inherit the jax.distributed client and
        # wedge its shutdown barrier; the decoupled topology drives sync envs
        "env.sync_env=True",
        "dry_run=True",
        "env.capture_video=False",
        "buffer.memmap=False",
        "algo.rollout_steps=8",
        "algo.per_rank_batch_size=4",
        "algo.update_epochs=2",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.encoder.cnn_features_dim=16",
        "algo.encoder.mlp_features_dim=8",
        "algo.mlp_keys.encoder=[state]",
        "env.num_envs=2",
        "algo.run_test=True",
        "checkpoint.save_last=True",
        "metric.log_level=1",
        f"log_base_dir={tmp_path}/logs",
    ]
    run_two_process(RUNNER, argv=args, cwd=str(tmp_path))
    assert find_checkpoints(tmp_path), "player did not write a checkpoint from the trainer state"


def _args(tmp_path, **over):
    base = {
        "exp": "ppo_decoupled",
        "env": "dummy",
        "env.id": "dummy_discrete",
        "env.sync_env": "True",
        "env.capture_video": "False",
        "buffer.memmap": "False",
        "algo.rollout_steps": "8",
        "algo.per_rank_batch_size": "4",
        "algo.update_epochs": "1",
        "algo.dense_units": "8",
        "algo.mlp_layers": "1",
        "algo.encoder.cnn_features_dim": "16",
        "algo.encoder.mlp_features_dim": "8",
        "algo.mlp_keys.encoder": "[state]",
        "env.num_envs": "2",
        "algo.run_test": "False",
        "checkpoint.save_last": "True",
        "metric.log_level": "0",
        "log_base_dir": f"{tmp_path}/logs",
    }
    base.update(over)
    return [f"{k}={v}" for k, v in base.items()]


def test_ppo_decoupled_three_process_two_trainers(tmp_path):
    """1 player + 2 trainer processes: the rollout splits across the trainer
    mesh and the gradient pmean runs over two real processes (before this
    test the decoupled topology had only ever run with one trainer)."""
    run_multi_process(
        RUNNER,
        argv=_args(tmp_path, **{"algo.total_steps": "32"}),
        cwd=str(tmp_path),
        nproc=3,
        device_count=1,
        timeout=600,
    )
    assert find_checkpoints(tmp_path), "no checkpoint written by the 3-process run"


@pytest.mark.slow
def test_ppo_decoupled_resume(tmp_path):
    """Checkpoint mid-run (update 2 of 4), then resume from it and finish:
    the decoupled topology restores params, optimizer state, counters and
    the player's action-sampling stream (reference
    ppo_decoupled.py:45-46,104-116). Resume reloads the run config stored
    beside the checkpoint, so both runs share total_steps=64."""
    run_two_process(
        RUNNER,
        argv=_args(
            tmp_path,
            **{
                "algo.total_steps": "64",
                "checkpoint.every": "32",
                "checkpoint.save_last": "False",
            },
        ),
        cwd=str(tmp_path),
    )
    ckpts = find_checkpoints(tmp_path)
    assert len(ckpts) >= 2, f"expected mid-run + final checkpoints, got {ckpts}"
    midway = [c for c in ckpts if os.path.basename(c).startswith("ckpt_32_")]
    assert midway, ckpts
    run_two_process(
        RUNNER,
        argv=_args(tmp_path, **{"checkpoint.resume_from": midway[0]}),
        cwd=str(tmp_path),
    )
    resumed = [c for c in find_checkpoints(tmp_path) if c not in ckpts]
    assert resumed, "resumed run did not write its own checkpoint"

    from sheeprl_tpu.utils.checkpoint import load_checkpoint

    state = load_checkpoint(resumed[-1])
    assert state["update"] == 4, f"resumed run should end at update 4, got {state['update']}"
    assert "player_rng_key" in state and "opt_state" in state and state["opt_state"] is not None
