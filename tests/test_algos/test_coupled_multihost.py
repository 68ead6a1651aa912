"""Coupled (SPMD) multi-host e2e tests: full PPO and Dreamer-V3 ``main()``
across 2 real ``jax.distributed`` CPU processes × 2 virtual devices each —
the exact topology of the milestone multi-host configs (BASELINE.json (2)/(4)).

Each process owns its own envs, samples its block of the global batch,
assembles mesh-global arrays (``fabric.make_global`` — for DV3 through the
multi-host prefetch pipeline), runs the shard_map'd train step with its grad
pmean over the 4-device mesh, and writes its rank's checkpoint shard.
"""

import pytest

from tests.conftest import find_checkpoints, run_multi_process

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


def test_ppo_coupled_two_process(tmp_path):
    args = [
        "exp=ppo",
        "env=dummy",
        "env.id=dummy_discrete",
        # forked AsyncVectorEnv workers inherit the jax.distributed client
        # and wedge its shutdown barrier; drive sync envs multi-process
        "env.sync_env=True",
        "env.capture_video=False",
        "buffer.memmap=False",
        "algo.total_steps=64",
        "algo.rollout_steps=8",
        "algo.per_rank_batch_size=4",
        "algo.update_epochs=2",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.encoder.cnn_features_dim=16",
        "algo.encoder.mlp_features_dim=8",
        "algo.mlp_keys.encoder=[state]",
        "env.num_envs=2",
        "algo.run_test=False",
        "checkpoint.save_last=True",
        "metric.log_level=1",
        f"log_base_dir={tmp_path}/logs",
    ]
    run_multi_process(RUNNER, argv=args, cwd=str(tmp_path), nproc=2, device_count=2, timeout=600)
    ckpts = find_checkpoints(tmp_path)
    assert len(ckpts) >= 1, "coupled multi-host PPO wrote no checkpoint"


@pytest.mark.slow
def test_dreamer_v3_coupled_two_process(tmp_path):
    args = [
        "exp=dreamer_v3",
        "env=dummy",
        "env.id=dummy_discrete",
        "env.sync_env=True",
        "env.capture_video=False",
        "buffer.memmap=False",
        "buffer.size=64",
        "buffer.checkpoint=True",
        "buffer.prefetch=2",  # the multi-host prefetch pipeline stays ON
        "algo.total_steps=24",
        "algo.learning_starts=8",
        "algo.replay_ratio=0.5",
        "algo.per_rank_batch_size=1",
        "algo.per_rank_sequence_length=4",
        "algo.horizon=4",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.world_model.encoder.cnn_channels_multiplier=2",
        "algo.world_model.recurrent_model.recurrent_state_size=8",
        "algo.world_model.representation_model.hidden_size=8",
        "algo.world_model.transition_model.hidden_size=8",
        "algo.world_model.discrete_size=4",
        "algo.world_model.stochastic_size=4",
        "algo.cnn_keys.encoder=[rgb]",
        "algo.mlp_keys.encoder=[]",
        "env.num_envs=1",
        "env.screen_size=64",
        "algo.run_test=False",
        "checkpoint.save_last=True",
        "metric.log_level=1",
        f"log_base_dir={tmp_path}/logs",
    ]
    run_multi_process(RUNNER, argv=args, cwd=str(tmp_path), nproc=2, device_count=2, timeout=600)
    # every rank contributes its checkpoint shard (buffer gather to rank files)
    ckpts = find_checkpoints(tmp_path)
    assert len(ckpts) >= 1, "coupled multi-host Dreamer-V3 wrote no checkpoint"
