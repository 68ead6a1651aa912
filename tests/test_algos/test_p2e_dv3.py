"""P2E DV3 smoke tests (reference: tests/test_algos/test_algos.py::test_p2e_dv3).

One CLI-driven update of the exploration phase (world model + ensembles +
exploration/task actors and critics), then the exploration -> finetuning
hand-off and the evaluate round trip.
"""

import os

import pytest

from sheeprl_tpu.cli import run

TINY = [
    "env=dummy",
    "dry_run=True",
    "env.capture_video=False",
    "buffer.memmap=False",
    "algo.per_rank_batch_size=1",
    "algo.per_rank_sequence_length=1",
    "buffer.size=10",
    "algo.learning_starts=0",
    "algo.replay_ratio=1",
    "algo.per_rank_pretrain_steps=1",
    "algo.horizon=4",
    "algo.dense_units=8",
    "algo.mlp_layers=1",
    "algo.ensembles.n=3",
    "algo.world_model.encoder.cnn_channels_multiplier=2",
    "algo.world_model.recurrent_model.recurrent_state_size=8",
    "algo.world_model.representation_model.hidden_size=8",
    "algo.world_model.transition_model.hidden_size=8",
    "algo.world_model.discrete_size=4",
    "algo.world_model.stochastic_size=4",
    "algo.cnn_keys.encoder=[rgb]",
    "algo.mlp_keys.encoder=[state]",
    "env.num_envs=2",
    "env.screen_size=64",
    "algo.run_test=True",
    "checkpoint.save_last=True",
    "metric.log_level=1",
]


def expl_args(tmp_path, env_id="dummy_discrete"):
    return ["exp=p2e_dv3_exploration", f"env.id={env_id}", f"log_base_dir={tmp_path}/logs"] + TINY


def find_checkpoints(path):
    ckpts = []
    for root, _, files in os.walk(path):
        ckpts += [os.path.join(root, f) for f in files if f.endswith(".ckpt")]
    return ckpts


@pytest.mark.parametrize("env_id", ["dummy_discrete", "dummy_continuous"])
def test_p2e_dv3_exploration(tmp_path, monkeypatch, env_id):
    monkeypatch.chdir(tmp_path)
    run(expl_args(tmp_path, env_id))
    assert find_checkpoints(tmp_path)


@pytest.mark.slow
def test_p2e_dv3_exploration_to_finetuning_roundtrip(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    run(expl_args(tmp_path))
    (ckpt,) = find_checkpoints(tmp_path)
    run(
        ["exp=p2e_dv3_finetuning", "env.id=dummy_discrete", f"log_base_dir={tmp_path}/logs_ft"]
        + TINY
        + [f"checkpoint.exploration_ckpt_path={ckpt}"]
    )
    assert find_checkpoints(f"{tmp_path}/logs_ft")


@pytest.fixture(scope="module")
def exploration_ckpt(tmp_path_factory):
    """One exploration run's checkpoint, for the tests that start from one."""
    tmp_path = tmp_path_factory.mktemp("exploration")
    with pytest.MonkeyPatch.context() as patch:
        patch.chdir(tmp_path)
        run(expl_args(tmp_path))
    (ckpt,) = find_checkpoints(tmp_path)
    return ckpt


def test_p2e_dv3_evaluate_roundtrip(tmp_path, monkeypatch, exploration_ckpt):
    monkeypatch.chdir(tmp_path)
    from sheeprl_tpu.cli import evaluation

    evaluation([f"checkpoint_path={exploration_ckpt}"])


def test_p2e_dv3_finetuning_trains_two_windows_on_donated_parameters(tmp_path, monkeypatch, exploration_ckpt):
    """The finetuning loop runs ``dreamer_v3.make_train_fn``'s step, which
    donates its parameter trees: every tree a window hands in is gone at the
    window's end, the player holds the newest, and the last checkpoint is of
    the last window's weights."""
    import jax
    import numpy as np

    from sheeprl_tpu.algos.p2e_dv3 import p2e_dv3_finetuning as program
    from sheeprl_tpu.utils.checkpoint import load_checkpoint

    monkeypatch.chdir(tmp_path)
    seen = {"steps": [], "player": None}
    real_make, real_build = program.make_train_fn, program.build_agent

    def make_train_fn(*args, **kwargs):
        fn = real_make(*args, **kwargs)

        def train(*a):
            out = fn(*a)
            seen["steps"].append((a[:3], out[:3]))
            return out

        return train

    def build_agent(*args, **kwargs):
        built = real_build(*args, **kwargs)
        seen["player"] = built[-1]
        return built

    monkeypatch.setattr(program, "make_train_fn", make_train_fn)
    monkeypatch.setattr(program, "build_agent", build_agent)
    run(
        ["exp=p2e_dv3_finetuning", "env.id=dummy_discrete", f"log_base_dir={tmp_path}/logs_ft"]
        + [a for a in TINY if a not in ("dry_run=True", "algo.run_test=True")]
        + [f"checkpoint.exploration_ckpt_path={exploration_ckpt}", "dry_run=False", "algo.total_steps=4", "algo.run_test=False", "fabric.devices=1"]
    )
    steps, player = seen["steps"], seen["player"]
    assert len(steps) >= 2  # two updates of two envs, a window each
    for handed, _ in steps:
        assert all(leaf.is_deleted() for leaf in jax.tree.leaves(handed))
    newest = steps[-1][1]
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(newest))
    assert player.wm_params is newest[0] and player.actor_params is newest[1]
    (ckpt,) = find_checkpoints(f"{tmp_path}/logs_ft")
    state = load_checkpoint(ckpt)
    for saved, tree in ((state["world_model"], newest[0]), (state["actor_task"], newest[1]), (state["critic_task"], newest[2])):
        for a, b in zip(jax.tree.leaves(saved), jax.tree.leaves(tree)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
