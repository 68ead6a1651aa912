"""Dreamer-V3 smoke tests (reference: tests/test_algos/test_algos.py::test_dreamer_v3).

One CLI-driven update with tiny nets on dummy envs, exercising the full
pipeline (rollout -> sequential buffer -> fused train step -> checkpoint ->
test) on the 8-device virtual mesh.
"""

import os

import pytest

from sheeprl_tpu.cli import run


def dv3_args(tmp_path, env_id="dummy_discrete"):
    return [
        "exp=dreamer_v3",
        "env=dummy",
        f"env.id={env_id}",
        "dry_run=True",
        "env.capture_video=False",
        "buffer.memmap=False",
        "algo.per_rank_batch_size=1",
        "algo.per_rank_sequence_length=1",
        "buffer.size=8",
        "algo.learning_starts=0",
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
        "algo.run_test=True",
        "checkpoint.save_last=True",
        "metric.log_level=1",
        f"log_base_dir={tmp_path}/logs",
    ]


def find_checkpoints(tmp_path):
    ckpts = []
    for root, _, files in os.walk(tmp_path):
        ckpts += [os.path.join(root, f) for f in files if f.endswith(".ckpt")]
    return ckpts


@pytest.mark.parametrize("env_id", ["dummy_discrete", "dummy_multidiscrete", "dummy_continuous"])
def test_dreamer_v3_dummy(tmp_path, monkeypatch, env_id):
    monkeypatch.chdir(tmp_path)
    run(dv3_args(tmp_path, env_id))
    assert find_checkpoints(tmp_path)


def test_dreamer_v3_mlp_only(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    run(
        dv3_args(tmp_path)
        + ["algo.cnn_keys.encoder=[]", "algo.mlp_keys.encoder=[state]"]
    )


def test_dreamer_v3_model_axis_mesh(tmp_path, monkeypatch):
    """Full CLI run on a 2-D (data=2, model=4) mesh: params shard over the
    model axis (fabric.param_spec rule), the batch over data, GSPMD inserts
    the collectives — SURVEY §2.7 stretch scope the reference lacks."""
    monkeypatch.chdir(tmp_path)
    run(
        dv3_args(tmp_path)
        + [
            # dims divisible by model=4 so kernels genuinely split
            "algo.dense_units=16",
            "algo.world_model.recurrent_model.recurrent_state_size=16",
            "fabric.mesh_axes=[data,model]",
            "fabric.mesh_shape=[2,4]",
            "algo.per_rank_batch_size=2",
        ]
    )
    assert find_checkpoints(tmp_path)


def test_dreamer_v3_fused_pallas_recurrent(tmp_path, monkeypatch):
    """Full train update through the Pallas RSSM-step kernel. The program
    never infers interpreter mode, so on the CPU test mesh the TEST asks for
    it, at the one place the world model builds its fused cell."""
    import functools

    from sheeprl_tpu.algos.dreamer_v3 import agent

    monkeypatch.setattr(
        agent, "FusedRecurrentModel", functools.partial(agent.FusedRecurrentModel, interpret=True)
    )
    monkeypatch.chdir(tmp_path)
    run(dv3_args(tmp_path) + ["algo.world_model.recurrent_model.fused=pallas"])
    assert find_checkpoints(tmp_path)


def test_dreamer_v3_resume(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    run(dv3_args(tmp_path))
    (ckpt,) = find_checkpoints(tmp_path)
    run(dv3_args(tmp_path) + [f"checkpoint.resume_from={ckpt}"])


def test_dreamer_v3_evaluate_roundtrip(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    run(dv3_args(tmp_path))
    (ckpt,) = find_checkpoints(tmp_path)
    from sheeprl_tpu.cli import evaluation

    evaluation([f"checkpoint_path={ckpt}"])


def test_dreamer_v3_device_buffer(tmp_path, monkeypatch):
    """Full update through the HBM-resident replay ring (buffer.device=true;
    on the CPU test backend the ring lives in host memory but exercises the
    same scatter-write/gather/checkpoint code paths as on TPU)."""
    monkeypatch.chdir(tmp_path)
    run(dv3_args(tmp_path) + ["fabric.devices=1", "buffer.device=true"])
    assert find_checkpoints(tmp_path)


def test_dreamer_v3_device_buffer_resume_across_modes(tmp_path, monkeypatch):
    """A checkpoint written by a device-ring run resumes into a host-buffer
    run and vice versa (adapt_restored_buffer)."""
    monkeypatch.chdir(tmp_path)
    run(dv3_args(tmp_path) + ["fabric.devices=1", "buffer.device=true", "buffer.checkpoint=True"])
    (ckpt,) = find_checkpoints(tmp_path)
    # device ckpt -> host run
    run(
        dv3_args(tmp_path)
        + ["fabric.devices=1", "buffer.device=false", "buffer.checkpoint=True", f"checkpoint.resume_from={ckpt}"]
    )
    # newest ckpt (host run) -> device run
    newest = max(find_checkpoints(tmp_path), key=os.path.getmtime)
    run(
        dv3_args(tmp_path)
        + ["fabric.devices=1", "buffer.device=true", "buffer.checkpoint=True", f"checkpoint.resume_from={newest}"]
    )
