"""A2C and DroQ smoke tests (reference: tests/test_algos/test_algos.py)."""

import os

from sheeprl_tpu.cli import run


def find_checkpoints(tmp_path):
    ckpts = []
    for root, _, files in os.walk(tmp_path):
        ckpts += [os.path.join(root, f) for f in files if f.endswith(".ckpt")]
    return ckpts


def a2c_args(tmp_path):
    return [
        "exp=a2c",
        "dry_run=True",
        "env.capture_video=False",
        "buffer.memmap=False",
        "algo.rollout_steps=8",
        "algo.dense_units=8",
        "env.num_envs=2",
        "algo.run_test=True",
        "checkpoint.save_last=True",
        "metric.log_level=1",
        f"log_base_dir={tmp_path}/logs",
    ]


def test_a2c_cartpole(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    run(a2c_args(tmp_path))
    assert find_checkpoints(tmp_path)


def test_a2c_host_pinned_training(tmp_path, monkeypatch):
    """algo.train_device=cpu runs the whole A2C update on the host backend
    (the host-train escape hatch shared with plain PPO) — full run + resume."""
    monkeypatch.chdir(tmp_path)
    args = a2c_args(tmp_path) + ["fabric.devices=1", "algo.train_device=cpu"]
    run(args)
    (ckpt,) = find_checkpoints(tmp_path)
    run(args + [f"checkpoint.resume_from={ckpt}", "fabric.devices=1"])


def test_a2c_continuous(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    run(a2c_args(tmp_path) + ["env.id=Pendulum-v1"])


def test_a2c_evaluate_roundtrip(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    run(a2c_args(tmp_path))
    (ckpt,) = find_checkpoints(tmp_path)
    from sheeprl_tpu.cli import evaluation

    evaluation([f"checkpoint_path={ckpt}"])


def droq_args(tmp_path):
    return [
        "exp=droq",
        "dry_run=True",
        "env.capture_video=False",
        "buffer.memmap=False",
        "algo.per_rank_batch_size=8",
        "algo.hidden_size=16",
        "algo.learning_starts=0",
        "env.num_envs=2",
        "algo.run_test=True",
        "checkpoint.save_last=True",
        "metric.log_level=1",
        f"log_base_dir={tmp_path}/logs",
    ]


def test_droq_pendulum(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    run(droq_args(tmp_path))
    assert find_checkpoints(tmp_path)


def test_droq_resume(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    run(droq_args(tmp_path))
    (ckpt,) = find_checkpoints(tmp_path)
    run(droq_args(tmp_path) + [f"checkpoint.resume_from={ckpt}"])


def test_droq_evaluate_roundtrip(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    run(droq_args(tmp_path))
    (ckpt,) = find_checkpoints(tmp_path)
    from sheeprl_tpu.cli import evaluation

    evaluation([f"checkpoint_path={ckpt}"])


def test_droq_device_buffer(tmp_path, monkeypatch):
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
        ]
    )
