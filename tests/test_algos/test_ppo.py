"""PPO smoke tests (reference: tests/test_algos/test_algos.py::test_ppo).

One full CLI-driven update on tiny nets against dummy/gym envs — the
integration layer of the test pyramid (SURVEY.md §4.1). Runs on the 8-device
virtual CPU mesh from conftest, so the shard_map data-parallel path is
exercised on every test.
"""

import os

import numpy as np
import pytest

from sheeprl_tpu.cli import run


def standard_args(tmp_path):
    return [
        "exp=ppo",
        "dry_run=True",
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


def test_ppo_cartpole_vector(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    run(standard_args(tmp_path))
    assert find_checkpoints(tmp_path)


def test_ppo_host_pinned_training(tmp_path, monkeypatch):
    """algo.train_device=cpu: the whole fused update runs on the host
    backend (the host-train escape hatch, resolve_train_device) — full
    run + resume through the host-jitted no-mesh train path."""
    monkeypatch.chdir(tmp_path)
    args = standard_args(tmp_path) + ["fabric.devices=1", "algo.train_device=cpu"]
    run(args)
    (ckpt,) = find_checkpoints(tmp_path)
    run(args + [f"checkpoint.resume_from={ckpt}", "fabric.devices=1"])


def test_ppo_dummy_discrete_pixels(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    run(
        standard_args(tmp_path)
        + [
            "env=dummy",
            "env.id=dummy_discrete",
            "env.screen_size=36",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.mlp_keys.encoder=[state]",
        ]
    )


def test_ppo_dummy_continuous(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    run(
        standard_args(tmp_path)
        + [
            "env=dummy",
            "env.id=dummy_continuous",
            "env.screen_size=36",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.mlp_keys.encoder=[state]",
        ]
    )


def test_ppo_dummy_multidiscrete(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    run(
        standard_args(tmp_path)
        + [
            "env=dummy",
            "env.id=dummy_multidiscrete",
            "env.screen_size=36",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.mlp_keys.encoder=[]",
        ]
    )


def test_ppo_frame_stack(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    run(
        standard_args(tmp_path)
        + [
            "env=dummy",
            "env.id=dummy_discrete",
            "env.screen_size=36",
            "env.frame_stack=2",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.mlp_keys.encoder=[]",
        ]
    )


def test_ppo_resume_from_checkpoint(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    run(standard_args(tmp_path))
    (ckpt,) = find_checkpoints(tmp_path)
    run(standard_args(tmp_path) + [f"checkpoint.resume_from={ckpt}"])


def test_ppo_resume_env_mismatch_raises(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    run(standard_args(tmp_path))
    (ckpt,) = find_checkpoints(tmp_path)
    with pytest.raises(ValueError, match="different environment"):
        run(standard_args(tmp_path) + [f"checkpoint.resume_from={ckpt}", "env.id=Acrobot-v1"])


def test_ppo_evaluate_roundtrip(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    run(standard_args(tmp_path))
    (ckpt,) = find_checkpoints(tmp_path)
    from sheeprl_tpu.cli import evaluation

    evaluation([f"checkpoint_path={ckpt}"])


def test_ppo_evaluate_group_override(tmp_path, monkeypatch):
    """`fabric=cpu` on the eval CLI must re-compose the fabric group (hydra
    semantics), not overwrite cfg.fabric with the bare string."""
    monkeypatch.chdir(tmp_path)
    run(standard_args(tmp_path))
    (ckpt,) = find_checkpoints(tmp_path)
    from sheeprl_tpu.cli import evaluation

    evaluation([f"checkpoint_path={ckpt}", "fabric=cpu"])


def test_ppo_unknown_algo_error(tmp_path):
    with pytest.raises(ValueError, match="no registered algorithm"):
        run(standard_args(tmp_path) + ["algo.name=not_an_algo"])


def test_ppo_telemetry_smoke(tmp_path, monkeypatch):
    """One tiny CPU update with metric.telemetry.enabled=True: the run must
    leave a telemetry.jsonl whose span names match the timer metric keys and
    that carries compile/device_poll/heartbeat events, and tools/report.py must be
    able to compute SPS from it without log scraping (ISSUE acceptance)."""
    import json
    import sys

    monkeypatch.chdir(tmp_path)
    run(
        standard_args(tmp_path)
        + ["metric.telemetry.enabled=True", "metric.telemetry.poll_interval=0.0"]
    )

    jsonls = []
    for root, _, files in os.walk(tmp_path):
        jsonls += [os.path.join(root, f) for f in files if f == "telemetry.jsonl"]
    assert len(jsonls) == 1, f"expected exactly one telemetry.jsonl, found {jsonls}"
    events = [json.loads(line) for line in open(jsonls[0]) if line.strip()]

    kinds = {e["event"] for e in events}
    assert {"run_start", "span", "compile", "device_poll", "heartbeat", "run_end"} <= kinds
    for e in events:
        assert {"event", "t", "step", "process_index"} <= set(e)

    # span names ARE the timer metric keys — the loop's two timed sections
    span_names = {e["name"] for e in events if e["event"] == "span"}
    assert {"Time/env_interaction_time", "Time/train_time"} <= span_names

    # tools/report.py digests the stream without touching the run's logs
    from tools import report

    summary = report.telemetry_summary(jsonls[0])
    assert summary["sps_env"] > 0
    assert summary["sps_train"] > 0
    assert summary["compiles"] >= 1
    assert summary["device_polls"] >= 1
    hb = [e for e in events if e["event"] == "heartbeat"][-1]
    # MFU numerator: the AOT cost analysis of the fused train step landed
    assert hb.get("flops_per_train_step", 0) > 0
    assert hb.get("train_flops_per_sec", 0) > 0


def test_ppo_host_train_keeps_params_alive(tmp_path):
    """Host-pinned train path donation invariant (ISSUE satellite): the
    player aliases the params buffers, so train_fn must donate ONLY
    opt_state — after one update the old params must still be readable and
    the old opt_state must be deleted."""
    import gymnasium as gym
    import jax
    import numpy as np
    import optax

    from sheeprl_tpu.algos.ppo.agent import build_agent
    from sheeprl_tpu.algos.ppo.ppo import make_train_fn
    from sheeprl_tpu.config.compose import compose, instantiate
    from sheeprl_tpu.parallel.fabric import put_tree
    from sheeprl_tpu.utils.utils import dotdict

    cfg = dotdict(
        compose(
            "config",
            [
                "exp=ppo",
                "dry_run=True",
                "fabric.devices=1",
                "algo.rollout_steps=8",
                "algo.per_rank_batch_size=4",
                "algo.update_epochs=1",
                "algo.dense_units=8",
                "algo.mlp_layers=1",
                "algo.encoder.mlp_features_dim=8",
                "algo.encoder.cnn_features_dim=16",
                "env.num_envs=1",
                f"log_base_dir={tmp_path}/logs",
            ],
        )
    )
    fabric_cfg = dict(cfg.fabric.to_dict())
    fabric_cfg.pop("callbacks", None)
    fabric = instantiate({**fabric_cfg, "callbacks": []})
    obs_space = gym.spaces.Dict({"state": gym.spaces.Box(-np.inf, np.inf, (4,), np.float32)})
    agent, params = build_agent(fabric, (2,), False, cfg, obs_space, None)

    host = jax.devices("cpu")[0]
    params = put_tree(jax.device_get(params), host)
    tx = optax.adam(1e-3)
    opt_state = put_tree(jax.device_get(tx.init(params)), host)
    train_fn = make_train_fn(fabric, agent, tx, cfg, ["state"], n_local=8, host_device=host)

    rng = np.random.default_rng(0)
    onehot = np.eye(2, dtype=np.float32)[rng.integers(0, 2, size=8)]
    flat = {
        "state": rng.normal(size=(8, 4)).astype(np.float32),
        "actions": onehot,
        "logprobs": np.full((8, 1), -0.7, np.float32),
        "values": np.zeros((8, 1), np.float32),
        "returns": np.ones((8, 1), np.float32),
        "advantages": rng.normal(size=(8, 1)).astype(np.float32),
    }
    new_params, new_opt_state, metrics = train_fn(
        params, opt_state, flat, jax.random.PRNGKey(0), np.float32(0.2), np.float32(0.0)
    )
    jax.block_until_ready((new_params, new_opt_state, metrics))

    # the invariant: params buffers survive the update (the host player
    # keeps serving rollouts from them) ...
    jax.tree.map(np.asarray, params)
    # ... while opt_state really was donated (the memory win stays)
    with pytest.raises(RuntimeError, match="deleted"):
        jax.tree.map(np.asarray, opt_state)
