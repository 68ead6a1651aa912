"""Plan2Explore on Dreamer-V3 — finetuning phase (reference:
sheeprl/algos/p2e_dv3/p2e_dv3_finetuning.py:27-475) — TPU-native.

Loads the exploration checkpoint, then runs the plain fused Dreamer-V3 train
step (this repo's ``dreamer_v3.make_train_fn``) on the task models. The
player acts with the EXPLORATION actor until the first gradient step, then
switches to the task actor (reference :348-352). Model/shape hyperparameters
are forced to the exploration run's values (reference :46-69, plus the env
fields handled by the CLI, cli.py:108-139).
"""

from __future__ import annotations

import os
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P  # noqa: F401 (kept for parity with sibling entrypoints)

from sheeprl_tpu.ops.optim import build_tx
from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import METRIC_ORDER, make_train_fn
from sheeprl_tpu.algos.p2e_dv3.agent import build_agent
from sheeprl_tpu.algos.p2e_dv3.utils import AGGREGATOR_KEYS_FINETUNING, prepare_obs, test
from sheeprl_tpu.data.device_buffer import (
    DeviceReplayBuffer,
    adapt_restored_buffer,
    make_sequential_replay,
)
from sheeprl_tpu.data.prefetch import sampled_batches
from sheeprl_tpu.envs import build_vector_env
from sheeprl_tpu.ops.math import MomentsState, init_moments
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, save_configs


@register_algorithm()
def main(fabric, cfg: Dict[str, Any], exploration_cfg: Dict[str, Any]):
    resume_from_checkpoint = bool(cfg.checkpoint.resume_from)
    if resume_from_checkpoint:
        state = fabric.load(cfg.checkpoint.resume_from)
    else:
        state = fabric.load(cfg.checkpoint.exploration_ckpt_path)

    # all model hyperparameters must match the exploration phase
    # (reference :46-69)
    for k in (
        "gamma",
        "lmbda",
        "horizon",
        "dense_units",
        "mlp_layers",
        "unimix",
        "hafner_initialization",
        "world_model",
        "actor",
        "critic",
        "cnn_keys",
        "mlp_keys",
    ):
        if k in exploration_cfg.algo:
            cfg.algo[k] = exploration_cfg.algo[k]
    cfg.env.clip_rewards = exploration_cfg.env.clip_rewards
    if cfg.buffer.get("load_from_exploration") and exploration_cfg.buffer.checkpoint:
        cfg.env.num_envs = exploration_cfg.env.num_envs
    cfg.env.frame_stack = 1

    log_dir = get_log_dir(cfg)
    logger = get_logger(cfg, log_dir)
    fabric.logger = logger
    logger.log_hyperparams(cfg.to_dict() if hasattr(cfg, "to_dict") else dict(cfg))
    print(f"Log dir: {log_dir}")

    rank = fabric.process_index
    num_envs = int(cfg.env.num_envs)
    world_size = fabric.data_parallel_size  # batch-split width: the data axis (= device count on a 1-D mesh)
    num_processes = fabric.num_processes

    envs = build_vector_env(cfg, rank, log_dir if rank == 0 else None, "train", restart_on_exception=True)
    action_space = envs.single_action_space
    observation_space = envs.single_observation_space
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")

    is_continuous = isinstance(action_space, gym.spaces.Box)
    is_multidiscrete = isinstance(action_space, gym.spaces.MultiDiscrete)
    actions_dim = tuple(
        action_space.shape if is_continuous else (action_space.nvec.tolist() if is_multidiscrete else [action_space.n])
    )
    clip_rewards_fn = (lambda r: np.tanh(r)) if cfg.env.clip_rewards else (lambda r: r)

    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    obs_keys = cnn_keys + mlp_keys

    (
        wm,
        wm_params,
        actor,
        actor_task_params,
        critic,
        critic_task_params,
        target_critic_task_params,
        actor_expl_params,
        _critics_exploration,
        _ensemble,
        _ensembles_params,
        player,
    ) = build_agent(
        fabric,
        actions_dim,
        is_continuous,
        cfg,
        observation_space,
        state["world_model"],
        None,
        state["actor_task"],
        state["critic_task"],
        state["target_critic_task"],
        state["actor_exploration"],
    )

    world_tx = build_tx(cfg.algo.world_model.optimizer, cfg.algo.world_model.clip_gradients)
    actor_tx = build_tx(cfg.algo.actor.optimizer, cfg.algo.actor.clip_gradients)
    critic_tx = build_tx(cfg.algo.critic.optimizer, cfg.algo.critic.clip_gradients)
    world_opt = fabric.replicate(jax.tree.map(jnp.asarray, state["world_optimizer"]))
    actor_opt = fabric.replicate(jax.tree.map(jnp.asarray, state["actor_task_optimizer"]))
    critic_opt = fabric.replicate(jax.tree.map(jnp.asarray, state["critic_task_optimizer"]))
    moments_state: MomentsState = init_moments()
    if "moments_task" in state:
        moments_state = MomentsState(
            low=jnp.asarray(state["moments_task"]["low"]), high=jnp.asarray(state["moments_task"]["high"])
        )
    # committed to the mesh like the rest of the train state, or window 2
    # compiles a SECOND executable of the whole step (see dreamer_v3.main)
    moments_state = fabric.replicate(moments_state)

    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = MetricAggregator(cfg.metric.get("aggregator", {}).get("metrics", {}) or {})
    for k in AGGREGATOR_KEYS_FINETUNING - set(aggregator.metrics):
        aggregator.add(k, "mean")

    buffer_size = cfg.buffer.size // int(num_envs * num_processes) if not cfg.dry_run else 4
    rb = make_sequential_replay(
        cfg,
        fabric,
        observation_space,
        actions_dim,
        buffer_size,
        num_envs,
        obs_keys,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}"),
        seed=cfg.seed,
    )
    if (resume_from_checkpoint and cfg.buffer.checkpoint) or (
        cfg.buffer.get("load_from_exploration") and exploration_cfg.buffer.checkpoint
    ):
        from sheeprl_tpu.utils.checkpoint import select_buffer

        rb = adapt_restored_buffer(
            select_buffer(state["rb"], rank, num_processes),
            isinstance(rb, DeviceReplayBuffer),
            seed=cfg.seed,
            memmap=cfg.buffer.memmap,
            memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}"),
        )

    @jax.jit
    def ema(cp, tcp, tau):
        return jax.tree.map(lambda c, t: tau * c + (1 - tau) * t, cp, tcp)

    # the step donates its parameter trees (``dreamer_v3.make_train_fn``), and
    # this loop holds to the rule that allows it: the player is handed the
    # newest trees right behind a window's last dispatch and enqueues nothing
    # inside a window, the target refresh is a program of its own enqueued
    # before the step that follows it, and a checkpoint reads the bindings of
    # after the window
    train_fn = make_train_fn(
        fabric, wm, actor, critic, world_tx, actor_tx, critic_tx, cfg, is_continuous, actions_dim
    )

    train_step = 0
    last_train = 0
    start_step = state["update"] + 1 if resume_from_checkpoint else 1
    policy_step = state["update"] * num_envs * num_processes if resume_from_checkpoint else 0
    last_log = state["last_log"] if resume_from_checkpoint else 0
    last_checkpoint = state["last_checkpoint"] if resume_from_checkpoint else 0
    policy_steps_per_update = int(num_envs * num_processes)
    num_updates = int(cfg.algo.total_steps // policy_steps_per_update) if not cfg.dry_run else 1
    learning_starts = cfg.algo.learning_starts // policy_steps_per_update if not cfg.dry_run else 0
    per_rank_batch_size = int(cfg.algo.per_rank_batch_size)
    sequence_length = int(cfg.algo.per_rank_sequence_length)
    if resume_from_checkpoint:
        from sheeprl_tpu.utils.checkpoint import elastic_per_rank_batch_size

        per_rank_batch_size = elastic_per_rank_batch_size(state["batch_size"], world_size)
        if not cfg.buffer.checkpoint:
            learning_starts += start_step

    ratio = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)
    if resume_from_checkpoint:
        ratio.load_state_dict(state["ratio"])

    key = jax.random.PRNGKey(int(cfg.seed))
    if resume_from_checkpoint and "rng_key" in state:
        key = jnp.asarray(state["rng_key"])
    # action keys live on the player's device so a host-pinned player
    # never blocks on a chip round trip per env step
    from sheeprl_tpu.parallel.fabric import put_tree as _put_tree

    player_key = _put_tree(jax.random.fold_in(key, 1), player.device)
    if cfg.checkpoint.resume_from and "player_rng_key" in state:
        # continue the pre-resume action-sampling stream
        player_key = _put_tree(jnp.asarray(state["player_rng_key"]), player.device)

    step_data: Dict[str, np.ndarray] = {}
    obs, _ = envs.reset(seed=cfg.seed)
    prepared = prepare_obs(obs, cnn_keys=cnn_keys, num_envs=num_envs)
    for k in obs_keys:
        step_data[k] = prepared[k][np.newaxis]
    step_data["rewards"] = np.zeros((1, num_envs, 1), np.float32)
    step_data["truncated"] = np.zeros((1, num_envs, 1), np.float32)
    step_data["terminated"] = np.zeros((1, num_envs, 1), np.float32)
    step_data["is_first"] = np.ones_like(step_data["terminated"])
    player.init_states()

    # the player explores with the exploration actor until the first
    # gradient step (reference :348-352)
    player_actor_type = "exploration"
    player.actor_params = actor_expl_params

    cumulative_per_rank_gradient_steps = 0
    for update in range(start_step, num_updates + 1):
        policy_step += num_envs * num_processes

        with timer("Time/env_interaction_time"):
            player_key, action_key = jax.random.split(player_key)
            prepared = prepare_obs(obs, cnn_keys=cnn_keys, num_envs=num_envs)
            mask = {k: v for k, v in prepared.items() if k.startswith("mask")}
            actions = player.get_actions(prepared, action_key, mask=mask or None)
            if is_continuous:
                real_actions = actions
            else:
                splits = np.cumsum(actions_dim)[:-1]
                real_actions = np.stack(
                    [p.argmax(-1) for p in np.split(actions, splits, axis=-1)], axis=-1
                )
                if real_actions.shape[-1] == 1 and not is_multidiscrete:
                    real_actions = real_actions[..., 0]

            step_data["actions"] = np.asarray(actions, np.float32).reshape(1, num_envs, -1)
            rb.add(step_data, validate_args=cfg.buffer.validate_args)

            next_obs, rewards, terminated, truncated, infos = envs.step(
                real_actions.reshape(envs.action_space.shape)
            )
            dones = np.logical_or(terminated, truncated).astype(np.uint8)

        step_data["is_first"] = np.zeros_like(step_data["terminated"])
        if "restart_on_exception" in infos:
            for i, roe in enumerate(np.asarray(infos["restart_on_exception"]).reshape(-1)):
                if roe and not dones[i]:
                    if isinstance(rb, DeviceReplayBuffer):
                        rb.amend_last(i, terminated=0.0, truncated=1.0, is_first=0.0)
                    else:
                        sub = rb.buffer[i]
                        last_idx = (sub._pos - 1) % sub.buffer_size
                        sub["terminated"][last_idx] = 0.0
                        sub["truncated"][last_idx] = 1.0
                        sub["is_first"][last_idx] = 0.0
                    step_data["is_first"][0, i] = 1.0

        if cfg.metric.log_level > 0 and "final_info" in infos:
            ep = infos["final_info"].get("episode")
            if ep is not None:
                for i in np.nonzero(ep.get("_r", []))[0]:
                    aggregator.update("Rewards/rew_avg", float(ep["r"][i]))
                    aggregator.update("Game/ep_len_avg", float(ep["l"][i]))
                    print(f"Rank-0: policy_step={policy_step}, reward_env_{i}={ep['r'][i]}")

        real_next_obs = {k: np.asarray(v).copy() for k, v in next_obs.items()}
        if "final_obs" in infos:
            for idx, final_obs in enumerate(infos["final_obs"]):
                if final_obs is not None:
                    for k, v in final_obs.items():
                        real_next_obs[k][idx] = v

        prepared_next = prepare_obs(next_obs, cnn_keys=cnn_keys, num_envs=num_envs)
        for k in obs_keys:
            step_data[k] = prepared_next[k][np.newaxis]
        obs = next_obs

        rewards = np.asarray(rewards, np.float32).reshape(1, num_envs, 1)
        step_data["terminated"] = np.asarray(terminated, np.float32).reshape(1, num_envs, 1)
        step_data["truncated"] = np.asarray(truncated, np.float32).reshape(1, num_envs, 1)
        step_data["rewards"] = clip_rewards_fn(rewards)

        dones_idxes = dones.nonzero()[0].tolist()
        if dones_idxes:
            prepared_final = prepare_obs(
                {k: real_next_obs[k][dones_idxes] for k in obs_keys},
                cnn_keys=cnn_keys,
                num_envs=len(dones_idxes),
            )
            reset_data = {k: prepared_final[k][np.newaxis] for k in obs_keys}
            reset_data["terminated"] = step_data["terminated"][:, dones_idxes]
            reset_data["truncated"] = step_data["truncated"][:, dones_idxes]
            reset_data["actions"] = np.zeros((1, len(dones_idxes), int(np.sum(actions_dim))), np.float32)
            reset_data["rewards"] = step_data["rewards"][:, dones_idxes]
            reset_data["is_first"] = np.zeros_like(reset_data["terminated"])
            rb.add(reset_data, dones_idxes, validate_args=cfg.buffer.validate_args)

            step_data["rewards"][:, dones_idxes] = 0.0
            step_data["terminated"][:, dones_idxes] = 0.0
            step_data["truncated"][:, dones_idxes] = 0.0
            step_data["is_first"][:, dones_idxes] = 1.0
            player.init_states(dones_idxes)

        # ---------------- training ---------------- #
        if update >= learning_starts:
            per_rank_gradient_steps = ratio(policy_step / num_processes)
            if per_rank_gradient_steps > 0:
                if player_actor_type != "task":
                    player_actor_type = "task"
                    player.actor_params = actor_task_params
                # batch i+1's host->HBM transfer overlaps gradient step i
                batches = sampled_batches(
                    rb,
                    per_rank_batch_size * fabric.local_data_parallel_size,
                    sequence_length,
                    per_rank_gradient_steps,
                    cnn_keys,
                    fabric,
                    prefetch=int(cfg.buffer.get("prefetch", 0) or 0),
                )
                with timer("Time/train_time"):
                    for i, batch in enumerate(batches):
                        if (
                            cumulative_per_rank_gradient_steps
                            % cfg.algo.critic.per_rank_target_network_update_freq
                            == 0
                        ):
                            tau = 1.0 if cumulative_per_rank_gradient_steps == 0 else float(cfg.algo.critic.tau)
                            target_critic_task_params = ema(critic_task_params, target_critic_task_params, tau)
                        key, train_key = jax.random.split(key)
                        (
                            wm_params,
                            actor_task_params,
                            critic_task_params,
                            world_opt,
                            actor_opt,
                            critic_opt,
                            moments_state,
                            metrics,
                        ) = train_fn(
                            wm_params,
                            actor_task_params,
                            critic_task_params,
                            target_critic_task_params,
                            world_opt,
                            actor_opt,
                            critic_opt,
                            moments_state,
                            batch,
                            train_key,
                        )
                        cumulative_per_rank_gradient_steps += 1
                    metrics = np.asarray(jax.device_get(metrics))
                    train_step += num_processes
                # the trees the player held went into the window's steps: it gets
                # the newest before anything else touches it. Non-blocking in
                # host-player mode: the trees stream through the async pipe and
                # flip a block or two later (fabric.stream_attr)
                player.stream_attr("wm_params", wm_params)
                player.stream_attr("actor_params", actor_task_params)
                if cfg.metric.log_level > 0:
                    for name, value in zip(METRIC_ORDER, metrics):
                        aggregator.update(name, float(value))

        # ---------------- logging ---------------- #
        if cfg.metric.log_level > 0 and (policy_step - last_log >= cfg.metric.log_every or update == num_updates):
            metrics_dict = aggregator.compute()
            logger.log_metrics(metrics_dict, policy_step)
            aggregator.reset()
            if policy_step > 0:
                logger.log_metrics(
                    {"Params/replay_ratio": cumulative_per_rank_gradient_steps * num_processes / policy_step},
                    policy_step,
                )
            if not timer.disabled:
                timer_metrics = timer.compute()
                if timer_metrics.get("Time/train_time"):
                    logger.log_metrics(
                        {"Time/sps_train": (train_step - last_train) / timer_metrics["Time/train_time"]},
                        policy_step,
                    )
                if timer_metrics.get("Time/env_interaction_time"):
                    logger.log_metrics(
                        {
                            "Time/sps_env_interaction": (
                                (policy_step - last_log) / num_processes * cfg.env.action_repeat
                            )
                            / timer_metrics["Time/env_interaction_time"]
                        },
                        policy_step,
                    )
                timer.reset()
            last_log = policy_step
            last_train = train_step

        # ---------------- checkpoint ---------------- #
        if (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every) or (
            update == num_updates and cfg.checkpoint.save_last
        ):
            last_checkpoint = policy_step
            ckpt_state = {
                "world_model": jax.device_get(wm_params),
                "actor_task": jax.device_get(actor_task_params),
                "critic_task": jax.device_get(critic_task_params),
                "target_critic_task": jax.device_get(target_critic_task_params),
                "world_optimizer": jax.device_get(world_opt),
                "actor_task_optimizer": jax.device_get(actor_opt),
                "critic_task_optimizer": jax.device_get(critic_opt),
                "actor_exploration": jax.device_get(actor_expl_params),
                "moments_task": {
                    "low": np.asarray(jax.device_get(moments_state.low)),
                    "high": np.asarray(jax.device_get(moments_state.high)),
                },
                "ratio": ratio.state_dict(),
                "update": update,
                "batch_size": per_rank_batch_size * world_size,
                "last_log": last_log,
                "last_checkpoint": last_checkpoint,
                "rng_key": jax.device_get(key),
                "player_rng_key": jax.device_get(player_key),
            }
            ckpt_path = os.path.join(log_dir, "checkpoint", f"ckpt_{policy_step}_{rank}.ckpt")
            fabric.call(
                "on_checkpoint_coupled",
                ckpt_path=ckpt_path,
                state=ckpt_state,
                replay_buffer=rb if cfg.buffer.checkpoint else None,
            )

    # land any in-flight async param stream so the final evaluation and
    # model registration use the last update's weights
    player.flush_stream_attrs()
    envs.close()
    # task test few-shot (reference :458-462)
    if fabric.is_global_zero and cfg.algo.run_test:
        player.actor_params = actor_task_params
        test(player, fabric, cfg, log_dir, "few-shot", greedy=False)
    logger.finalize()
