"""A2C (reference: sheeprl/algos/a2c/a2c.py:25-361) — TPU-native.

The PPO skeleton without clipping: one gradient step per update over the
whole rollout. The reference emulates a full-batch gradient by accumulating
minibatch backward passes with ``no_backward_sync`` (a2c.py:62-96); here the
sum/mean reduction over the sharded rollout inside one jitted shard_map step
IS that accumulation — a gradient ``pmean`` replaces the final DDP sync.
"""

from __future__ import annotations

import os
from functools import partial
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from sheeprl_tpu.parallel.shard_map import shard_map
from jax.sharding import PartitionSpec as P

from sheeprl_tpu.algos.a2c.agent import build_agent
from sheeprl_tpu.algos.a2c.loss import policy_loss, value_loss
from sheeprl_tpu.algos.a2c.utils import AGGREGATOR_KEYS, prepare_obs, test
from sheeprl_tpu.algos.ppo.agent import PPOPlayer, evaluate_actions, rollout_step
from sheeprl_tpu.algos.ppo.ppo import (
    resolve_fused_rollout_spec,
    resolve_scenario_family,
    scenario_theta_matrix,
)
from sheeprl_tpu.config.compose import instantiate
from sheeprl_tpu.envs.variants import ScenarioFamily
from sheeprl_tpu.parallel.fabric import put_tree, resolve_player_device, resolve_train_device
from sheeprl_tpu.envs import build_vector_env
from sheeprl_tpu.obs import (
    log_sps_and_heartbeat,
    telemetry_advance,
    telemetry_mark_warm,
    telemetry_register_flops,
    telemetry_run_metrics,
    telemetry_train_window,
)
from sheeprl_tpu.ops.math import gae
from sheeprl_tpu.ops.rollout_scan import ENV_STREAM_SALT, init_env_carry, make_onpolicy_superstep_fn
from sheeprl_tpu.ops.superstep import fused_fallback, reset_fused_fallback_warnings
from sheeprl_tpu.resilience import RunResilience
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.prealloc import RolloutStore
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import save_configs


def make_local_train(fabric, agent, tx, cfg, obs_keys, *, use_mesh: bool):
    """The UNJITTED one-gradient-step update body (A2C has no epochs or
    minibatches — the whole-rollout mean IS the reference's accumulated
    full-batch gradient).  ``use_mesh`` guards the collectives so the same
    body serves the shard_map'd update and the single-device escape hatch."""
    reduction = str(cfg.algo.loss_reduction)
    data_axis = fabric.data_axis

    def local_train(params, opt_state, data):
        def loss_fn(p):
            obs = {k: data[k] for k in obs_keys}
            logprobs, _, values = evaluate_actions(agent, p, obs, data["actions"])
            pg = policy_loss(logprobs, data["advantages"], reduction)
            v = value_loss(values, data["returns"], reduction)
            return pg + v, (pg, v)

        (_, (pg, v)), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        if use_mesh:
            grads = lax.pmean(grads, data_axis)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        metrics = jnp.stack([pg, v])
        if use_mesh:
            metrics = lax.pmean(metrics, data_axis)
        return params, opt_state, metrics

    return local_train


def make_fused_local_train(fabric, agent, tx, cfg, obs_keys, *, use_mesh: bool):
    """Adapt the A2C update body to the fused superstep's ``local_train``
    contract (``ops/rollout_scan.py``): A2C's single full-batch gradient step
    needs neither the train key nor the clip/entropy coefficients, so they
    are accepted and dropped."""
    local_train = make_local_train(fabric, agent, tx, cfg, obs_keys, use_mesh=use_mesh)

    def fused_local_train(params, opt_state, data, key, clip_coef, ent_coef):
        del key, clip_coef, ent_coef
        return local_train(params, opt_state, data)

    return fused_local_train


def make_train_fn(fabric, agent, tx, cfg, obs_keys):
    multi_device = fabric.world_size > 1
    local_train = make_local_train(fabric, agent, tx, cfg, obs_keys, use_mesh=multi_device)
    if multi_device:
        train_fn = shard_map(
            local_train,
            mesh=fabric.mesh,
            in_specs=(P(), P(), P(fabric.data_axis)),
            out_specs=(P(), P(), P()),
        )
    else:
        train_fn = local_train
    return jax.jit(train_fn, donate_argnums=(0, 1))


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    if cfg.checkpoint.resume_from:
        state = fabric.load(cfg.checkpoint.resume_from)

    if len(cfg.algo.cnn_keys.encoder) > 0:
        import warnings

        warnings.warn("A2C is vector-only; the CNN keys will be ignored")
        cfg.algo.cnn_keys.encoder = []

    log_dir = get_log_dir(cfg)
    logger = get_logger(cfg, log_dir)
    fabric.logger = logger
    logger.log_hyperparams(cfg.to_dict() if hasattr(cfg, "to_dict") else dict(cfg))
    print(f"Log dir: {log_dir}")
    resil = RunResilience(fabric, cfg, log_dir)

    rank = fabric.process_index
    num_envs = int(cfg.env.num_envs)
    world_size = fabric.data_parallel_size  # batch-split width: the data axis (= device count on a 1-D mesh)
    num_processes = fabric.num_processes

    envs = build_vector_env(cfg, rank, log_dir if rank == 0 else None, "train")
    observation_space = envs.single_observation_space
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    obs_keys = mlp_keys
    if not obs_keys:
        raise RuntimeError("You should specify at least one MLP key for the encoder: `mlp_keys.encoder=[state]`")

    is_continuous = isinstance(envs.single_action_space, gym.spaces.Box)
    is_multidiscrete = isinstance(envs.single_action_space, gym.spaces.MultiDiscrete)
    actions_dim = tuple(
        envs.single_action_space.shape
        if is_continuous
        else (envs.single_action_space.nvec.tolist() if is_multidiscrete else [envs.single_action_space.n])
    )

    # scenario variants ride the fused rollout only (same contract as PPO);
    # `distractors` widens the observation the agent is built against
    # resolved unconditionally: enabled variants with the fused path off must
    # hit the loud RuntimeError below, never silently train the base env
    scenario_family = resolve_scenario_family(cfg)
    obs_widened = False
    if scenario_family is not None and len(mlp_keys) == 1:
        k0 = mlp_keys[0]
        if tuple(observation_space[k0].shape) != (scenario_family.obs_dim,):
            spaces_d = dict(observation_space.spaces)
            spaces_d[k0] = gym.spaces.Box(-np.inf, np.inf, (scenario_family.obs_dim,), np.float32)
            observation_space = gym.spaces.Dict(spaces_d)
            obs_widened = True

    agent, params = build_agent(
        fabric,
        actions_dim,
        is_continuous,
        cfg,
        observation_space,
        state["agent"] if cfg.checkpoint.resume_from else None,
    )
    player = PPOPlayer(
        agent, params, device=resolve_player_device(cfg.algo.get("player_device", "auto"))
    )

    rollout_steps = int(cfg.algo.rollout_steps)
    policy_steps_per_update = num_envs * rollout_steps * num_processes
    num_updates = int(cfg.algo.total_steps) // policy_steps_per_update if not cfg.dry_run else 1

    opt_cfg = dict(cfg.algo.optimizer.to_dict() if hasattr(cfg.algo.optimizer, "to_dict") else cfg.algo.optimizer)
    if cfg.algo.max_grad_norm and float(cfg.algo.max_grad_norm) > 0:
        opt_cfg["max_grad_norm"] = float(cfg.algo.max_grad_norm)
    tx = instantiate(opt_cfg)
    # host-train escape hatch (same as plain PPO, resolve_train_device): when
    # the dispatch round trip is above 5 ms a tiny model's update runs on the
    # host core — the single-device train program has no mesh collectives, so
    # committing params/opt/batch to the host is all it takes
    train_device = resolve_train_device(
        cfg.algo.get("train_device", "auto"), params, fabric.world_size
    )
    if train_device is not None:
        params = put_tree(jax.device_get(params), train_device)
        player.update_params(params)
    opt_state = state["opt_state"] if cfg.checkpoint.resume_from else tx.init(params)
    opt_state = (
        put_tree(opt_state, train_device) if train_device is not None else fabric.replicate(opt_state)
    )

    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = MetricAggregator(cfg.metric.get("aggregator", {}).get("metrics", {}) or {})
    for k in AGGREGATOR_KEYS - set(aggregator.metrics):
        aggregator.add(k, "mean")

    n_global = rollout_steps * num_envs * num_processes
    if n_global % world_size != 0:
        raise ValueError(
            f"rollout_steps*num_envs*processes ({n_global}) must be divisible by the device count ({world_size})"
        )
    train_fn = make_train_fn(fabric, agent, tx, cfg, obs_keys)
    gae_fn = jax.jit(partial(gae, gamma=float(cfg.algo.gamma), gae_lambda=float(cfg.algo.gae_lambda)))

    # fused on-policy collection (`algo.fused_rollout`, ported from PPO): the
    # T-step rollout, GAE and A2C's single full-batch gradient step compile
    # into ONE donated jit — one dispatch per update instead of T+3
    fused_rollout = bool(cfg.algo.get("fused_rollout", False))
    reset_fused_fallback_warnings()
    fused_spec = None
    if fused_rollout:
        fused_spec = resolve_fused_rollout_spec(
            cfg, fabric, [], mlp_keys, observation_space, is_continuous, is_multidiscrete, actions_dim
        )
        if fused_spec is not None and train_device is None and num_envs % world_size != 0:
            fused_fallback(
                "env_shard", f"env.num_envs ({num_envs}) must be divisible by the device count ({world_size})"
            )
            fused_spec = None
    if scenario_family is not None and fused_spec is None:
        raise RuntimeError(
            "env.variants requires the fused rollout path; set "
            "algo.fused_rollout=True (if it is set, the fused_fallback "
            "telemetry event names the gate that failed)"
        )
    superstep_fn = None
    if fused_spec is not None:
        use_mesh_fused = train_device is None
        superstep_fn = make_onpolicy_superstep_fn(
            fused_spec,
            policy_fn=partial(rollout_step, agent),
            value_fn=lambda p, o: agent.apply(p, o)[1],
            local_train=make_fused_local_train(fabric, agent, tx, cfg, obs_keys, use_mesh=use_mesh_fused),
            obs_key=mlp_keys[0],
            rollout_steps=rollout_steps,
            step_increment=num_envs * num_processes,
            gamma=float(cfg.algo.gamma),
            gae_lambda=float(cfg.algo.gae_lambda),
            mesh=fabric.mesh if use_mesh_fused else None,
            data_axis=fabric.data_axis if use_mesh_fused else None,
        )

    start_update = (state["update"] + 1) if cfg.checkpoint.resume_from else 1
    policy_step = state["update"] * policy_steps_per_update if cfg.checkpoint.resume_from else 0
    last_log = state["last_log"] if cfg.checkpoint.resume_from else 0
    last_checkpoint = state["last_checkpoint"] if cfg.checkpoint.resume_from else 0
    train_step = 0
    last_train = 0

    key = jax.random.PRNGKey(int(cfg.seed))
    # action keys live on the player's device so a host-pinned player
    # never blocks on a chip round trip per env step
    player_key = put_tree(jax.random.fold_in(key, 1), player.device)
    next_obs, _ = envs.reset(seed=cfg.seed)
    next_obs = prepare_obs(next_obs, num_envs=num_envs)

    def ckpt_state_fn(completed_update: int) -> Dict[str, Any]:
        return {
            "agent": jax.device_get(params),
            "opt_state": jax.device_get(opt_state),
            "update": completed_update,
            "batch_size": int(cfg.algo.per_rank_batch_size) * world_size,
            "last_log": last_log,
            "last_checkpoint": last_checkpoint,
        }

    def ckpt_path_fn(step: int) -> str:
        return os.path.join(log_dir, "checkpoint", f"ckpt_{step}_{rank}.ckpt")

    # a crash anywhere in the loop gets the preemption treatment too: the
    # lambdas read the loop's CURRENT policy_step/update at crash time
    resil.arm_crash_guard(
        path_fn=lambda: ckpt_path_fn(policy_step),
        state_fn=lambda: ckpt_state_fn(update - 1),
    )
    preempted = False
    if superstep_fn is not None:
        # ------------------------------------------------------------------
        # fused on-policy path: rollout + GAE + the single gradient step are
        # ONE donated jit; the metrics fetch is the only host sync per update
        # ------------------------------------------------------------------
        if use_mesh_fused:
            def place_carry(carry):
                return jax.tree.map(lambda x: jax.device_put(x, fabric.batch_sharding), carry)

            key = jax.device_put(key, fabric.replicated)
        else:

            def place_carry(carry):
                return put_tree(carry, train_device)

            key = put_tree(key, train_device)
        # one scenario row per env for the run's lifetime (PPO's contract)
        thetas = (
            scenario_theta_matrix(cfg, fused_spec, num_envs)
            if isinstance(fused_spec, ScenarioFamily)
            else None
        )
        env_carry = place_carry(
            init_env_carry(
                fused_spec,
                num_envs,
                jax.random.fold_in(jax.random.PRNGKey(int(cfg.seed)), ENV_STREAM_SALT),
                thetas=thetas,
            )
        )
        for update in range(start_update, num_updates + 1):
            telemetry_advance(policy_step)
            if resil.preempt_requested():
                last_checkpoint = policy_step
                resil.emergency_checkpoint(ckpt_path_fn(policy_step), ckpt_state_fn(update - 1))
                preempted = True
                break
            if update == start_update + 1:
                telemetry_mark_warm()
            # rollout_actions' fold schedule on top of a per-update key — the
            # same in-graph discipline as the fused PPO loop
            update_key = jax.random.fold_in(player_key, update)
            step_before = policy_step
            with timer("Time/env_interaction_time"):
                params, opt_state, env_carry, key, metrics, ep_stats = superstep_fn(
                    params,
                    opt_state,
                    env_carry,
                    update_key,
                    key,
                    np.uint32(step_before),
                    # A2C has no clip/entropy coefficients; the superstep's
                    # scalar slots are inert for its local_train
                    np.float32(0.0),
                    np.float32(0.0),
                )
                policy_step += policy_steps_per_update
                metrics = np.asarray(metrics)
            telemetry_train_window(1, 1)
            if not resil.check_finite(metrics, update):
                restored = resil.rollback(update=update)
                params = resil.place_like(restored["agent"], params)
                opt_state = resil.place_like(restored["opt_state"], opt_state)
                player_key = resil.resalt_key(player_key)
                player.update_params(params)
                # fresh episodes: poisoned params may have driven the carried
                # env state non-finite too
                env_carry = place_carry(
                    init_env_carry(
                        fused_spec,
                        num_envs,
                        jax.random.fold_in(jax.random.PRNGKey(int(cfg.seed)), update),
                        thetas=thetas,
                    )
                )
                continue
            train_step += num_processes
            if update == start_update:
                telemetry_register_flops(
                    superstep_fn,
                    params,
                    opt_state,
                    env_carry,
                    update_key,
                    key,
                    np.uint32(step_before),
                    np.float32(0.0),
                    np.float32(0.0),
                )
            if cfg.metric.log_level > 0:
                # one fetch of the per-step episode flags replaces the host
                # loop's final_info plumbing
                ep_done = np.asarray(ep_stats["done"])
                finished = np.nonzero(ep_done)
                if finished[0].size:
                    finished_rets = np.asarray(ep_stats["ret"])[finished]
                    for r in finished_rets:
                        aggregator.update("Rewards/rew_avg", float(r))
                    for length in np.asarray(ep_stats["len"])[finished]:
                        aggregator.update("Game/ep_len_avg", float(length))
                    # same per-episode evidence lines as the host loop — the
                    # learning-check recipes (benchmarks/learning_checks.sh,
                    # tools/sweep.py) grep these for the reward trend
                    for i, r in zip(finished[-1], finished_rets):
                        print(f"Rank-0: policy_step={policy_step}, reward_env_{i}={float(r)}")
                aggregator.update("Loss/policy_loss", float(metrics[0]))
                aggregator.update("Loss/value_loss", float(metrics[1]))
                if policy_step - last_log >= cfg.metric.log_every or update == num_updates:
                    metrics_dict = aggregator.compute()
                    logger.log_metrics(metrics_dict, policy_step)
                    telemetry_run_metrics(metrics_dict)
                    aggregator.reset()
                    log_sps_and_heartbeat(
                        logger,
                        policy_step=policy_step,
                        env_steps=(policy_step - last_log) * cfg.env.action_repeat,
                        train_steps=train_step - last_train,
                        train_invocations=(train_step - last_train) // num_processes,
                    )
                    last_log = policy_step
                    last_train = train_step
            if (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every) or (
                update == num_updates and cfg.checkpoint.save_last
            ):
                last_checkpoint = policy_step
                fabric.call(
                    "on_checkpoint_coupled", ckpt_path=ckpt_path_fn(policy_step), state=ckpt_state_fn(update)
                )
        # the player sampled nothing during the fused loop; publish the final
        # params once for the eval rollout below
        player.update_params(params)
    else:
        # rollout arrays preallocated once and written in place — no per-step
        # list appends, no end-of-window np.stack copy
        store = RolloutStore(rollout_steps)
        for update in range(start_update, num_updates + 1):
            telemetry_advance(policy_step)
            if resil.preempt_requested():
                last_checkpoint = policy_step
                resil.emergency_checkpoint(ckpt_path_fn(policy_step), ckpt_state_fn(update - 1))
                preempted = True
                break
            if update == start_update + 1:
                # no bench probe in this loop — warm the recompile watchdog here
                telemetry_mark_warm()
            buf = store.begin(update)
            with timer("Time/env_interaction_time"):
                for t in range(rollout_steps):
                    policy_step += num_envs * num_processes
                    player_key, action_key = jax.random.split(player_key)
                    actions, logprobs, values = player.get_actions(next_obs, action_key)
                    actions_np, logprobs_np, values_np = jax.device_get((actions, logprobs, values))
                    if is_continuous:
                        real_actions = actions_np
                    else:
                        splits = np.cumsum(actions_dim)[:-1]
                        real_actions = np.stack(
                            [p.argmax(-1) for p in np.split(actions_np, splits, axis=-1)], axis=-1
                        )
                        if real_actions.shape[-1] == 1 and not is_multidiscrete:
                            real_actions = real_actions[..., 0]

                    obs, rewards, terminated, truncated, info = envs.step(
                        real_actions.reshape(envs.action_space.shape)
                    )
                    rewards = np.asarray(rewards, np.float32).reshape(num_envs, 1)
                    dones = np.logical_or(terminated, truncated).reshape(num_envs, 1).astype(np.float32)
                    step_values = {k: next_obs[k] for k in obs_keys}
                    step_values["dones"] = dones
                    step_values["values"] = values_np
                    step_values["actions"] = actions_np
                    step_values["logprobs"] = logprobs_np
                    step_values["rewards"] = rewards
                    buf.put(t, step_values)
                    next_obs = prepare_obs(obs, num_envs=num_envs)

                    if cfg.metric.log_level > 0 and "final_info" in info:
                        ep = info["final_info"].get("episode")
                        if ep is not None:
                            for i in np.nonzero(ep.get("_r", []))[0]:
                                aggregator.update("Rewards/rew_avg", float(ep["r"][i]))
                                aggregator.update("Game/ep_len_avg", float(ep["l"][i]))
                                print(f"Rank-0: policy_step={policy_step}, reward_env_{i}={ep['r'][i]}")

            local_data = buf.arrays()
            next_values = np.asarray(player.get_values(next_obs))
            # GAE on the player's device (the host when the player is pinned
            # there): rollout arrays are already host-side, so the advantage
            # pass never pays a device round trip (same routing as plain PPO)
            returns, advantages = gae_fn(
                put_tree(local_data["rewards"], player.device),
                put_tree(local_data["values"], player.device),
                put_tree(local_data["dones"], player.device),
                put_tree(next_values, player.device),
            )
            local_data["returns"] = np.asarray(returns)
            local_data["advantages"] = np.asarray(advantages)
            flat = {k: v.reshape(v.shape[0] * v.shape[1], *v.shape[2:]) for k, v in local_data.items()}
            if num_processes > 1:
                flat = fabric.make_global(flat, (fabric.data_axis,))

            with timer("Time/train_time"):
                params, opt_state, metrics = train_fn(params, opt_state, flat)
                # one host fetch serves the sync point, the NaN sentinel and the
                # aggregator scalars below — block_until_ready + a second asarray
                # (or float(metrics[i]) per scalar) would each be an extra
                # blocking transfer per update
                metrics = np.asarray(metrics)
            if not resil.check_finite(metrics, update):
                # restore the newest committed checkpoint and fork the action key
                # away from the stream that diverged; the loop keeps advancing
                restored = resil.rollback(update=update)
                params = resil.place_like(restored["agent"], params)
                opt_state = resil.place_like(restored["opt_state"], opt_state)
                player_key = resil.resalt_key(player_key)
                player.update_params(params)
                continue
            player.params = params
            train_step += num_processes
            if update == start_update:
                telemetry_register_flops(train_fn, params, opt_state, flat)

            if cfg.metric.log_level > 0:
                aggregator.update("Loss/policy_loss", float(metrics[0]))
                aggregator.update("Loss/value_loss", float(metrics[1]))
                if policy_step - last_log >= cfg.metric.log_every or update == num_updates:
                    metrics_dict = aggregator.compute()
                    logger.log_metrics(metrics_dict, policy_step)
                    telemetry_run_metrics(metrics_dict)
                    aggregator.reset()
                    log_sps_and_heartbeat(
                        logger,
                        policy_step=policy_step,
                        env_steps=(policy_step - last_log) * cfg.env.action_repeat,
                        train_steps=train_step - last_train,
                        train_invocations=(train_step - last_train) // num_processes,
                    )
                    last_log = policy_step
                    last_train = train_step

            if (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every) or (
                update == num_updates and cfg.checkpoint.save_last
            ):
                last_checkpoint = policy_step
                fabric.call("on_checkpoint_coupled", ckpt_path=ckpt_path_fn(policy_step), state=ckpt_state_fn(update))

    envs.close()
    if fabric.is_global_zero and cfg.algo.run_test and not preempted:
        if obs_widened:
            # the agent expects the scenario family's widened observation; the
            # host eval env emits the base one — there is nothing to evaluate
            import warnings

            warnings.warn("skipping run_test: env.variants widened the observation past the host env's")
        else:
            test(player, fabric, cfg, log_dir)
    logger.finalize()
    resil.close()
    if preempted:
        resil.exit_preempted()
