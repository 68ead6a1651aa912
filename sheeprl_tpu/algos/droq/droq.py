"""DroQ (reference: sheeprl/algos/droq/droq.py:31-412) — TPU-native.

SAC with Dropout-Q critics and a high replay ratio (20). Per update: G
critic-only gradient steps (shared TD target, per-critic MSE, target EMA
after every step — reference droq.py:96-119), then ONE actor+alpha update on
a separate batch using the ensemble MEAN Q (droq.py:121-139). The whole G
loop is a ``lax.scan`` inside one jitted shard_map step; dropout rngs are
per-critic, per-step.
"""

from __future__ import annotations

import os
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from sheeprl_tpu.ops.optim import build_tx
from sheeprl_tpu.parallel.shard_map import shard_map
from jax.sharding import PartitionSpec as P

from sheeprl_tpu.algos.droq.agent import (
    actor_action_and_log_prob,
    build_agent,
    critic_ensemble_apply,
)
from sheeprl_tpu.algos.sac.loss import entropy_loss, policy_loss
from sheeprl_tpu.algos.sac.utils import AGGREGATOR_KEYS, prepare_obs, test
from sheeprl_tpu.data.device_buffer import draw_transition_batch
from sheeprl_tpu.envs import build_vector_env
from sheeprl_tpu.obs import telemetry_mark_warm_after_warmup, telemetry_train_window
from sheeprl_tpu.ops.superstep import fold_sample_key, fused_fallback, reset_fused_fallback_warnings
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, gradient_step_chunks, save_configs, weighted_chunk_metrics


def _ensemble_apply_dropout(critic, stacked_params, obs, action, key, n_critics):
    keys = jax.random.split(key, n_critics)
    qs = jax.vmap(
        lambda p, k: critic.apply(p, obs, action, deterministic=False, rngs={"dropout": k})
    )(stacked_params, keys)
    return jnp.moveaxis(qs[..., 0], 0, -1)  # [B, n_critics]


def make_train_fn(fabric, agent, actor_tx, critic_tx, alpha_tx, cfg, *, fused_length=None, fused_batch_size=None):
    gamma = float(cfg.algo.gamma)
    tau = float(cfg.algo.tau)
    target_entropy = agent.target_entropy
    n_critics = agent.num_critics
    actor, critic = agent.actor, agent.critic
    use_dropout = float(cfg.algo.critic.get("dropout", 0.0)) > 0.0
    data_axis = fabric.data_axis
    multi_device = fabric.world_size > 1
    # fused superstep mode (algo.fused_gradient_steps): `critic_data` becomes
    # the device ring's (bufs, pos, full) context and every scanned critic
    # step draws its own batch on device — gather, TD update and target EMA
    # in ONE dispatch per chunk. The actor update stays one dispatch.
    fused = fused_length is not None
    if fused and multi_device:
        # fused + mesh = pure data-parallel shard_map (main() has already
        # fallen back for model_axis / multi-process runs): the ring context
        # arrives env-axis sharded and every device scans its own in-graph
        # draws of a per-shard batch
        if fabric.model_axis is not None or fabric.num_processes != 1:
            raise ValueError(
                "fused in-scan gather supersteps need a single-process pure "
                f"data-parallel run; got model_axis={fabric.model_axis!r}, "
                f"num_processes={fabric.num_processes}"
            )
        if int(fused_batch_size) % fabric.data_parallel_size:
            raise ValueError(
                f"fused_batch_size ({fused_batch_size}) must divide by "
                f"data_parallel_size ({fabric.data_parallel_size})"
            )
    fused_draw_size = (
        int(fused_batch_size) // (fabric.data_parallel_size if multi_device else 1)
        if fused
        else None
    )

    def pmean(x):
        return lax.pmean(x, data_axis) if multi_device else x

    def q_apply(params, obs, action, key):
        if use_dropout:
            return _ensemble_apply_dropout(critic, params, obs, action, key, n_critics)
        return critic_ensemble_apply(critic, params, obs, action)

    def local_critic_scan(
        actor_params, critic_params, target_params, log_alpha,
        critic_opt, critic_data, key,
    ):
        if multi_device:
            key = jax.random.fold_in(key, lax.axis_index(data_axis))
        alpha = jnp.exp(log_alpha)

        def critic_step(carry, batch):
            critic_params, target_params, critic_opt, key = carry
            key, k_next, k_drop_t, k_drop = jax.random.split(key, 4)
            next_actions, next_logpi = actor_action_and_log_prob(
                actor, actor_params, batch["next_observations"], k_next
            )
            q_next = q_apply(target_params, batch["next_observations"], next_actions, k_drop_t)
            min_q_next = jnp.min(q_next, axis=-1, keepdims=True) - alpha * next_logpi
            target = lax.stop_gradient(
                batch["rewards"] + (1 - batch["terminated"]) * gamma * min_q_next
            )

            def loss_fn(p):
                q = q_apply(p, batch["observations"], batch["actions"], k_drop)
                # per-critic MSE against the shared target (Alg. 2 line 8)
                return sum(
                    jnp.mean(jnp.square(q[..., i : i + 1] - target)) for i in range(n_critics)
                )

            qf_loss, grads = jax.value_and_grad(loss_fn)(critic_params)
            grads = pmean(grads)
            updates, critic_opt = critic_tx.update(grads, critic_opt, critic_params)
            critic_params = optax.apply_updates(critic_params, updates)
            # EMA after every critic step (reference droq.py:119)
            target_params = jax.tree.map(
                lambda c, t: tau * c + (1 - tau) * t, critic_params, target_params
            )
            return (critic_params, target_params, critic_opt, key), qf_loss

        if fused:
            bufs, pos, full = critic_data

            def fused_critic_step(carry, _):
                # draw key = carried key folded with the sample salt, so the
                # index noise stays decorrelated from the dropout/gradient
                # noise critic_step derives from the same key via split
                # the carried key was already folded with axis_index on a
                # mesh, so the salted draw is per-shard decorrelated for free
                batch = draw_transition_batch(
                    bufs, pos, full, fold_sample_key(carry[-1]), fused_draw_size
                )
                return critic_step(carry, batch)

            (critic_params, target_params, critic_opt, key), qf_losses = lax.scan(
                fused_critic_step,
                (critic_params, target_params, critic_opt, key),
                None,
                length=int(fused_length),
            )
        else:
            (critic_params, target_params, critic_opt, key), qf_losses = lax.scan(
                critic_step, (critic_params, target_params, critic_opt, key), critic_data
            )
        return critic_params, target_params, critic_opt, pmean(qf_losses.mean())

    def local_actor_update(
        actor_params, critic_params, log_alpha, actor_opt, alpha_opt, actor_batch, key,
    ):
        # one actor + alpha update per env update (reference droq.py:121-139)
        if multi_device:
            key = jax.random.fold_in(key, lax.axis_index(data_axis))
        alpha = jnp.exp(log_alpha)
        key, k_actor, k_drop = jax.random.split(key, 3)

        def actor_loss_fn(p):
            actions, logpi = actor_action_and_log_prob(actor, p, actor_batch["observations"], k_actor)
            q = q_apply(critic_params, actor_batch["observations"], actions, k_drop)
            mean_q = jnp.mean(q, axis=-1, keepdims=True)  # DroQ: mean, not min
            return policy_loss(alpha, logpi, mean_q), logpi

        (a_loss, logpi), actor_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(actor_params)
        actor_grads = pmean(actor_grads)
        updates, actor_opt = actor_tx.update(actor_grads, actor_opt, actor_params)
        actor_params = optax.apply_updates(actor_params, updates)

        alpha_grad = pmean(
            jax.grad(lambda la: entropy_loss(la, lax.stop_gradient(logpi), target_entropy))(log_alpha)
        )
        updates, alpha_opt = alpha_tx.update(alpha_grad, alpha_opt, log_alpha)
        log_alpha = optax.apply_updates(log_alpha, updates)
        alpha_l = entropy_loss(log_alpha, logpi, target_entropy)
        return actor_params, log_alpha, actor_opt, alpha_opt, pmean(jnp.stack([a_loss, alpha_l]))

    critic_fn, actor_fn = local_critic_scan, local_actor_update
    if multi_device:
        # critic_data slot: pre-gathered [G, B, ...] stacks shard along the
        # batch axis; a fused ring context (bufs, pos, full) shards along the
        # env axis, matching the DeviceReplayBuffer's placement
        critic_data_spec = (
            (P(data_axis), P(data_axis), P(data_axis)) if fused else P(None, data_axis)
        )
        critic_fn = shard_map(
            local_critic_scan,
            mesh=fabric.mesh,
            in_specs=(P(), P(), P(), P(), P(), critic_data_spec, P()),
            out_specs=(P(), P(), P(), P()),
        )
        actor_fn = shard_map(
            local_actor_update,
            mesh=fabric.mesh,
            in_specs=(P(), P(), P(), P(), P(), P(data_axis), P()),
            out_specs=(P(), P(), P(), P(), P()),
        )
    # Split into two jits so the critic scan can run in fixed-size chunks
    # (utils.gradient_step_chunks — scan length changes recompile) while the
    # actor update stays exactly once per env update like the reference.
    # Donate only optimizer state: param buffers stay un-donated because
    # concurrent readers (async param streaming to the host player, the EMA)
    # may still be in flight when the next dispatch would alias over them.
    return (
        jax.jit(critic_fn, donate_argnums=(4,)),
        jax.jit(actor_fn, donate_argnums=(3, 4)),
    )


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    rank = fabric.process_index
    world_size = fabric.data_parallel_size  # batch-split width: the data axis (= device count on a 1-D mesh)
    num_processes = fabric.num_processes
    num_envs = int(cfg.env.num_envs)

    if cfg.checkpoint.resume_from:
        state = fabric.load(cfg.checkpoint.resume_from)

    log_dir = get_log_dir(cfg)
    logger = get_logger(cfg, log_dir)
    fabric.logger = logger
    logger.log_hyperparams(cfg.to_dict() if hasattr(cfg, "to_dict") else dict(cfg))
    print(f"Log dir: {log_dir}")

    envs = build_vector_env(cfg, rank, log_dir if rank == 0 else None, "train")
    action_space = envs.single_action_space
    observation_space = envs.single_observation_space
    if not isinstance(action_space, gym.spaces.Box):
        raise ValueError("Only continuous action space is supported for the DroQ agent")
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    if len(mlp_keys) == 0:
        raise RuntimeError("You should specify at least one MLP key for the encoder: `mlp_keys.encoder=[state]`")

    agent, player = build_agent(
        fabric, cfg, observation_space, action_space, state["agent"] if cfg.checkpoint.resume_from else None
    )

    critic_tx = build_tx(cfg.algo.critic.optimizer)
    actor_tx = build_tx(cfg.algo.actor.optimizer)
    alpha_tx = build_tx(cfg.algo.alpha.optimizer)
    critic_opt = fabric.replicate(critic_tx.init(jax.device_get(agent.critic_params)))
    actor_opt = fabric.replicate(actor_tx.init(jax.device_get(agent.actor_params)))
    alpha_opt = fabric.replicate(alpha_tx.init(jax.device_get(agent.log_alpha)))
    if cfg.checkpoint.resume_from:
        critic_opt = fabric.replicate(jax.tree.map(jnp.asarray, state["qf_optimizer"]))
        actor_opt = fabric.replicate(jax.tree.map(jnp.asarray, state["actor_optimizer"]))
        alpha_opt = fabric.replicate(jax.tree.map(jnp.asarray, state["alpha_optimizer"]))

    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = MetricAggregator(cfg.metric.get("aggregator", {}).get("metrics", {}) or {})
    for k in AGGREGATOR_KEYS - set(aggregator.metrics):
        aggregator.add(k, "mean")

    buffer_size = cfg.buffer.size // int(num_envs * num_processes) if not cfg.dry_run else 1
    # high replay ratio is DroQ's defining regime — exactly where re-staging
    # every resampled batch over the link dominates; the HBM ring uploads each
    # transition once and gathers on-chip (buffer.device=auto)
    from sheeprl_tpu.data.device_buffer import (
        DeviceReplayBuffer,
        adapt_restored_buffer,
        make_transition_replay,
    )

    rb = make_transition_replay(
        cfg,
        fabric,
        observation_space,
        stored_keys=mlp_keys,
        actions_dim=action_space.shape,
        buffer_size=buffer_size,
        num_envs=num_envs,
        obs_keys=("observations",),
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}"),
        seed=cfg.seed,
        store_next_obs=True,
    )
    use_device_rb = isinstance(rb, DeviceReplayBuffer)
    if cfg.checkpoint.resume_from and cfg.buffer.checkpoint:
        from sheeprl_tpu.utils.checkpoint import select_buffer

        rb = adapt_restored_buffer(
            select_buffer(state["rb"], rank, num_processes),
            use_device_rb,
            seed=cfg.seed,
            mode="transition",
            memmap=cfg.buffer.memmap,
            memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}"),
        )

    # fused supersteps (algo.fused_gradient_steps): K > 0 moves the replay
    # gather INSIDE the scanned critic chunk so one train window of G critic
    # steps issues ceil(G / K) dispatches (the actor update stays one)
    fused_k = int(cfg.algo.get("fused_gradient_steps", 0) or 0)
    if fused_k > 0:
        reset_fused_fallback_warnings()
        if not use_device_rb:
            fused_fallback(
                "host_buffer",
                "algo.fused_gradient_steps needs the device replay buffer (buffer.device) to "
                "draw batches inside the scanned chunk; the host-buffer path already runs each "
                "chunk as one dispatch. Falling back to the per-chunk host gather.",
            )
            fused_k = 0
        elif fabric.num_processes > 1:
            fused_fallback(
                "multi_process",
                "algo.fused_gradient_steps cannot span processes "
                f"(num_processes={fabric.num_processes}); falling back to the per-chunk gather path.",
            )
            fused_k = 0
        elif fabric.world_size > 1 and fabric.model_axis is not None:
            fused_fallback(
                "model_axis",
                "algo.fused_gradient_steps is pure data-parallel, but this run shards params "
                f"over model_axis={fabric.model_axis!r}; falling back to the per-chunk gather path.",
            )
            fused_k = 0

    critic_fn, actor_fn = make_train_fn(fabric, agent, actor_tx, critic_tx, alpha_tx, cfg)

    train_step = 0
    last_train = 0
    start_step = state["update"] + 1 if cfg.checkpoint.resume_from else 1
    policy_step = state["update"] * num_envs * num_processes if cfg.checkpoint.resume_from else 0
    last_log = state["last_log"] if cfg.checkpoint.resume_from else 0
    last_checkpoint = state["last_checkpoint"] if cfg.checkpoint.resume_from else 0
    policy_steps_per_update = int(num_envs * num_processes)
    num_updates = int(cfg.algo.total_steps // policy_steps_per_update) if not cfg.dry_run else 1
    learning_starts = cfg.algo.learning_starts // policy_steps_per_update if not cfg.dry_run else 0
    per_rank_batch_size = int(cfg.algo.per_rank_batch_size)
    if cfg.checkpoint.resume_from:
        from sheeprl_tpu.utils.checkpoint import elastic_per_rank_batch_size

        per_rank_batch_size = elastic_per_rank_batch_size(state["batch_size"], world_size)
        if not cfg.buffer.checkpoint:
            learning_starts += start_step

    ratio = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)
    if cfg.checkpoint.resume_from:
        ratio.load_state_dict(state["ratio"])

    # per scanned length one compiled critic superstep (chunking keeps the set
    # of lengths at {fused_k} ∪ {possible remainders}); built lazily AFTER the
    # elastic resume may have rewritten per_rank_batch_size
    fused_critic_fns: Dict[int, Any] = {}

    def get_fused_critic_fn(n: int):
        fn = fused_critic_fns.get(n)
        if fn is None:
            fn = make_train_fn(
                fabric,
                agent,
                actor_tx,
                critic_tx,
                alpha_tx,
                cfg,
                fused_length=n,
                fused_batch_size=per_rank_batch_size * fabric.local_data_parallel_size,
            )[0]
            fused_critic_fns[n] = fn
        return fn

    key = jax.random.PRNGKey(int(cfg.seed))
    # action keys live on the player's device so a host-pinned player
    # never blocks on a chip round trip per env step
    from sheeprl_tpu.parallel.fabric import put_tree as _put_tree

    player_key = _put_tree(jax.random.fold_in(key, 1), player.device)
    obs, _ = envs.reset(seed=cfg.seed)
    cumulative_per_rank_gradient_steps = 0
    step_data: Dict[str, np.ndarray] = {}
    for update in range(start_step, num_updates + 1):
        telemetry_mark_warm_after_warmup(update, learning_starts)
        policy_step += num_envs * num_processes

        with timer("Time/env_interaction_time"):
            if update <= learning_starts:
                actions = envs.action_space.sample()
            else:
                player_key, action_key = jax.random.split(player_key)
                np_obs = prepare_obs(obs, mlp_keys=mlp_keys, num_envs=num_envs)
                actions = player.get_actions(np_obs, action_key)
            next_obs, rewards, terminated, truncated, infos = envs.step(
                np.asarray(actions).reshape(envs.action_space.shape)
            )

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

        step_data["terminated"] = np.asarray(terminated, np.float32).reshape(1, num_envs, 1)
        step_data["truncated"] = np.asarray(truncated, np.float32).reshape(1, num_envs, 1)
        step_data["actions"] = np.asarray(actions, np.float32).reshape(1, num_envs, -1)
        step_data["observations"] = prepare_obs(obs, mlp_keys=mlp_keys, num_envs=num_envs)[np.newaxis]
        step_data["next_observations"] = prepare_obs(
            real_next_obs, mlp_keys=mlp_keys, num_envs=num_envs
        )[np.newaxis]
        step_data["rewards"] = np.asarray(rewards, np.float32).reshape(1, num_envs, 1)
        rb.add(step_data, validate_args=cfg.buffer.validate_args)
        obs = next_obs

        if update >= learning_starts:
            per_rank_gradient_steps = ratio(policy_step / num_processes)
            if per_rank_gradient_steps > 0:
                from sheeprl_tpu.data.buffers import to_device

                # G critic steps in fixed-size scan chunks (every distinct
                # scan length is a fresh XLA compile — gradient_step_chunks);
                # sampling/staging stays OUTSIDE the train timer like the
                # other SAC-family loops
                qf_losses = []
                window_dispatches = 0
                chunk_cfg = {"gradient_steps_chunk": fused_k} if fused_k > 0 else cfg.algo
                for chunk_steps in gradient_step_chunks(per_rank_gradient_steps, chunk_cfg):
                    chunk_fn = critic_fn
                    if fused_k > 0:
                        # in-scan gather: the whole chunk is ONE dispatch;
                        # only the [E] pos/full cursors cross the link
                        critic_data = rb.superstep_inputs()
                        chunk_fn = get_fused_critic_fn(chunk_steps)
                        window_dispatches += 1
                    elif use_device_rb:
                        # on-chip gather: only the indices cross the link
                        critic_data = rb.sample_transitions(
                            batch_size=per_rank_batch_size * fabric.local_data_parallel_size,
                            n_samples=chunk_steps,
                        )
                        window_dispatches += 2  # gather program + scanned train program
                    else:
                        window_dispatches += 1
                        critic_sample = rb.sample(
                            batch_size=per_rank_batch_size * fabric.local_data_parallel_size,
                            n_samples=chunk_steps,
                        )
                        critic_data = {k: np.asarray(v, np.float32) for k, v in critic_sample.items()}
                        if num_processes > 1:
                            critic_data = fabric.make_global(critic_data, (None, fabric.data_axis))
                        else:
                            # async HBM staging ahead of the fused replay loop
                            critic_data = to_device(critic_data)
                    with timer("Time/train_time"):
                        key, train_key = jax.random.split(key)
                        (
                            agent.critic_params,
                            agent.target_critic_params,
                            critic_opt,
                            qf_loss,
                        ) = chunk_fn(
                            agent.actor_params,
                            agent.critic_params,
                            agent.target_critic_params,
                            agent.log_alpha,
                            critic_opt,
                            critic_data,
                            train_key,
                        )
                    qf_losses.append((chunk_steps, qf_loss))
                    cumulative_per_rank_gradient_steps += chunk_steps

                # then ONE actor+alpha update (reference droq.py:121-139)
                if use_device_rb:
                    actor_batch = {
                        k: v[0]
                        for k, v in rb.sample_transitions(
                            batch_size=per_rank_batch_size * fabric.local_data_parallel_size
                        ).items()
                    }  # [B, ...]
                    window_dispatches += 2  # actor-batch gather + actor program
                else:
                    window_dispatches += 1
                    actor_sample = rb.sample(batch_size=per_rank_batch_size * fabric.local_data_parallel_size)
                    actor_batch = {
                        k: np.asarray(v, np.float32)[0] for k, v in actor_sample.items()
                    }  # [B, ...]
                    if num_processes > 1:
                        actor_batch = fabric.make_global(actor_batch, (fabric.data_axis,))
                    else:
                        actor_batch = to_device(actor_batch)
                with timer("Time/train_time"):
                    key, train_key = jax.random.split(key)
                    (
                        agent.actor_params,
                        agent.log_alpha,
                        actor_opt,
                        alpha_opt,
                        actor_metrics,
                    ) = actor_fn(
                        agent.actor_params,
                        agent.critic_params,
                        agent.log_alpha,
                        actor_opt,
                        alpha_opt,
                        actor_batch,
                        train_key,
                    )
                    qf_mean = float(weighted_chunk_metrics(qf_losses))
                    actor_metrics = np.asarray(jax.device_get(actor_metrics))
                    train_step += num_processes
                telemetry_train_window(window_dispatches, per_rank_gradient_steps + 1)
                player.update_params(agent.actor_params)
                if cfg.metric.log_level > 0:
                    aggregator.update("Loss/value_loss", float(qf_mean))
                    aggregator.update("Loss/policy_loss", float(actor_metrics[0]))
                    aggregator.update("Loss/alpha_loss", float(actor_metrics[1]))

        if cfg.metric.log_level > 0 and (policy_step - last_log >= cfg.metric.log_every or update == num_updates):
            logger.log_metrics(aggregator.compute(), policy_step)
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

        if (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every) or (
            update == num_updates and cfg.checkpoint.save_last
        ):
            last_checkpoint = policy_step
            ckpt_state = {
                "agent": {
                    "actor": jax.device_get(agent.actor_params),
                    "critics": jax.device_get(agent.critic_params),
                    "target_critics": jax.device_get(agent.target_critic_params),
                    "log_alpha": jax.device_get(agent.log_alpha),
                },
                "qf_optimizer": jax.device_get(critic_opt),
                "actor_optimizer": jax.device_get(actor_opt),
                "alpha_optimizer": jax.device_get(alpha_opt),
                "ratio": ratio.state_dict(),
                "update": update,
                "batch_size": per_rank_batch_size * world_size,
                "last_log": last_log,
                "last_checkpoint": last_checkpoint,
            }
            ckpt_path = os.path.join(log_dir, "checkpoint", f"ckpt_{policy_step}_{rank}.ckpt")
            fabric.call(
                "on_checkpoint_coupled",
                ckpt_path=ckpt_path,
                state=ckpt_state,
                replay_buffer=rb if cfg.buffer.checkpoint else None,
            )

    # land any in-flight async param stream before the final evaluation
    player.flush_stream_attrs()
    envs.close()
    if fabric.is_global_zero and cfg.algo.run_test:
        test(player, fabric, cfg, log_dir)
    logger.finalize()
