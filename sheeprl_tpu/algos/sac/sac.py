"""SAC, coupled (reference: sheeprl/algos/sac/sac.py:32-424) — TPU-native.

Redesign highlights:

- **All G gradient steps of an update fused into one jit**: the sampled
  ``[G, B, ...]`` batch is scanned on device (critic, EMA, actor, alpha
  updates per step) — the reference dispatches each minibatch from Python
  (sac.py:337-351).
- **Critic ensemble is vmapped**, not looped.
- The reference's per-rank sample → ``fabric.all_gather`` → DistributedSampler
  round-robin (sac.py:303-333) collapses to: host samples the global batch,
  shard_map splits it over the data axis, gradient ``pmean`` restores DDP
  semantics (including the explicit ``log_alpha.grad`` all-reduce,
  sac.py:72).
"""

from __future__ import annotations

import os
import warnings
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

from sheeprl_tpu.algos.sac.agent import (
    SACAgent,
    actor_action_and_log_prob,
    build_agent,
    critic_ensemble_apply,
)
from sheeprl_tpu.algos.sac.loss import critic_loss, entropy_loss, policy_loss
from sheeprl_tpu.algos.sac.utils import AGGREGATOR_KEYS, prepare_obs, test
from sheeprl_tpu.data.device_buffer import draw_transition_batch
from sheeprl_tpu.envs import build_vector_env
from sheeprl_tpu.obs import (
    log_sps_and_heartbeat,
    telemetry_advance,
    telemetry_mark_warm_after_warmup,
    telemetry_run_metrics,
    telemetry_train_window,
)
from sheeprl_tpu.ops.superstep import fold_sample_key, fused_fallback, reset_fused_fallback_warnings
from sheeprl_tpu.resilience import RunResilience
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, gradient_step_chunks, save_configs, weighted_chunk_metrics


def make_train_fn(
    fabric,
    agent: SACAgent,
    actor_tx,
    critic_tx,
    alpha_tx,
    cfg,
    *,
    fused_length=None,
    fused_batch_size=None,
    fused_sample_next_obs=False,
):
    gamma = float(cfg.algo.gamma)
    tau = float(cfg.algo.tau)
    target_entropy = agent.target_entropy
    num_critics = agent.num_critics
    actor, critic = agent.actor, agent.critic
    data_axis = fabric.data_axis
    multi_device = fabric.world_size > 1
    # fused superstep mode (algo.fused_gradient_steps): instead of scanning a
    # pre-gathered [G, B, ...] batch, `data` is the device ring's
    # (bufs, pos, full) context and every scanned step draws its own batch
    # on device — replay gather, critic/actor/alpha updates and the target
    # EMA all land in ONE dispatch per chunk (ops/superstep.py rationale)
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
    # EMA cadence in gradient steps (reference sac.py:56 ties it to updates)
    ema_every = max(1, int(cfg.algo.critic.target_network_frequency) // max(1, int(cfg.env.num_envs)))

    def pmean(x):
        return lax.pmean(x, data_axis) if multi_device else x

    def local_train(
        actor_params, critic_params, target_params, log_alpha,
        actor_opt, critic_opt, alpha_opt, grad_counter, data, key,
    ):
        if multi_device:
            key = jax.random.fold_in(key, lax.axis_index(data_axis))

        def one_step(carry, batch):
            (actor_params, critic_params, target_params, log_alpha,
             actor_opt, critic_opt, alpha_opt, counter, key) = carry
            key, k_next, k_actor = jax.random.split(key, 3)
            alpha = jnp.exp(log_alpha)

            # soft critic update (Eq. 5)
            next_actions, next_logpi = actor_action_and_log_prob(
                actor, actor_params, batch["next_observations"], k_next
            )
            q_next = critic_ensemble_apply(critic, target_params, batch["next_observations"], next_actions)
            min_q_next = jnp.min(q_next, axis=-1, keepdims=True) - alpha * next_logpi
            target = batch["rewards"] + (1 - batch["terminated"]) * gamma * min_q_next
            target = lax.stop_gradient(target)

            def critic_loss_fn(p):
                q = critic_ensemble_apply(critic, p, batch["observations"], batch["actions"])
                return critic_loss(q, target, num_critics)

            qf_loss, critic_grads = jax.value_and_grad(critic_loss_fn)(critic_params)
            critic_grads = pmean(critic_grads)
            updates, critic_opt = critic_tx.update(critic_grads, critic_opt, critic_params)
            critic_params = optax.apply_updates(critic_params, updates)

            # target EMA (reference agent.py:264-267)
            do_ema = (counter % ema_every) == 0
            target_params = jax.tree.map(
                lambda c, t: jnp.where(do_ema, tau * c + (1 - tau) * t, t), critic_params, target_params
            )

            # actor update (Eq. 7)
            def actor_loss_fn(p):
                actions, logpi = actor_action_and_log_prob(actor, p, batch["observations"], k_actor)
                q = critic_ensemble_apply(critic, critic_params, batch["observations"], actions)
                min_q = jnp.min(q, axis=-1, keepdims=True)
                return policy_loss(alpha, logpi, min_q), logpi

            (a_loss, logpi), actor_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(actor_params)
            actor_grads = pmean(actor_grads)
            updates, actor_opt = actor_tx.update(actor_grads, actor_opt, actor_params)
            actor_params = optax.apply_updates(actor_params, updates)

            # entropy coefficient update (Eq. 17; grad all-reduced like
            # reference sac.py:72)
            alpha_grad = jax.grad(lambda la: entropy_loss(la, lax.stop_gradient(logpi), target_entropy))(
                log_alpha
            )
            alpha_grad = pmean(alpha_grad)
            updates, alpha_opt = alpha_tx.update(alpha_grad, alpha_opt, log_alpha)
            log_alpha = optax.apply_updates(log_alpha, updates)

            alpha_l = entropy_loss(log_alpha, logpi, target_entropy)
            carry = (actor_params, critic_params, target_params, log_alpha,
                     actor_opt, critic_opt, alpha_opt, counter + 1, key)
            return carry, jnp.stack([qf_loss, a_loss, alpha_l])

        carry = (actor_params, critic_params, target_params, log_alpha,
                 actor_opt, critic_opt, alpha_opt, grad_counter, key)
        if fused:
            bufs, pos, full = data

            def fused_step(carry, _):
                # the draw key is the carried key folded with the sample salt,
                # so the index noise never correlates with the gradient noise
                # one_step derives from the same key via split
                # the carried key was already folded with axis_index on a
                # mesh (local_train's first line), so the salted draw is
                # per-shard decorrelated for free
                batch = draw_transition_batch(
                    bufs,
                    pos,
                    full,
                    fold_sample_key(carry[-1]),
                    fused_draw_size,
                    sample_next_obs=fused_sample_next_obs,
                    obs_keys=("observations",),
                )
                return one_step(carry, batch)

            carry, metrics = lax.scan(fused_step, carry, None, length=int(fused_length))
        else:
            carry, metrics = lax.scan(one_step, carry, data)
        (actor_params, critic_params, target_params, log_alpha,
         actor_opt, critic_opt, alpha_opt, grad_counter, _) = carry
        return (
            actor_params, critic_params, target_params, log_alpha,
            actor_opt, critic_opt, alpha_opt, grad_counter,
            pmean(metrics.mean(axis=0)),
        )

    if multi_device:
        # data slot: pre-gathered [G, B, ...] stacks shard along the batch
        # axis; a fused ring context (bufs, pos, full) shards along the env
        # axis, matching the DeviceReplayBuffer's placement
        data_spec = (
            (P(data_axis), P(data_axis), P(data_axis)) if fused else P(None, data_axis)
        )
        train_fn = shard_map(
            local_train,
            mesh=fabric.mesh,
            in_specs=(P(), P(), P(), P(), P(), P(), P(), P(), data_spec, P()),
            out_specs=(P(), P(), P(), P(), P(), P(), P(), P(), P()),
        )
    else:
        train_fn = local_train
    # donate only optimizer/aux state: param buffers stay un-donated because
    # concurrent readers (async param streaming to the host player, the ema /
    # hard-copy target refresh) may still be in flight when the next train
    # dispatch would otherwise alias over them
    return jax.jit(train_fn, donate_argnums=(4, 5, 6))


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    rank = fabric.process_index
    world_size = fabric.data_parallel_size  # batch-split width: the data axis (= device count on a 1-D mesh)
    num_processes = fabric.num_processes  # hosts: sets the env-step accounting
    num_envs = int(cfg.env.num_envs)

    if cfg.checkpoint.resume_from:
        state = fabric.load(cfg.checkpoint.resume_from)

    if len(cfg.algo.cnn_keys.encoder) > 0:
        warnings.warn("SAC algorithm cannot allow to use images as observations, the CNN keys will be ignored")
        cfg.algo.cnn_keys.encoder = []

    log_dir = get_log_dir(cfg)
    logger = get_logger(cfg, log_dir)
    fabric.logger = logger
    logger.log_hyperparams(cfg.to_dict() if hasattr(cfg, "to_dict") else dict(cfg))
    print(f"Log dir: {log_dir}")
    resil = RunResilience(fabric, cfg, log_dir)

    envs = build_vector_env(cfg, rank, log_dir if rank == 0 else None, "train")
    action_space = envs.single_action_space
    observation_space = envs.single_observation_space
    if not isinstance(action_space, gym.spaces.Box):
        raise ValueError("Only continuous action space is supported for the SAC agent")
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    if len(mlp_keys) == 0:
        raise RuntimeError("You should specify at least one MLP key for the encoder: `mlp_keys.encoder=[state]`")
    for k in mlp_keys:
        if len(observation_space[k].shape) > 1:
            raise ValueError(
                "Only environments with vector-only observations are supported by the SAC agent. "
                f"The observation with key '{k}' has shape {observation_space[k].shape}."
            )

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
    # HBM replay ring when the chip allows it (buffer.device=auto): each
    # transition is uploaded once, every high-replay-ratio resample is an
    # on-chip gather — the same trade the Dreamer loops made in round 3
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
        store_next_obs=not cfg.buffer.sample_next_obs,
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
    # gather INSIDE the scanned chunk so one train window of G steps issues
    # ceil(G / K) dispatches with no host round trip in between
    fused_k = int(cfg.algo.get("fused_gradient_steps", 0) or 0)
    if fused_k > 0:
        reset_fused_fallback_warnings()
        if not use_device_rb:
            fused_fallback(
                "host_buffer",
                "algo.fused_gradient_steps needs the device replay buffer (buffer.device) to draw "
                "batches inside the scanned chunk; the host-buffer path already runs each chunk as "
                "one dispatch. Falling back to the per-chunk host gather.",
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

    train_fn = make_train_fn(fabric, agent, actor_tx, critic_tx, alpha_tx, cfg)

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

    # per scanned length one compiled superstep (chunking keeps the set of
    # lengths at {fused_k} ∪ {possible remainders}); built lazily AFTER the
    # elastic resume may have rewritten per_rank_batch_size
    fused_train_fns: Dict[int, Any] = {}

    def get_fused_fn(n: int):
        fn = fused_train_fns.get(n)
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
                fused_sample_next_obs=bool(cfg.buffer.sample_next_obs),
            )
            fused_train_fns[n] = fn
        return fn

    key = jax.random.PRNGKey(int(cfg.seed))
    grad_counter = jnp.zeros((), jnp.int32)
    # action keys stay on the player's device (no chip round trip per step
    # when the player is host-pinned)
    from sheeprl_tpu.parallel.fabric import put_tree

    player_key = put_tree(jax.random.fold_in(key, 1), player.device)

    obs, _ = envs.reset(seed=cfg.seed)
    cumulative_per_rank_gradient_steps = 0
    step_data: Dict[str, np.ndarray] = {}

    def ckpt_state_fn(completed_update: int) -> Dict[str, Any]:
        return {
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
            "update": completed_update,
            "batch_size": per_rank_batch_size * world_size,
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
        replay_buffer_fn=lambda: rb if cfg.buffer.checkpoint else None,
    )
    preempted = False
    for update in range(start_step, num_updates + 1):
        telemetry_advance(policy_step)
        if resil.preempt_requested():
            last_checkpoint = policy_step
            resil.emergency_checkpoint(
                ckpt_path_fn(policy_step),
                ckpt_state_fn(update - 1),
                replay_buffer=rb if cfg.buffer.checkpoint else None,
            )
            preempted = True
            break
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
        if not cfg.buffer.sample_next_obs:
            step_data["next_observations"] = prepare_obs(
                real_next_obs, mlp_keys=mlp_keys, num_envs=num_envs
            )[np.newaxis]
        step_data["rewards"] = np.asarray(rewards, np.float32).reshape(1, num_envs, 1)
        rb.add(step_data, validate_args=cfg.buffer.validate_args)

        obs = next_obs

        if update >= learning_starts:
            per_rank_gradient_steps = ratio(policy_step / num_processes)
            # fixed-size scan chunks: every distinct scan length is a fresh
            # XLA compile, and Ratio's first post-warmup call repays the whole
            # warmup debt in one G (utils.gradient_step_chunks)
            chunk_metrics = []
            window_dispatches = 0
            chunk_cfg = {"gradient_steps_chunk": fused_k} if fused_k > 0 else cfg.algo
            for chunk_steps in gradient_step_chunks(per_rank_gradient_steps, chunk_cfg):
                # [G, B_total, ...] so the chunk's gradient loop runs in one
                # jit; each process samples its share of the global batch and
                # the shards assemble into one global array over the mesh
                chunk_fn = train_fn
                if fused_k > 0:
                    # in-scan gather: the whole chunk is ONE dispatch; only
                    # the [E] pos/full cursors cross the link per chunk
                    data = rb.superstep_inputs(sample_next_obs=cfg.buffer.sample_next_obs)
                    chunk_fn = get_fused_fn(chunk_steps)
                    window_dispatches += 1
                elif use_device_rb:
                    # on-chip gather: only the indices cross the link.
                    # local_data_parallel_size, NOT local_device_count: on a
                    # 2-D (data x model) mesh the batch splits over the data
                    # axis only — model-axis devices see the same batch shard
                    data = rb.sample_transitions(
                        batch_size=per_rank_batch_size * fabric.local_data_parallel_size,
                        n_samples=chunk_steps,
                        sample_next_obs=cfg.buffer.sample_next_obs,
                    )
                    window_dispatches += 2  # gather program + scanned train program
                else:
                    window_dispatches += 1
                    sample = rb.sample(
                        batch_size=per_rank_batch_size * fabric.local_data_parallel_size,
                        n_samples=chunk_steps,
                        sample_next_obs=cfg.buffer.sample_next_obs,
                    )
                    data = {k: np.asarray(v, np.float32) for k, v in sample.items()}
                    if num_processes > 1:
                        data = fabric.make_global(data, (None, fabric.data_axis))
                    else:
                        # async HBM staging: device_put returns immediately and
                        # XLA orders the copy before the fused train step reads
                        # it; on a mesh the stack goes up pre-sharded along the
                        # batch axis (the train fn's in_spec), not replicated
                        from sheeprl_tpu.data.buffers import to_device
                        data = to_device(
                            data,
                            sharding=fabric.sharding(None, fabric.data_axis)
                            if fabric.world_size > 1
                            else None,
                        )
                with timer("Time/train_time"):
                    key, train_key = jax.random.split(key)
                    (
                        agent.actor_params,
                        agent.critic_params,
                        agent.target_critic_params,
                        agent.log_alpha,
                        actor_opt,
                        critic_opt,
                        alpha_opt,
                        grad_counter,
                        metrics,
                    ) = chunk_fn(
                        agent.actor_params,
                        agent.critic_params,
                        agent.target_critic_params,
                        agent.log_alpha,
                        actor_opt,
                        critic_opt,
                        alpha_opt,
                        grad_counter,
                        data,
                        train_key,
                    )
                    chunk_metrics.append((chunk_steps, metrics))  # device array; fetched once below
                cumulative_per_rank_gradient_steps += chunk_steps
            if per_rank_gradient_steps > 0:
                telemetry_train_window(window_dispatches, per_rank_gradient_steps)
                train_step += num_processes  # one "train event" per update
                # one fetch serves both the sentinel and the aggregator
                window_metrics = weighted_chunk_metrics(chunk_metrics)
                if not resil.check_finite(window_metrics, update):
                    # restore the newest committed checkpoint over the whole
                    # train state (params + all three optimizers) and fork
                    # the sample key away from the stream that diverged
                    restored = resil.rollback(update=update)
                    ra = restored["agent"]
                    agent.actor_params = resil.place_like(ra["actor"], agent.actor_params)
                    agent.critic_params = resil.place_like(ra["critics"], agent.critic_params)
                    agent.target_critic_params = resil.place_like(
                        ra["target_critics"], agent.target_critic_params
                    )
                    agent.log_alpha = resil.place_like(ra["log_alpha"], agent.log_alpha)
                    actor_opt = resil.place_like(restored["actor_optimizer"], actor_opt)
                    critic_opt = resil.place_like(restored["qf_optimizer"], critic_opt)
                    alpha_opt = resil.place_like(restored["alpha_optimizer"], alpha_opt)
                    key = resil.resalt_key(key)
                    player.update_params(agent.actor_params)
                    continue
                player.update_params(agent.actor_params)
                if cfg.metric.log_level > 0:
                    metrics = window_metrics
                    aggregator.update("Loss/value_loss", float(metrics[0]))
                    aggregator.update("Loss/policy_loss", float(metrics[1]))
                    aggregator.update("Loss/alpha_loss", float(metrics[2]))

        if cfg.metric.log_level > 0 and (policy_step - last_log >= cfg.metric.log_every or update == num_updates):
            metrics_dict = aggregator.compute()
            logger.log_metrics(metrics_dict, policy_step)
            telemetry_run_metrics(metrics_dict)
            aggregator.reset()
            if policy_step > 0:
                logger.log_metrics(
                    {"Params/replay_ratio": cumulative_per_rank_gradient_steps * num_processes / policy_step},
                    policy_step,
                )
            log_sps_and_heartbeat(
                logger,
                policy_step=policy_step,
                env_steps=(policy_step - last_log) / num_processes * cfg.env.action_repeat,
                train_steps=train_step - last_train,
            )
            last_log = policy_step
            last_train = train_step

        if (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every) or (
            update == num_updates and cfg.checkpoint.save_last
        ):
            last_checkpoint = policy_step
            fabric.call(
                "on_checkpoint_coupled",
                ckpt_path=ckpt_path_fn(policy_step),
                state=ckpt_state_fn(update),
                replay_buffer=rb if cfg.buffer.checkpoint else None,
            )

    # land any in-flight async param stream before the final evaluation
    player.flush_stream_attrs()
    envs.close()
    if fabric.is_global_zero and cfg.algo.run_test and not preempted:
        test(player, fabric, cfg, log_dir)
    logger.finalize()
    resil.close()
    if preempted:
        resil.exit_preempted()
