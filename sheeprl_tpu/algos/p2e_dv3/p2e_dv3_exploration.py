"""Plan2Explore on Dreamer-V3 — exploration phase (reference:
sheeprl/algos/p2e_dv3/p2e_dv3_exploration.py:41-1057) — TPU-native.

ONE jitted train step fuses all five optimizations of the reference's train():

1. world model (same losses as Dreamer-V3, with the reward/continue heads fed
   stop-gradient latents as in the reference, :160-163),
2. ensemble learning — the N-member ensemble is a vmapped param tree; the
   one-step-prediction MSE loss runs all members in a single batched matmul
   (reference Python loop :205-230),
3. exploration behaviour — one imagination rollout shared by all exploration
   critics; intrinsic reward = ensemble-disagreement variance (:271-287);
   weighted advantage mix across critics (:261-308),
4. per-critic exploration value losses with EMA target critics (:344-369),
5. task behaviour — the plain DV3 actor/critic update on the same replayed
   posteriors (:374-480), learned zero-shot from exploration data.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Sequence

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from sheeprl_tpu.ops.optim import build_tx
from sheeprl_tpu.algos.dreamer_v3.agent import (
    WorldModel,
    actor_logprob_entropy,
    rssm_scan,
    sample_actor_actions,
)
from sheeprl_tpu.algos.dreamer_v3.loss import reconstruction_loss
from sheeprl_tpu.algos.p2e_dv3.agent import build_agent, ensemble_apply
from sheeprl_tpu.algos.p2e_dv3.utils import AGGREGATOR_KEYS, prepare_obs, test
from sheeprl_tpu.data.device_buffer import (
    DeviceReplayBuffer,
    adapt_restored_buffer,
    make_sequential_replay,
)
from sheeprl_tpu.data.prefetch import sampled_batches
from sheeprl_tpu.envs import build_vector_env
from sheeprl_tpu.ops.distributions import (
    Bernoulli,
    Independent,
    MSEDistribution,
    OneHotCategorical,
    SymlogDistribution,
    TwoHotEncodingDistribution,
)
from sheeprl_tpu.ops.math import MomentsState, compute_lambda_values, init_moments, update_moments
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, save_configs

from sheeprl_tpu.parallel.shard_map import shard_map

BASE_METRIC_ORDER = (
    "Loss/world_model_loss",
    "Loss/observation_loss",
    "Loss/reward_loss",
    "Loss/state_loss",
    "Loss/continue_loss",
    "State/kl",
    "State/post_entropy",
    "State/prior_entropy",
    "Loss/ensemble_loss",
    "Loss/policy_loss_exploration",
    "Loss/policy_loss_task",
    "Loss/value_loss_task",
    "Grads/world_model",
    "Grads/ensemble",
    "Grads/actor_exploration",
    "Grads/actor_task",
    "Grads/critic_task",
)
PER_CRITIC_METRICS = (
    "Loss/value_loss_exploration_{k}",
    "Values_exploration/predicted_values_{k}",
    "Values_exploration/lambda_values_{k}",
    "Grads/critic_exploration_{k}",
    "Rewards/intrinsic_{k}",
)


def metric_order(critic_keys: Sequence[str]):
    order = list(BASE_METRIC_ORDER)
    for k in critic_keys:
        order += [m.format(k=k) for m in PER_CRITIC_METRICS]
    return tuple(order)


def make_train_fn(
    fabric,
    wm: WorldModel,
    actor,
    critic,
    ensemble,
    critic_meta: Dict[str, Dict[str, Any]],  # {k: {weight, reward_type}} (static)
    world_tx,
    actor_task_tx,
    critic_task_tx,
    actor_expl_tx,
    critic_expl_tx,
    ensemble_tx,
    cfg: Dict[str, Any],
    is_continuous: bool,
    actions_dim: Sequence[int],
):
    """One fused gradient step over a ``[T, B_local]`` sequence batch
    (replaces reference train(), p2e_dv3_exploration.py:41-518)."""
    algo = cfg.algo
    wmc = algo.world_model
    cnn_keys = tuple(algo.cnn_keys.encoder)
    mlp_keys = tuple(algo.mlp_keys.encoder)
    cnn_dec_keys = tuple(algo.cnn_keys.decoder)
    mlp_dec_keys = tuple(algo.mlp_keys.decoder)
    horizon = int(algo.horizon)
    gamma = float(algo.gamma)
    lmbda = float(algo.lmbda)
    ent_coef = float(algo.actor.ent_coef)
    kl_dynamic, kl_representation = float(wmc.kl_dynamic), float(wmc.kl_representation)
    kl_free_nats, kl_regularizer = float(wmc.kl_free_nats), float(wmc.kl_regularizer)
    continue_scale = float(wmc.continue_scale_factor)
    intrinsic_multiplier = float(algo.intrinsic_reward_multiplier)
    moments_cfg = algo.actor.moments
    data_axis = fabric.data_axis
    multi_device = fabric.world_size > 1
    critic_keys = tuple(critic_meta.keys())
    weights_sum = sum(m["weight"] for m in critic_meta.values())

    def pmean(x):
        return lax.pmean(x, data_axis) if multi_device else x

    def moments_update(state, lam):
        return update_moments(
            state,
            lam,
            decay=float(moments_cfg.decay),
            max_=float(moments_cfg.max),
            percentile_low=float(moments_cfg.percentile.low),
            percentile_high=float(moments_cfg.percentile.high),
            axis_name=data_axis if multi_device else None,
        )

    def local_train(
        wm_params,
        actor_task_params,
        critic_task_params,
        target_critic_task_params,
        actor_expl_params,
        expl_critic_params,  # {k: params}
        expl_target_params,  # {k: params}
        ens_params,
        world_opt,
        actor_task_opt,
        critic_task_opt,
        actor_expl_opt,
        expl_critic_opts,  # {k: opt_state}
        ensemble_opt,
        moments_task,
        moments_expl,  # {k: MomentsState}
        data,
        key,
    ):
        if multi_device:
            key = jax.random.fold_in(key, lax.axis_index(data_axis))
        k_scan, k_img_expl, k_img_task = jax.random.split(key, 3)
        sg = lax.stop_gradient

        T = data["rewards"].shape[0]
        B = data["rewards"].shape[1]
        is_first = data["is_first"].at[0].set(1.0)
        batch_actions = jnp.concatenate(
            [jnp.zeros_like(data["actions"][:1]), data["actions"][:-1]], axis=0
        )
        batch_obs = {k: data[k] for k in cnn_keys + mlp_keys}
        obs_targets = {k: data[k].astype(jnp.float32) / 255.0 - 0.5 for k in cnn_dec_keys}
        obs_targets.update({k: data[k].astype(jnp.float32) for k in mlp_dec_keys})

        # ---------------- 1. world model ---------------- #
        def world_loss_fn(p):
            embedded = wm.apply(p, batch_obs, method=WorldModel.encode)
            hs, zs, post_logits, prior_logits = rssm_scan(wm, p, embedded, batch_actions, is_first, k_scan)
            latents = jnp.concatenate([zs, hs], axis=-1)
            recon = wm.apply(p, latents, method=WorldModel.decode)
            po = {k: MSEDistribution(recon[k], dims=3) for k in cnn_dec_keys}
            po.update({k: SymlogDistribution(recon[k], dims=1) for k in mlp_dec_keys})
            # reward/continue heads train on detached latents in P2E
            # (reference :160-163)
            pr = TwoHotEncodingDistribution(
                wm.apply(p, sg(latents), method=WorldModel.reward_logits), dims=1
            )
            pc = Independent(
                Bernoulli(logits=wm.apply(p, sg(latents), method=WorldModel.continue_logits)), 1
            )
            loss, kl, state_loss, reward_loss, observation_loss, continue_loss = reconstruction_loss(
                po,
                obs_targets,
                pr,
                data["rewards"],
                prior_logits,
                post_logits,
                kl_dynamic,
                kl_representation,
                kl_free_nats,
                kl_regularizer,
                pc,
                1 - data["terminated"],
                continue_scale,
            )
            aux = (hs, zs, post_logits, prior_logits, kl, state_loss, reward_loss, observation_loss, continue_loss)
            return loss, aux

        (rec_loss, aux), wm_grads = jax.value_and_grad(world_loss_fn, has_aux=True)(wm_params)
        hs, zs, post_logits, prior_logits = aux[:4]
        kl, state_loss, reward_loss, observation_loss, continue_loss = aux[4:]
        wm_grads = pmean(wm_grads)
        wm_gnorm = optax.global_norm(wm_grads)
        wm_updates, world_opt = world_tx.update(wm_grads, world_opt, wm_params)
        wm_params = optax.apply_updates(wm_params, wm_updates)

        # ---------------- 2. ensemble learning ---------------- #
        ens_in = jnp.concatenate([sg(zs), sg(hs), data["actions"]], axis=-1)  # [T, B, L+A]
        ens_target = sg(zs)[1:]  # next posterior, [T-1, B, S]

        def ens_loss_fn(ep):
            outs = ensemble_apply(ensemble, ep, ens_in)[:, :-1]  # [N, T-1, B, S]
            # sum over members of per-member mean NLL (reference :206-220)
            logp = MSEDistribution(outs, dims=1).log_prob(
                jnp.broadcast_to(ens_target[None], outs.shape)
            )
            return -logp.mean(axis=(1, 2)).sum()

        ens_loss, ens_grads = jax.value_and_grad(ens_loss_fn)(ens_params)
        ens_grads = pmean(ens_grads)
        ens_gnorm = optax.global_norm(ens_grads)
        ens_updates, ensemble_opt = ensemble_tx.update(ens_grads, ensemble_opt, ens_params)
        ens_params = optax.apply_updates(ens_params, ens_updates)

        # shared starting states for both imaginations
        start_z = sg(zs).reshape(T * B, -1)
        start_h = sg(hs).reshape(T * B, -1)
        true_continue = (1 - data["terminated"]).reshape(T * B, 1)

        def imagine(actor_params, key):
            lat0 = jnp.concatenate([start_z, start_h], axis=-1)

            def step(carry, _):
                z, h, lat, key = carry
                key, k_act, k_state = jax.random.split(key, 3)
                action = sample_actor_actions(actor, actor_params, sg(lat), k_act)
                z, h = wm.apply(wm_params, z, h, action, k_state, method=WorldModel.imagination)
                new_lat = jnp.concatenate([z, h], axis=-1)
                return (z, h, new_lat, key), (lat, action)

            _, (lats, acts) = lax.scan(step, (start_z, start_h, lat0, key), None, length=horizon + 1)
            return lats, acts

        # ---------------- 3. exploration behaviour ---------------- #
        def actor_expl_loss_fn(p):
            trajectories, imagined_actions = imagine(p, k_img_expl)  # [H+1, N, ...]

            continues = Independent(
                Bernoulli(logits=wm.apply(wm_params, trajectories, method=WorldModel.continue_logits)), 1
            ).mode
            continues = jnp.concatenate([true_continue[None], continues[1:]], axis=0)

            # intrinsic reward: ensemble disagreement (reference :271-287)
            ens_preds = ensemble_apply(
                ensemble, ens_params, jnp.concatenate([sg(trajectories), sg(imagined_actions)], axis=-1)
            )  # [N_ens, H+1, TB, S]
            intrinsic_reward = (
                ens_preds.var(axis=0).mean(axis=-1, keepdims=True) * intrinsic_multiplier
            )

            advantages = []
            per_critic = {}
            new_moments = {}
            for k in critic_keys:
                values = TwoHotEncodingDistribution(
                    critic.apply(expl_critic_params[k], trajectories), dims=1
                ).mean
                if critic_meta[k]["reward_type"] == "intrinsic":
                    reward = intrinsic_reward
                else:
                    reward = TwoHotEncodingDistribution(
                        wm.apply(wm_params, trajectories, method=WorldModel.reward_logits), dims=1
                    ).mean
                lambda_values = compute_lambda_values(
                    reward[1:], values[1:], continues[1:] * gamma, lmbda
                )
                new_moments[k], (offset, invscale) = moments_update(moments_expl[k], lambda_values)
                baseline = values[:-1]
                normed_lambda = (lambda_values - offset) / invscale
                normed_baseline = (baseline - offset) / invscale
                advantages.append(
                    (normed_lambda - normed_baseline) * critic_meta[k]["weight"] / weights_sum
                )
                per_critic[k] = (lambda_values, values)

            advantage = sum(advantages)
            discount = sg(jnp.cumprod(continues * gamma, axis=0) / gamma)
            logp, entropy = actor_logprob_entropy(actor, p, sg(trajectories), sg(imagined_actions))
            if is_continuous:
                objective = advantage
            else:
                objective = logp[..., None][:-1] * sg(advantage)
            policy_loss = -jnp.mean(sg(discount[:-1]) * (objective + ent_coef * entropy[..., None][:-1]))
            aux = (trajectories, per_critic, discount, new_moments, intrinsic_reward.mean())
            return policy_loss, aux

        (policy_loss_expl, (trajectories, per_critic, discount, moments_expl, intrinsic_mean)), actor_expl_grads = (
            jax.value_and_grad(actor_expl_loss_fn, has_aux=True)(actor_expl_params)
        )
        actor_expl_grads = pmean(actor_expl_grads)
        actor_expl_gnorm = optax.global_norm(actor_expl_grads)
        expl_updates, actor_expl_opt = actor_expl_tx.update(actor_expl_grads, actor_expl_opt, actor_expl_params)
        actor_expl_params = optax.apply_updates(actor_expl_params, expl_updates)

        # ---------------- 4. exploration critics ---------------- #
        traj_in = sg(trajectories[:-1])
        expl_metrics = {}
        new_expl_params = {}
        new_expl_opts = {}
        for k in critic_keys:
            lambda_values, values = per_critic[k]
            target_values = TwoHotEncodingDistribution(
                critic.apply(expl_target_params[k], traj_in), dims=1
            ).mean

            def critic_loss_fn(p):
                qv = TwoHotEncodingDistribution(critic.apply(p, traj_in), dims=1)
                value_loss = -qv.log_prob(sg(lambda_values)) - qv.log_prob(sg(target_values))
                return jnp.mean(value_loss * sg(discount[:-1]).squeeze(-1))

            value_loss_k, grads_k = jax.value_and_grad(critic_loss_fn)(expl_critic_params[k])
            grads_k = pmean(grads_k)
            gnorm_k = optax.global_norm(grads_k)
            updates_k, new_expl_opts[k] = critic_expl_tx.update(
                grads_k, expl_critic_opts[k], expl_critic_params[k]
            )
            new_expl_params[k] = optax.apply_updates(expl_critic_params[k], updates_k)
            expl_metrics[k] = (value_loss_k, sg(values).mean(), sg(lambda_values).mean(), gnorm_k)

        # ---------------- 5. task behaviour (zero-shot) ---------------- #
        def actor_task_loss_fn(p):
            trajectories, imagined_actions = imagine(p, k_img_task)
            values = TwoHotEncodingDistribution(critic.apply(critic_task_params, trajectories), dims=1).mean
            rewards = TwoHotEncodingDistribution(
                wm.apply(wm_params, trajectories, method=WorldModel.reward_logits), dims=1
            ).mean
            continues = Independent(
                Bernoulli(logits=wm.apply(wm_params, trajectories, method=WorldModel.continue_logits)), 1
            ).mode
            continues = jnp.concatenate([true_continue[None], continues[1:]], axis=0)

            lambda_values = compute_lambda_values(rewards[1:], values[1:], continues[1:] * gamma, lmbda)
            discount = sg(jnp.cumprod(continues * gamma, axis=0) / gamma)

            new_moments, (offset, invscale) = moments_update(moments_task, lambda_values)
            baseline = values[:-1]
            advantage = (lambda_values - offset) / invscale - (baseline - offset) / invscale
            logp, entropy = actor_logprob_entropy(actor, p, sg(trajectories), sg(imagined_actions))
            if is_continuous:
                objective = advantage
            else:
                objective = logp[..., None][:-1] * sg(advantage)
            policy_loss = -jnp.mean(sg(discount[:-1]) * (objective + ent_coef * entropy[..., None][:-1]))
            return policy_loss, (trajectories, lambda_values, discount, new_moments)

        (policy_loss_task, (task_traj, task_lambda, task_discount, moments_task)), actor_task_grads = (
            jax.value_and_grad(actor_task_loss_fn, has_aux=True)(actor_task_params)
        )
        actor_task_grads = pmean(actor_task_grads)
        actor_task_gnorm = optax.global_norm(actor_task_grads)
        task_updates, actor_task_opt = actor_task_tx.update(actor_task_grads, actor_task_opt, actor_task_params)
        actor_task_params = optax.apply_updates(actor_task_params, task_updates)

        task_traj_in = sg(task_traj[:-1])
        task_target_values = TwoHotEncodingDistribution(
            critic.apply(target_critic_task_params, task_traj_in), dims=1
        ).mean

        def critic_task_loss_fn(p):
            qv = TwoHotEncodingDistribution(critic.apply(p, task_traj_in), dims=1)
            value_loss = -qv.log_prob(sg(task_lambda)) - qv.log_prob(sg(task_target_values))
            return jnp.mean(value_loss * sg(task_discount[:-1]).squeeze(-1))

        value_loss_task, critic_task_grads = jax.value_and_grad(critic_task_loss_fn)(critic_task_params)
        critic_task_grads = pmean(critic_task_grads)
        critic_task_gnorm = optax.global_norm(critic_task_grads)
        ct_updates, critic_task_opt = critic_task_tx.update(critic_task_grads, critic_task_opt, critic_task_params)
        critic_task_params = optax.apply_updates(critic_task_params, ct_updates)

        post_ent = Independent(OneHotCategorical(logits=sg(post_logits)), 1).entropy().mean()
        prior_ent = Independent(OneHotCategorical(logits=sg(prior_logits)), 1).entropy().mean()
        metric_list = [
            rec_loss,
            observation_loss,
            reward_loss,
            state_loss,
            continue_loss,
            kl,
            post_ent,
            prior_ent,
            ens_loss,
            policy_loss_expl,
            policy_loss_task,
            value_loss_task,
            wm_gnorm,
            ens_gnorm,
            actor_expl_gnorm,
            actor_task_gnorm,
            critic_task_gnorm,
        ]
        for k in critic_keys:
            value_loss_k, pred_mean, lambda_mean, gnorm_k = expl_metrics[k]
            intrinsic_metric = (
                intrinsic_mean
                if critic_meta[k]["reward_type"] == "intrinsic"
                else jnp.zeros_like(intrinsic_mean)
            )
            metric_list += [value_loss_k, pred_mean, lambda_mean, gnorm_k, intrinsic_metric]
        metrics = pmean(jnp.stack(metric_list))
        return (
            wm_params,
            actor_task_params,
            critic_task_params,
            actor_expl_params,
            new_expl_params,
            ens_params,
            world_opt,
            actor_task_opt,
            critic_task_opt,
            actor_expl_opt,
            new_expl_opts,
            ensemble_opt,
            moments_task,
            moments_expl,
            metrics,
        )

    if multi_device:
        train_fn = shard_map(
            local_train,
            mesh=fabric.mesh,
            in_specs=(
                P(), P(), P(), P(), P(), P(), P(), P(),
                P(), P(), P(), P(), P(), P(), P(), P(),
                P(None, data_axis), P(),
            ),
            out_specs=(P(),) * 15,
        )
    else:
        train_fn = local_train
    # donate only optimizer/aux state: param buffers stay un-donated because
    # concurrent readers (async param streaming to the host player, the ema /
    # hard-copy target refresh) may still be in flight when the next train
    # dispatch would otherwise alias over them
    return jax.jit(train_fn, donate_argnums=(8, 9, 10, 11, 12, 13, 14, 15))


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    if cfg.checkpoint.resume_from:
        state = fabric.load(cfg.checkpoint.resume_from)

    # these arguments cannot be changed (reference :530-532)
    cfg.env.frame_stack = 1
    cfg.algo.player.actor_type = "exploration"

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
    if (
        len(set(cnn_keys).intersection(cfg.algo.cnn_keys.decoder)) == 0
        and len(set(mlp_keys).intersection(cfg.algo.mlp_keys.decoder)) == 0
    ):
        raise RuntimeError("The CNN keys or the MLP keys of the encoder and decoder must not be disjointed")
    if set(cfg.algo.cnn_keys.decoder) - set(cnn_keys):
        raise RuntimeError("The CNN keys of the decoder must be contained in the encoder ones.")
    if set(cfg.algo.mlp_keys.decoder) - set(mlp_keys):
        raise RuntimeError("The MLP keys of the decoder must be contained in the encoder ones.")
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
        critics_exploration,
        ensemble,
        ensembles_params,
        player,
    ) = build_agent(
        fabric,
        actions_dim,
        is_continuous,
        cfg,
        observation_space,
        state["world_model"] if cfg.checkpoint.resume_from else None,
        state["ensembles"] if cfg.checkpoint.resume_from else None,
        state["actor_task"] if cfg.checkpoint.resume_from else None,
        state["critic_task"] if cfg.checkpoint.resume_from else None,
        state["target_critic_task"] if cfg.checkpoint.resume_from else None,
        state["actor_exploration"] if cfg.checkpoint.resume_from else None,
        state["critics_exploration"] if cfg.checkpoint.resume_from else None,
    )
    critic_keys = tuple(critics_exploration.keys())
    critic_meta = {
        k: {"weight": v["weight"], "reward_type": v["reward_type"]} for k, v in critics_exploration.items()
    }

    world_tx = build_tx(cfg.algo.world_model.optimizer, cfg.algo.world_model.clip_gradients)
    actor_task_tx = build_tx(cfg.algo.actor.optimizer, cfg.algo.actor.clip_gradients)
    critic_task_tx = build_tx(cfg.algo.critic.optimizer, cfg.algo.critic.clip_gradients)
    actor_expl_tx = build_tx(cfg.algo.actor.optimizer, cfg.algo.actor.clip_gradients)
    critic_expl_tx = build_tx(cfg.algo.critic.optimizer, cfg.algo.critic.clip_gradients)
    ensemble_tx = build_tx(cfg.algo.ensembles.optimizer, cfg.algo.ensembles.clip_gradients)

    world_opt = fabric.replicate(world_tx.init(jax.device_get(wm_params)))
    actor_task_opt = fabric.replicate(actor_task_tx.init(jax.device_get(actor_task_params)))
    critic_task_opt = fabric.replicate(critic_task_tx.init(jax.device_get(critic_task_params)))
    actor_expl_opt = fabric.replicate(actor_expl_tx.init(jax.device_get(actor_expl_params)))
    expl_critic_opts = {
        k: fabric.replicate(critic_expl_tx.init(jax.device_get(v["params"])))
        for k, v in critics_exploration.items()
    }
    ensemble_opt = fabric.replicate(ensemble_tx.init(jax.device_get(ensembles_params)))
    moments_task: MomentsState = init_moments()
    moments_expl = {k: init_moments() for k in critic_keys}
    if cfg.checkpoint.resume_from:
        world_opt = fabric.replicate(jax.tree.map(jnp.asarray, state["world_optimizer"]))
        actor_task_opt = fabric.replicate(jax.tree.map(jnp.asarray, state["actor_task_optimizer"]))
        critic_task_opt = fabric.replicate(jax.tree.map(jnp.asarray, state["critic_task_optimizer"]))
        actor_expl_opt = fabric.replicate(jax.tree.map(jnp.asarray, state["actor_exploration_optimizer"]))
        ensemble_opt = fabric.replicate(jax.tree.map(jnp.asarray, state["ensemble_optimizer"]))
        for k in critic_keys:
            expl_critic_opts[k] = fabric.replicate(
                jax.tree.map(jnp.asarray, state[f"critic_exploration_optimizer_{k}"])
            )
            m = state[f"moments_exploration_{k}"]
            moments_expl[k] = MomentsState(low=jnp.asarray(m["low"]), high=jnp.asarray(m["high"]))
        moments_task = MomentsState(
            low=jnp.asarray(state["moments_task"]["low"]), high=jnp.asarray(state["moments_task"]["high"])
        )
    # committed to the mesh like the rest of the train state, or window 2
    # compiles a SECOND executable of the whole step (see dreamer_v3.main)
    moments_task = fabric.replicate(moments_task)
    moments_expl = {k: fabric.replicate(m) for k, m in moments_expl.items()}

    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    # per-critic metric expansion (reference :680-707): the config declares
    # generic exploration metrics; the aggregator gets one per critic key
    aggregator = MetricAggregator(cfg.metric.get("aggregator", {}).get("metrics", {}) or {})
    for generic in ("Loss/value_loss_exploration", "Values_exploration/predicted_values",
                    "Values_exploration/lambda_values", "Grads/critic_exploration", "Rewards/intrinsic"):
        aggregator.metrics.pop(generic, None)
    for name in ("Rewards/rew_avg", "Game/ep_len_avg") + metric_order(critic_keys):
        if name not in aggregator.metrics:
            aggregator.add(name, "mean")

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
    if cfg.checkpoint.resume_from and cfg.buffer.checkpoint:
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

    train_fn = make_train_fn(
        fabric,
        wm,
        actor,
        critic,
        ensemble,
        critic_meta,
        world_tx,
        actor_task_tx,
        critic_task_tx,
        actor_expl_tx,
        critic_expl_tx,
        ensemble_tx,
        cfg,
        is_continuous,
        actions_dim,
    )

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
    sequence_length = int(cfg.algo.per_rank_sequence_length)
    if cfg.checkpoint.resume_from:
        from sheeprl_tpu.utils.checkpoint import elastic_per_rank_batch_size

        per_rank_batch_size = elastic_per_rank_batch_size(state["batch_size"], world_size)
        if not cfg.buffer.checkpoint:
            learning_starts += start_step

    ratio = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)
    if cfg.checkpoint.resume_from:
        ratio.load_state_dict(state["ratio"])

    key = jax.random.PRNGKey(int(cfg.seed))
    if cfg.checkpoint.resume_from and "rng_key" in state:
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

    cumulative_per_rank_gradient_steps = 0
    for update in range(start_step, num_updates + 1):
        policy_step += num_envs * num_processes

        with timer("Time/env_interaction_time"):
            if update <= learning_starts and cfg.checkpoint.resume_from is None:
                real_actions = actions = np.array(envs.action_space.sample())
                if not is_continuous:
                    actions = np.concatenate(
                        [
                            np.eye(act_dim, dtype=np.float32)[act.reshape(-1)]
                            for act, act_dim in zip(actions.reshape(len(actions_dim), -1), actions_dim)
                        ],
                        axis=-1,
                    )
            else:
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
                            for k in critic_keys:
                                critics_exploration[k]["target_params"] = ema(
                                    critics_exploration[k]["params"],
                                    critics_exploration[k]["target_params"],
                                    tau,
                                )
                        key, train_key = jax.random.split(key)
                        (
                            wm_params,
                            actor_task_params,
                            critic_task_params,
                            actor_expl_params,
                            new_expl_params,
                            ensembles_params,
                            world_opt,
                            actor_task_opt,
                            critic_task_opt,
                            actor_expl_opt,
                            expl_critic_opts,
                            ensemble_opt,
                            moments_task,
                            moments_expl,
                            metrics,
                        ) = train_fn(
                            wm_params,
                            actor_task_params,
                            critic_task_params,
                            target_critic_task_params,
                            actor_expl_params,
                            {k: critics_exploration[k]["params"] for k in critic_keys},
                            {k: critics_exploration[k]["target_params"] for k in critic_keys},
                            ensembles_params,
                            world_opt,
                            actor_task_opt,
                            critic_task_opt,
                            actor_expl_opt,
                            expl_critic_opts,
                            ensemble_opt,
                            moments_task,
                            moments_expl,
                            batch,
                            train_key,
                        )
                        for k in critic_keys:
                            critics_exploration[k]["params"] = new_expl_params[k]
                        cumulative_per_rank_gradient_steps += 1
                    metrics = np.asarray(jax.device_get(metrics))
                    train_step += num_processes
                # non-blocking in host-player mode: the trees stream through the
                # async pipe and flip a block or two later (fabric.stream_attr)
                player.stream_attr("wm_params", wm_params)
                player.stream_attr("actor_params", actor_expl_params)
                if cfg.metric.log_level > 0:
                    for name, value in zip(metric_order(critic_keys), metrics):
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
            critics_state: Dict[str, Any] = {"critics_exploration": {}}
            for k in critic_keys:
                critics_state["critics_exploration"][k] = {
                    "module": jax.device_get(critics_exploration[k]["params"]),
                    "target_module": jax.device_get(critics_exploration[k]["target_params"]),
                }
                critics_state[f"critic_exploration_optimizer_{k}"] = jax.device_get(expl_critic_opts[k])
                critics_state[f"moments_exploration_{k}"] = {
                    "low": np.asarray(jax.device_get(moments_expl[k].low)),
                    "high": np.asarray(jax.device_get(moments_expl[k].high)),
                }
            ckpt_state = {
                "world_model": jax.device_get(wm_params),
                "actor_task": jax.device_get(actor_task_params),
                "critic_task": jax.device_get(critic_task_params),
                "target_critic_task": jax.device_get(target_critic_task_params),
                "ensembles": jax.device_get(ensembles_params),
                "world_optimizer": jax.device_get(world_opt),
                "actor_task_optimizer": jax.device_get(actor_task_opt),
                "critic_task_optimizer": jax.device_get(critic_task_opt),
                "ensemble_optimizer": jax.device_get(ensemble_opt),
                "actor_exploration": jax.device_get(actor_expl_params),
                "actor_exploration_optimizer": jax.device_get(actor_expl_opt),
                "moments_task": {
                    "low": np.asarray(jax.device_get(moments_task.low)),
                    "high": np.asarray(jax.device_get(moments_task.high)),
                },
                "ratio": ratio.state_dict(),
                "update": update,
                "batch_size": per_rank_batch_size * world_size,
                "last_log": last_log,
                "last_checkpoint": last_checkpoint,
                "rng_key": jax.device_get(key),
                "player_rng_key": jax.device_get(player_key),
                **critics_state,
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
    # task test zero-shot (reference :1028-1033)
    if fabric.is_global_zero and cfg.algo.run_test:
        player.actor_params = actor_task_params
        test(player, fabric, cfg, log_dir, "zero-shot", greedy=False)
    logger.finalize()
