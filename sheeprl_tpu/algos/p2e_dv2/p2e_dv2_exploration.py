"""Plan2Explore on Dreamer-V2 — exploration phase (reference:
sheeprl/algos/p2e_dv2/p2e_dv2_exploration.py:39-880) — TPU-native.

ONE jitted train step fuses: DV2 world model (KL balancing; reward/continue
heads on detached latents, :150-154), ensemble learning in posterior space as
a vmapped batched MLP (:192-216), exploration behaviour with the
ensemble-disagreement intrinsic reward and a TARGET exploration critic
(:218-330), and zero-shot task behaviour (:332-420)."""

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
from sheeprl_tpu.algos.dreamer_v2.agent import (
    WorldModelDV2,
    actor_logprob_entropy,
    rssm_scan,
    sample_actor_actions,
)
from sheeprl_tpu.algos.dreamer_v2.loss import reconstruction_loss
from sheeprl_tpu.algos.p2e_dv2.agent import build_agent, ensemble_apply
from sheeprl_tpu.algos.p2e_dv2.utils import AGGREGATOR_KEYS, prepare_obs, test
from sheeprl_tpu.data.device_buffer import (
    DeviceReplayBuffer,
    adapt_restored_buffer,
    make_sequential_replay,
)
from sheeprl_tpu.data.prefetch import sampled_batches
from sheeprl_tpu.envs import build_vector_env
from sheeprl_tpu.ops.distributions import Bernoulli, Independent, Normal
from sheeprl_tpu.ops.math import compute_lambda_values_bootstrap
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, save_configs

from sheeprl_tpu.parallel.shard_map import shard_map

METRIC_ORDER = (
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
    "Loss/value_loss_exploration",
    "Loss/policy_loss_task",
    "Loss/value_loss_task",
    "Rewards/intrinsic",
    "Values_exploration/predicted_values",
    "Values_exploration/lambda_values",
    "Grads/world_model",
    "Grads/ensemble",
    "Grads/actor_exploration",
    "Grads/critic_exploration",
    "Grads/actor_task",
    "Grads/critic_task",
)


def make_train_fn(
    fabric,
    wm: WorldModelDV2,
    actor,
    critic,
    ensemble,
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
    kl_balancing_alpha = float(wmc.kl_balancing_alpha)
    kl_free_nats, kl_free_avg = float(wmc.kl_free_nats), bool(wmc.kl_free_avg)
    kl_regularizer = float(wmc.kl_regularizer)
    discount_scale = float(wmc.discount_scale_factor)
    use_continues = bool(wmc.use_continues)
    intrinsic_multiplier = float(algo.intrinsic_reward_multiplier)
    n_actions = int(np.sum(actions_dim))
    data_axis = fabric.data_axis
    multi_device = fabric.world_size > 1

    def pmean(x):
        return lax.pmean(x, data_axis) if multi_device else x

    def local_train(
        wm_params,
        actor_task_params,
        critic_task_params,
        target_critic_task_params,
        actor_expl_params,
        critic_expl_params,
        target_critic_expl_params,
        ens_params,
        world_opt,
        actor_task_opt,
        critic_task_opt,
        actor_expl_opt,
        critic_expl_opt,
        ensemble_opt,
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
        batch_obs = {k: data[k] for k in cnn_keys + mlp_keys}
        obs_targets = {k: data[k].astype(jnp.float32) / 255.0 - 0.5 for k in cnn_dec_keys}
        obs_targets.update({k: data[k].astype(jnp.float32) for k in mlp_dec_keys})

        # ---------------- 1. world model ---------------- #
        def world_loss_fn(p):
            embedded = wm.apply(p, batch_obs, method=WorldModelDV2.encode)
            hs, zs, post_logits, prior_logits = rssm_scan(
                wm, p, embedded, data["actions"], is_first, k_scan
            )
            latents = jnp.concatenate([zs, hs], axis=-1)
            recon = wm.apply(p, latents, method=WorldModelDV2.decode)
            po = {
                k: Independent(Normal(recon[k], jnp.ones_like(recon[k])), 3 if k in cnn_dec_keys else 1)
                for k in cnn_dec_keys + mlp_dec_keys
            }
            # reward/continue heads on detached latents in P2E (reference :150-154)
            pr = Independent(Normal(wm.apply(p, sg(latents), method=WorldModelDV2.reward_mean), 1.0), 1)
            if use_continues:
                pc = Independent(
                    Bernoulli(logits=wm.apply(p, sg(latents), method=WorldModelDV2.continue_logits)), 1
                )
                continue_targets = (1 - data["terminated"]) * gamma
            else:
                pc = continue_targets = None
            loss, kl, state_loss, reward_loss, observation_loss, continue_loss = reconstruction_loss(
                po,
                obs_targets,
                pr,
                data["rewards"],
                prior_logits,
                post_logits,
                kl_balancing_alpha,
                kl_free_nats,
                kl_free_avg,
                kl_regularizer,
                pc,
                continue_targets,
                discount_scale,
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

        # ---------------- 2. ensemble learning (posterior space) ----------- #
        ens_in = jnp.concatenate([sg(zs), sg(hs), data["actions"]], axis=-1)
        ens_target = sg(zs)[1:]

        def ens_loss_fn(ep):
            outs = ensemble_apply(ensemble, ep, ens_in)[:, :-1]  # [N, T-1, B, S]
            logp = Independent(Normal(outs, jnp.ones_like(outs)), 1).log_prob(
                jnp.broadcast_to(ens_target[None], outs.shape)
            )
            return -logp.mean(axis=(1, 2)).sum()

        ens_loss, ens_grads = jax.value_and_grad(ens_loss_fn)(ens_params)
        ens_grads = pmean(ens_grads)
        ens_gnorm = optax.global_norm(ens_grads)
        ens_updates, ensemble_opt = ensemble_tx.update(ens_grads, ensemble_opt, ens_params)
        ens_params = optax.apply_updates(ens_params, ens_updates)

        start_z = sg(zs).reshape(T * B, -1)
        start_h = sg(hs).reshape(T * B, -1)
        true_continue = (1 - data["terminated"]).reshape(1, T * B, 1) * gamma

        def imagine(actor_params, key):
            """DV2 imagination (reference :219-244): H+1 latents including the
            replayed start; ``acts[0]`` zeros, ``acts[i>=1]`` sampled at
            ``lats[i-1]``."""
            lat0 = jnp.concatenate([start_z, start_h], axis=-1)

            def step(carry, _):
                z, h, lat, key = carry
                key, k_act, k_state = jax.random.split(key, 3)
                action = sample_actor_actions(actor, actor_params, sg(lat), k_act)
                z, h = wm.apply(wm_params, z, h, action, k_state, method=WorldModelDV2.imagination)
                new_lat = jnp.concatenate([z, h], axis=-1)
                return (z, h, new_lat, key), (new_lat, action)

            _, (lats, acts) = lax.scan(step, (start_z, start_h, lat0, key), None, length=horizon)
            lats = jnp.concatenate([lat0[None], lats], axis=0)
            acts = jnp.concatenate([jnp.zeros((1, T * B, n_actions), acts.dtype), acts], axis=0)
            return lats, acts

        def continues_of(lats, like):
            if use_continues:
                continues = jax.nn.sigmoid(
                    wm.apply(wm_params, lats, method=WorldModelDV2.continue_logits)
                )
                return jnp.concatenate([true_continue, continues[1:]], axis=0)
            return jnp.ones_like(like) * gamma

        def behaviour_loss(actor_params, key, target_critic_params, reward_fn):
            """Shared DV2 behaviour objective (reference :265-330 expl /
            :332-420 task): lambda targets from TARGET-critic values with
            bootstrap; reinforce for discrete, dynamics for continuous."""
            lats, acts = imagine(actor_params, key)
            target_values = critic.apply(target_critic_params, lats)
            reward, reward_aux = reward_fn(lats, acts)
            continues = continues_of(lats, reward)
            lambda_values = compute_lambda_values_bootstrap(
                reward[:-1], target_values[:-1], continues[:-1], bootstrap=target_values[-1:], lmbda=lmbda
            )
            discount = sg(
                jnp.cumprod(jnp.concatenate([jnp.ones_like(continues[:1]), continues[:-1]], axis=0), axis=0)
            )
            if is_continuous:
                objective = lambda_values[1:]
            else:
                advantage = sg(lambda_values[1:] - target_values[:-2])
                logp, _ = actor_logprob_entropy(actor, actor_params, sg(lats[:-2]), sg(acts[1:-1]))
                objective = logp[..., None] * advantage
            _, entropy = actor_logprob_entropy(actor, actor_params, sg(lats[:-2]), sg(acts[1:-1]))
            policy_loss = -jnp.mean(sg(discount[:-2]) * (objective + ent_coef * entropy[..., None]))
            return policy_loss, (lats, lambda_values, discount, reward_aux, target_values)

        # ---------------- 3. exploration behaviour ---------------- #
        def intrinsic_reward_fn(lats, acts):
            ens_preds = ensemble_apply(
                ensemble, ens_params, jnp.concatenate([sg(lats), sg(acts)], axis=-1)
            )
            reward = ens_preds.var(axis=0).mean(axis=-1, keepdims=True) * intrinsic_multiplier
            return reward, reward.mean()

        (policy_loss_expl, (expl_lats, expl_lambda, expl_discount, intrinsic_mean, expl_values)), expl_grads = (
            jax.value_and_grad(behaviour_loss, has_aux=True)(
                actor_expl_params, k_img_expl, target_critic_expl_params, intrinsic_reward_fn
            )
        )
        expl_grads = pmean(expl_grads)
        actor_expl_gnorm = optax.global_norm(expl_grads)
        upd, actor_expl_opt = actor_expl_tx.update(expl_grads, actor_expl_opt, actor_expl_params)
        actor_expl_params = optax.apply_updates(actor_expl_params, upd)

        expl_traj_in = sg(expl_lats[:-1])

        def critic_expl_loss_fn(p):
            qv = Independent(Normal(critic.apply(p, expl_traj_in), 1.0), 1)
            return -jnp.mean(sg(expl_discount[:-1])[..., 0] * qv.log_prob(sg(expl_lambda)))

        value_loss_expl, cg = jax.value_and_grad(critic_expl_loss_fn)(critic_expl_params)
        cg = pmean(cg)
        critic_expl_gnorm = optax.global_norm(cg)
        upd, critic_expl_opt = critic_expl_tx.update(cg, critic_expl_opt, critic_expl_params)
        critic_expl_params = optax.apply_updates(critic_expl_params, upd)

        # ---------------- 4. task behaviour (zero-shot) ---------------- #
        def task_reward_fn(lats, acts):
            reward = wm.apply(wm_params, lats, method=WorldModelDV2.reward_mean)
            return reward, jnp.zeros(())

        (policy_loss_task, (task_lats, task_lambda, task_discount, _, _)), task_grads = jax.value_and_grad(
            behaviour_loss, has_aux=True
        )(actor_task_params, k_img_task, target_critic_task_params, task_reward_fn)
        task_grads = pmean(task_grads)
        actor_task_gnorm = optax.global_norm(task_grads)
        upd, actor_task_opt = actor_task_tx.update(task_grads, actor_task_opt, actor_task_params)
        actor_task_params = optax.apply_updates(actor_task_params, upd)

        task_traj_in = sg(task_lats[:-1])

        def critic_task_loss_fn(p):
            qv = Independent(Normal(critic.apply(p, task_traj_in), 1.0), 1)
            return -jnp.mean(sg(task_discount[:-1])[..., 0] * qv.log_prob(sg(task_lambda)))

        value_loss_task, cg = jax.value_and_grad(critic_task_loss_fn)(critic_task_params)
        cg = pmean(cg)
        critic_task_gnorm = optax.global_norm(cg)
        upd, critic_task_opt = critic_task_tx.update(cg, critic_task_opt, critic_task_params)
        critic_task_params = optax.apply_updates(critic_task_params, upd)

        from sheeprl_tpu.ops.distributions import OneHotCategorical

        post_ent = Independent(OneHotCategorical(logits=sg(post_logits)), 1).entropy().mean()
        prior_ent = Independent(OneHotCategorical(logits=sg(prior_logits)), 1).entropy().mean()
        metrics = pmean(
            jnp.stack(
                [
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
                    value_loss_expl,
                    policy_loss_task,
                    value_loss_task,
                    intrinsic_mean,
                    sg(expl_values).mean(),
                    sg(expl_lambda).mean(),
                    wm_gnorm,
                    ens_gnorm,
                    actor_expl_gnorm,
                    critic_expl_gnorm,
                    actor_task_gnorm,
                    critic_task_gnorm,
                ]
            )
        )
        return (
            wm_params,
            actor_task_params,
            critic_task_params,
            actor_expl_params,
            critic_expl_params,
            ens_params,
            world_opt,
            actor_task_opt,
            critic_task_opt,
            actor_expl_opt,
            critic_expl_opt,
            ensemble_opt,
            metrics,
        )

    if multi_device:
        train_fn = shard_map(
            local_train,
            mesh=fabric.mesh,
            in_specs=(P(),) * 14 + (P(None, data_axis), P()),
            out_specs=(P(),) * 13,
        )
    else:
        train_fn = local_train
    # donate only optimizer/aux state: param buffers stay un-donated because
    # concurrent readers (async param streaming to the host player, the ema /
    # hard-copy target refresh) may still be in flight when the next train
    # dispatch would otherwise alias over them
    return jax.jit(train_fn, donate_argnums=(8, 9, 10, 11, 12, 13))


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    if cfg.checkpoint.resume_from:
        state = fabric.load(cfg.checkpoint.resume_from)

    cfg.env.screen_size = 64
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
        critic_expl_params,
        target_critic_expl_params,
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
        state["critic_exploration"] if cfg.checkpoint.resume_from else None,
        state["target_critic_exploration"] if cfg.checkpoint.resume_from else None,
    )

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
    critic_expl_opt = fabric.replicate(critic_expl_tx.init(jax.device_get(critic_expl_params)))
    ensemble_opt = fabric.replicate(ensemble_tx.init(jax.device_get(ensembles_params)))
    if cfg.checkpoint.resume_from:
        world_opt = fabric.replicate(jax.tree.map(jnp.asarray, state["world_optimizer"]))
        actor_task_opt = fabric.replicate(jax.tree.map(jnp.asarray, state["actor_task_optimizer"]))
        critic_task_opt = fabric.replicate(jax.tree.map(jnp.asarray, state["critic_task_optimizer"]))
        actor_expl_opt = fabric.replicate(jax.tree.map(jnp.asarray, state["actor_exploration_optimizer"]))
        critic_expl_opt = fabric.replicate(jax.tree.map(jnp.asarray, state["critic_exploration_optimizer"]))
        ensemble_opt = fabric.replicate(jax.tree.map(jnp.asarray, state["ensemble_optimizer"]))

    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = MetricAggregator(cfg.metric.get("aggregator", {}).get("metrics", {}) or {})
    for k in (set(METRIC_ORDER) | {"Rewards/rew_avg", "Game/ep_len_avg", "Params/exploration_amount"}) - set(
        aggregator.metrics
    ):
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
    if cfg.checkpoint.resume_from and cfg.buffer.checkpoint:
        from sheeprl_tpu.utils.checkpoint import select_buffer

        rb = adapt_restored_buffer(
            select_buffer(state["rb"], rank, num_processes),
            isinstance(rb, DeviceReplayBuffer),
            seed=cfg.seed,
            memmap=cfg.buffer.memmap,
            memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}"),
        )

    # hard target copies (reference :823-833)
    @jax.jit
    def hard_copy(cp):
        return jax.tree.map(jnp.copy, cp)

    train_fn = make_train_fn(
        fabric,
        wm,
        actor,
        critic,
        ensemble,
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
    step_data["terminated"] = np.zeros((1, num_envs, 1), np.float32)
    step_data["truncated"] = np.zeros((1, num_envs, 1), np.float32)
    step_data["actions"] = np.zeros((1, num_envs, int(np.sum(actions_dim))), np.float32)
    step_data["rewards"] = np.zeros((1, num_envs, 1), np.float32)
    step_data["is_first"] = np.ones_like(step_data["terminated"])
    rb.add(step_data, validate_args=cfg.buffer.validate_args)
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
                actions = player.get_actions(
                    prepared, action_key, expl_step=policy_step, with_exploration=True
                )
                if is_continuous:
                    real_actions = actions
                else:
                    splits = np.cumsum(actions_dim)[:-1]
                    real_actions = np.stack(
                        [p.argmax(-1) for p in np.split(actions, splits, axis=-1)], axis=-1
                    )
                    if real_actions.shape[-1] == 1 and not is_multidiscrete:
                        real_actions = real_actions[..., 0]

            step_data["is_first"] = np.logical_or(
                step_data["terminated"], step_data["truncated"]
            ).astype(np.float32)
            next_obs, rewards, terminated, truncated, infos = envs.step(
                real_actions.reshape(envs.action_space.shape)
            )
            dones = np.logical_or(terminated, truncated).astype(np.uint8)

        if "restart_on_exception" in infos:
            for i, roe in enumerate(np.asarray(infos["restart_on_exception"]).reshape(-1)):
                if roe and not dones[i]:
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

        prepared_next = prepare_obs(real_next_obs, cnn_keys=cnn_keys, num_envs=num_envs)
        for k in obs_keys:
            step_data[k] = prepared_next[k][np.newaxis]
        obs = next_obs

        step_data["terminated"] = np.asarray(terminated, np.float32).reshape(1, num_envs, 1)
        step_data["truncated"] = np.asarray(truncated, np.float32).reshape(1, num_envs, 1)
        step_data["actions"] = np.asarray(actions, np.float32).reshape(1, num_envs, -1)
        step_data["rewards"] = clip_rewards_fn(np.asarray(rewards, np.float32).reshape(1, num_envs, 1))
        rb.add(step_data, validate_args=cfg.buffer.validate_args)

        dones_idxes = dones.nonzero()[0].tolist()
        if dones_idxes:
            prepared_reset = prepare_obs(
                {k: np.asarray(next_obs[k])[dones_idxes] for k in obs_keys},
                cnn_keys=cnn_keys,
                num_envs=len(dones_idxes),
            )
            reset_data = {k: prepared_reset[k][np.newaxis] for k in obs_keys}
            reset_data["terminated"] = np.zeros((1, len(dones_idxes), 1), np.float32)
            reset_data["truncated"] = np.zeros((1, len(dones_idxes), 1), np.float32)
            reset_data["actions"] = np.zeros((1, len(dones_idxes), int(np.sum(actions_dim))), np.float32)
            reset_data["rewards"] = np.zeros((1, len(dones_idxes), 1), np.float32)
            reset_data["is_first"] = np.ones_like(reset_data["terminated"])
            rb.add(reset_data, dones_idxes, validate_args=cfg.buffer.validate_args)
            step_data["terminated"][0, dones_idxes] = 0.0
            step_data["truncated"][0, dones_idxes] = 0.0
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
                            target_critic_task_params = hard_copy(critic_task_params)
                            target_critic_expl_params = hard_copy(critic_expl_params)
                        key, train_key = jax.random.split(key)
                        (
                            wm_params,
                            actor_task_params,
                            critic_task_params,
                            actor_expl_params,
                            critic_expl_params,
                            ensembles_params,
                            world_opt,
                            actor_task_opt,
                            critic_task_opt,
                            actor_expl_opt,
                            critic_expl_opt,
                            ensemble_opt,
                            metrics,
                        ) = train_fn(
                            wm_params,
                            actor_task_params,
                            critic_task_params,
                            target_critic_task_params,
                            actor_expl_params,
                            critic_expl_params,
                            target_critic_expl_params,
                            ensembles_params,
                            world_opt,
                            actor_task_opt,
                            critic_task_opt,
                            actor_expl_opt,
                            critic_expl_opt,
                            ensemble_opt,
                            batch,
                            train_key,
                        )
                        cumulative_per_rank_gradient_steps += 1
                    metrics = np.asarray(jax.device_get(metrics))
                    train_step += num_processes
                # non-blocking in host-player mode: the trees stream through the
                # async pipe and flip a block or two later (fabric.stream_attr)
                player.stream_attr("wm_params", wm_params)
                player.stream_attr("actor_params", actor_expl_params)
                if cfg.metric.log_level > 0:
                    for name, value in zip(METRIC_ORDER, metrics):
                        aggregator.update(name, float(value))
                    aggregator.update(
                        "Params/exploration_amount", float(actor.get_expl_amount(policy_step))
                    )

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
                "ensembles": jax.device_get(ensembles_params),
                "actor_exploration": jax.device_get(actor_expl_params),
                "critic_exploration": jax.device_get(critic_expl_params),
                "target_critic_exploration": jax.device_get(target_critic_expl_params),
                "world_optimizer": jax.device_get(world_opt),
                "actor_task_optimizer": jax.device_get(actor_task_opt),
                "critic_task_optimizer": jax.device_get(critic_task_opt),
                "actor_exploration_optimizer": jax.device_get(actor_expl_opt),
                "critic_exploration_optimizer": jax.device_get(critic_expl_opt),
                "ensemble_optimizer": jax.device_get(ensemble_opt),
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
    if fabric.is_global_zero and cfg.algo.run_test:
        player.actor_params = actor_task_params
        test(player, fabric, cfg, log_dir, "zero-shot", greedy=False)
    logger.finalize()
