"""SAC-AE (reference: sheeprl/algos/sac_ae/sac_ae.py:35-517) — TPU-native.

Pixel SAC with an autoencoder. Redesign highlights:

- **All G gradient steps fused into one jit** (the reference dispatches each
  batch from Python, :390-410): critic (+encoder), EMA targets, actor, alpha,
  and decoder (+encoder) updates run per scanned step.
- Frequency-gated updates (actor every ``actor.per_rank_update_freq`` steps,
  decoder every ``decoder.per_rank_update_freq``, target EMA every
  ``critic.per_rank_target_network_update_freq``, reference :74-118) are
  ``jnp.where``-applied so the graph stays static.
- The gradient routing of the reference's five optimizers maps to per-tree
  ``jax.grad``: the critic loss trains (encoder, qfs); the actor loss trains
  only the actor trunk (conv features stop-gradient'd); the reconstruction
  loss trains (encoder, decoder) with the L2 latent penalty (:100-118).
- Pixels stay uint8 through the buffer; /255 normalization and the 5-bit
  reconstruction target quantization (utils.preprocess_obs) happen in-graph.
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
from jax.sharding import PartitionSpec as P

from sheeprl_tpu.ops.optim import build_tx
from sheeprl_tpu.algos.sac.loss import critic_loss, entropy_loss, policy_loss
from sheeprl_tpu.algos.sac_ae.agent import (
    SACAEAgent,
    actor_action_and_log_prob,
    build_agent,
    qf_ensemble_apply,
)
from sheeprl_tpu.algos.sac_ae.utils import AGGREGATOR_KEYS, prepare_obs, test
from sheeprl_tpu.envs import build_vector_env
from sheeprl_tpu.obs import telemetry_mark_warm_after_warmup
from sheeprl_tpu.parallel.shard_map import shard_map
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, gradient_step_chunks, save_configs, weighted_chunk_metrics


def make_train_fn(fabric, agent: SACAEAgent, actor_tx, qf_tx, alpha_tx, encoder_tx, decoder_tx, cfg):
    algo = cfg.algo
    gamma = float(algo.gamma)
    tau = float(algo.tau)
    encoder_tau = float(algo.encoder.tau)
    l2_lambda = float(algo.decoder.l2_lambda)
    target_entropy = agent.target_entropy
    num_critics = agent.num_critics
    encoder, decoder, actor, qf = agent.encoder, agent.decoder, agent.actor, agent.qf
    cnn_keys = tuple(algo.cnn_keys.encoder)
    mlp_keys = tuple(algo.mlp_keys.encoder)
    cnn_dec_keys = tuple(algo.cnn_keys.decoder)
    mlp_dec_keys = tuple(algo.mlp_keys.decoder)
    target_update_freq = max(1, int(algo.critic.per_rank_target_network_update_freq))
    actor_update_freq = max(1, int(algo.actor.per_rank_update_freq))
    decoder_update_freq = max(1, int(algo.decoder.per_rank_update_freq))
    data_axis = fabric.data_axis
    multi_device = fabric.world_size > 1

    def pmean(x):
        return lax.pmean(x, data_axis) if multi_device else x

    def normalized(batch, prefix=""):
        obs = {k: batch[prefix + k] / 255.0 for k in cnn_keys}
        obs.update({k: batch[prefix + k] for k in mlp_keys})
        return obs

    def preprocess_target(x, bits=5):
        """5-bit quantized reconstruction target (reference
        utils.preprocess_obs; the dequantization noise is omitted — a
        deterministic half-bin shift keeps the jitted step noise-free)."""
        bins = 2**bits
        x = jnp.floor(x / 2 ** (8 - bits))
        return x / bins + 0.5 / bins - 0.5

    def local_train(
        encoder_params, decoder_params, actor_params, qfs_params,
        target_encoder_params, target_qfs_params, log_alpha,
        actor_opt, qf_opt, alpha_opt, encoder_opt, decoder_opt,
        grad_counter, data, key,
    ):
        if multi_device:
            key = jax.random.fold_in(key, lax.axis_index(data_axis))

        def one_step(carry, batch):
            (encoder_params, decoder_params, actor_params, qfs_params,
             target_encoder_params, target_qfs_params, log_alpha,
             actor_opt, qf_opt, alpha_opt, encoder_opt, decoder_opt,
             counter, key) = carry
            key, k_next, k_actor = jax.random.split(key, 3)
            alpha = jnp.exp(log_alpha)
            obs = normalized(batch)
            next_obs = normalized(batch, "next_")

            # -------- soft critic (+ encoder) update (reference :62-70) ---- #
            next_feat = encoder.apply(target_encoder_params, next_obs)
            actor_feat_next = encoder.apply(encoder_params, next_obs)
            next_actions, next_logpi = actor_action_and_log_prob(actor, actor_params, actor_feat_next, k_next)
            q_next = qf_ensemble_apply(qf, target_qfs_params, next_feat, next_actions)
            min_q_next = jnp.min(q_next, axis=-1, keepdims=True) - alpha * next_logpi
            target = batch["rewards"] + (1 - batch["terminated"]) * gamma * min_q_next
            target = lax.stop_gradient(target)

            def qf_loss_fn(ep, qp):
                feat = encoder.apply(ep, obs)
                q = qf_ensemble_apply(qf, qp, feat, batch["actions"])
                return critic_loss(q, target, num_critics)

            qf_loss, (enc_grads, qf_grads) = jax.value_and_grad(qf_loss_fn, argnums=(0, 1))(
                encoder_params, qfs_params
            )
            enc_grads, qf_grads = pmean(enc_grads), pmean(qf_grads)
            updates, qf_opt = qf_tx.update(qf_grads, qf_opt, qfs_params)
            qfs_params = optax.apply_updates(qfs_params, updates)
            # the reference's qf optimizer covers the encoder too (its critic
            # module embeds it, sac_ae.py:66-69 + agent.py:226-238)
            updates, encoder_opt = encoder_tx.update(enc_grads, encoder_opt, encoder_params)
            encoder_params = optax.apply_updates(encoder_params, updates)

            # -------- target EMA (reference :73-77) ----------------------- #
            do_ema = (counter % target_update_freq) == 0
            target_qfs_params = jax.tree.map(
                lambda c, t: jnp.where(do_ema, tau * c + (1 - tau) * t, t), qfs_params, target_qfs_params
            )
            target_encoder_params = jax.tree.map(
                lambda c, t: jnp.where(do_ema, encoder_tau * c + (1 - encoder_tau) * t, t),
                encoder_params,
                target_encoder_params,
            )

            # -------- actor + alpha update (reference :79-97) ------------- #
            # the frequency gates are lax.cond so skipped steps skip the whole
            # backward pass; the counter is identical on every replica, so all
            # shards take the same branch and the pmean collectives line up
            do_actor = (counter % actor_update_freq) == 0

            def actor_update(operand):
                actor_params, log_alpha, actor_opt, alpha_opt = operand

                def actor_loss_fn(p):
                    feat = encoder.apply(encoder_params, obs, detach_encoder_features=True)
                    actions, logpi = actor_action_and_log_prob(actor, p, feat, k_actor)
                    q = qf_ensemble_apply(qf, qfs_params, feat, actions)
                    min_q = jnp.min(q, axis=-1, keepdims=True)
                    return policy_loss(alpha, logpi, min_q), logpi

                (a_loss, logpi), actor_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(actor_params)
                actor_grads = pmean(actor_grads)
                updates, actor_opt = actor_tx.update(actor_grads, actor_opt, actor_params)
                actor_params = optax.apply_updates(actor_params, updates)

                alpha_grad = jax.grad(
                    lambda la: entropy_loss(la, lax.stop_gradient(logpi), target_entropy)
                )(log_alpha)
                alpha_grad = pmean(alpha_grad)
                updates, alpha_opt = alpha_tx.update(alpha_grad, alpha_opt, log_alpha)
                log_alpha = optax.apply_updates(log_alpha, updates)
                alpha_l = entropy_loss(log_alpha, logpi, target_entropy)
                return actor_params, log_alpha, actor_opt, alpha_opt, a_loss, alpha_l

            def actor_skip(operand):
                actor_params, log_alpha, actor_opt, alpha_opt = operand
                return actor_params, log_alpha, actor_opt, alpha_opt, jnp.zeros(()), jnp.zeros(())

            actor_params, log_alpha, actor_opt, alpha_opt, a_loss, alpha_l = lax.cond(
                do_actor, actor_update, actor_skip, (actor_params, log_alpha, actor_opt, alpha_opt)
            )

            # -------- decoder (+ encoder) update (reference :99-118) ------ #
            do_decoder = (counter % decoder_update_freq) == 0

            def decoder_update(operand):
                encoder_params, decoder_params, encoder_opt, decoder_opt = operand

                def recon_loss_fn(ep, dp):
                    hidden = encoder.apply(ep, obs)
                    recon = decoder.apply(dp, hidden)
                    loss = l2_lambda * jnp.mean(0.5 * jnp.square(hidden).sum(-1))
                    for k in cnn_dec_keys + mlp_dec_keys:
                        target_k = preprocess_target(batch[k]) if k in cnn_dec_keys else batch[k]
                        loss = loss + jnp.mean(jnp.square(target_k - recon[k]))
                    return loss

                rec_loss, (enc_grads, dec_grads) = jax.value_and_grad(recon_loss_fn, argnums=(0, 1))(
                    encoder_params, decoder_params
                )
                enc_grads, dec_grads = pmean(enc_grads), pmean(dec_grads)
                updates, encoder_opt = encoder_tx.update(enc_grads, encoder_opt, encoder_params)
                encoder_params = optax.apply_updates(encoder_params, updates)
                updates, decoder_opt = decoder_tx.update(dec_grads, decoder_opt, decoder_params)
                decoder_params = optax.apply_updates(decoder_params, updates)
                return encoder_params, decoder_params, encoder_opt, decoder_opt, rec_loss

            def decoder_skip(operand):
                encoder_params, decoder_params, encoder_opt, decoder_opt = operand
                return encoder_params, decoder_params, encoder_opt, decoder_opt, jnp.zeros(())

            encoder_params, decoder_params, encoder_opt, decoder_opt, rec_loss = lax.cond(
                do_decoder,
                decoder_update,
                decoder_skip,
                (encoder_params, decoder_params, encoder_opt, decoder_opt),
            )

            carry = (encoder_params, decoder_params, actor_params, qfs_params,
                     target_encoder_params, target_qfs_params, log_alpha,
                     actor_opt, qf_opt, alpha_opt, encoder_opt, decoder_opt,
                     counter + 1, key)
            return carry, jnp.stack([qf_loss, a_loss, alpha_l, rec_loss])

        carry = (encoder_params, decoder_params, actor_params, qfs_params,
                 target_encoder_params, target_qfs_params, log_alpha,
                 actor_opt, qf_opt, alpha_opt, encoder_opt, decoder_opt,
                 grad_counter, key)
        carry, metrics = lax.scan(one_step, carry, data)
        return (*carry[:13], pmean(metrics.mean(axis=0)))

    if multi_device:
        train_fn = shard_map(
            local_train,
            mesh=fabric.mesh,
            in_specs=(P(),) * 13 + (P(None, data_axis), P()),
            out_specs=(P(),) * 14,
        )
    else:
        train_fn = local_train
    # donate only optimizer/aux state: param buffers stay un-donated because
    # concurrent readers (async param streaming to the host player, the ema /
    # hard-copy target refresh) may still be in flight when the next train
    # dispatch would otherwise alias over them
    return jax.jit(train_fn, donate_argnums=(7, 8, 9, 10, 11, 12))


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    rank = fabric.process_index
    world_size = fabric.data_parallel_size  # batch-split width: the data axis (= device count on a 1-D mesh)
    num_processes = fabric.num_processes
    num_envs = int(cfg.env.num_envs)

    if cfg.checkpoint.resume_from:
        state = fabric.load(cfg.checkpoint.resume_from)

    # these arguments cannot be changed (reference sac_ae.py:137-138)
    cfg.env.screen_size = 64

    log_dir = get_log_dir(cfg)
    logger = get_logger(cfg, log_dir)
    fabric.logger = logger
    logger.log_hyperparams(cfg.to_dict() if hasattr(cfg, "to_dict") else dict(cfg))
    print(f"Log dir: {log_dir}")

    envs = build_vector_env(cfg, rank, log_dir if rank == 0 else None, "train")
    action_space = envs.single_action_space
    observation_space = envs.single_observation_space
    if not isinstance(action_space, gym.spaces.Box):
        raise ValueError("Only continuous action space is supported for the SAC-AE agent")
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    obs_keys = cnn_keys + mlp_keys
    if not obs_keys:
        raise RuntimeError(
            "You should specify at least one CNN key or MLP key from the cli: "
            "`algo.cnn_keys.encoder=[rgb]` or `algo.mlp_keys.encoder=[state]`"
        )

    actions_dim = tuple(action_space.shape)

    agent, player = build_agent(
        fabric,
        actions_dim,
        True,
        cfg,
        observation_space,
        action_space,
        state["agent"] if cfg.checkpoint.resume_from else None,
    )

    qf_tx = build_tx(cfg.algo.critic.optimizer)
    actor_tx = build_tx(cfg.algo.actor.optimizer)
    alpha_tx = build_tx(cfg.algo.alpha.optimizer)
    encoder_tx = build_tx(cfg.algo.encoder.optimizer)
    decoder_tx = build_tx(cfg.algo.decoder.optimizer)
    qf_opt = fabric.replicate(qf_tx.init(jax.device_get(agent.qfs_params)))
    actor_opt = fabric.replicate(actor_tx.init(jax.device_get(agent.actor_params)))
    alpha_opt = fabric.replicate(alpha_tx.init(jax.device_get(agent.log_alpha)))
    encoder_opt = fabric.replicate(encoder_tx.init(jax.device_get(agent.encoder_params)))
    decoder_opt = fabric.replicate(decoder_tx.init(jax.device_get(agent.decoder_params)))
    if cfg.checkpoint.resume_from:
        qf_opt = fabric.replicate(jax.tree.map(jnp.asarray, state["qf_optimizer"]))
        actor_opt = fabric.replicate(jax.tree.map(jnp.asarray, state["actor_optimizer"]))
        alpha_opt = fabric.replicate(jax.tree.map(jnp.asarray, state["alpha_optimizer"]))
        encoder_opt = fabric.replicate(jax.tree.map(jnp.asarray, state["encoder_optimizer"]))
        decoder_opt = fabric.replicate(jax.tree.map(jnp.asarray, state["decoder_optimizer"]))

    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = MetricAggregator(cfg.metric.get("aggregator", {}).get("metrics", {}) or {})
    for k in AGGREGATOR_KEYS - set(aggregator.metrics):
        aggregator.add(k, "mean")

    buffer_size = cfg.buffer.size // int(num_envs * num_processes) if not cfg.dry_run else 1
    # the pixel workload is where the HBM ring pays most: at replay ratio 1.0
    # the host buffer re-uploads every sampled [G, B] pixel batch over the
    # link; the ring uploads each frame once and gathers on-chip
    # (buffer.device=auto)
    from sheeprl_tpu.data.device_buffer import (
        DeviceReplayBuffer,
        adapt_restored_buffer,
        make_transition_replay,
    )

    rb = make_transition_replay(
        cfg,
        fabric,
        observation_space,
        stored_keys=obs_keys,
        actions_dim=action_space.shape,
        buffer_size=buffer_size,
        num_envs=num_envs,
        obs_keys=tuple(obs_keys) + tuple(f"next_{k}" for k in obs_keys),
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

    train_fn = make_train_fn(fabric, agent, actor_tx, qf_tx, alpha_tx, encoder_tx, decoder_tx, cfg)

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

    key = jax.random.PRNGKey(int(cfg.seed))
    # action keys live on the player's device so a host-pinned player
    # never blocks on a chip round trip per env step
    from sheeprl_tpu.parallel.fabric import put_tree as _put_tree

    player_key = _put_tree(jax.random.fold_in(key, 1), player.device)
    grad_counter = jnp.zeros((), jnp.int32)

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
                np_obs = prepare_obs(obs, cnn_keys=cnn_keys, num_envs=num_envs)
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

        # pixels stored raw uint8; vectors float32 (reference :330-345)
        raw_obs = {
            k: (np.asarray(obs[k]) if k in cnn_keys else np.asarray(obs[k], np.float32)) for k in obs_keys
        }
        raw_next = {
            k: (np.asarray(real_next_obs[k]) if k in cnn_keys else np.asarray(real_next_obs[k], np.float32))
            for k in obs_keys
        }
        for k in obs_keys:
            v = raw_obs[k]
            step_data[k] = v.reshape(1, num_envs, *v.shape[1:])
            nv = raw_next[k]
            step_data[f"next_{k}"] = nv.reshape(1, num_envs, *nv.shape[1:])
        step_data["terminated"] = np.asarray(terminated, np.float32).reshape(1, num_envs, 1)
        step_data["truncated"] = np.asarray(truncated, np.float32).reshape(1, num_envs, 1)
        step_data["actions"] = np.asarray(actions, np.float32).reshape(1, num_envs, -1)
        step_data["rewards"] = np.asarray(rewards, np.float32).reshape(1, num_envs, 1)
        rb.add(step_data, validate_args=cfg.buffer.validate_args)

        obs = next_obs

        if update >= learning_starts:
            per_rank_gradient_steps = ratio(policy_step / num_processes)
            # fixed-size scan chunks (utils.gradient_step_chunks): every
            # distinct scan length is a fresh XLA compile and Ratio's first
            # post-warmup call repays the whole warmup debt in one G
            chunk_metrics = []
            for chunk_steps in gradient_step_chunks(per_rank_gradient_steps, cfg.algo):
                if use_device_rb:
                    # on-chip gather (only indices cross the link); the
                    # frame-stack fold happens on device — storage stays raw
                    # so checkpoints swap between buffer modes
                    data = {}
                    for k, v in rb.sample_transitions(
                        batch_size=per_rank_batch_size * fabric.local_data_parallel_size,
                        n_samples=chunk_steps,
                    ).items():
                        if (k in cnn_keys or (k.startswith("next_") and k[5:] in cnn_keys)) and v.ndim == 6:
                            g, b, s, h, w, c = v.shape
                            v = jnp.moveaxis(v, 2, 4).reshape(g, b, h, w, s * c)
                        data[k] = v
                else:
                    sample = rb.sample(
                        batch_size=per_rank_batch_size * fabric.local_data_parallel_size,
                        n_samples=chunk_steps,
                    )
                    data = {}
                    for k, v in sample.items():
                        if k in cnn_keys or (k.startswith("next_") and k[5:] in cnn_keys):
                            # [G, B, S, H, W, C] or [G, B, H, W, C] -> fold stack;
                            # pixels STAY uint8 across the link (4x fewer bytes —
                            # the in-graph /255 normalization promotes to f32)
                            v = np.asarray(v)
                            if v.ndim == 6:
                                g, b, s, h, w, c = v.shape
                                v = np.moveaxis(v, 2, 4).reshape(g, b, h, w, s * c)
                            data[k] = v if v.dtype == np.uint8 else v.astype(np.float32)
                        else:
                            data[k] = np.asarray(v, np.float32)
                    if num_processes > 1:
                        data = fabric.make_global(data, (None, fabric.data_axis))
                    else:
                        # async HBM staging: overlap the [G, B] transfer with dispatch
                        from sheeprl_tpu.data.buffers import to_device
                        data = to_device(data)
                with timer("Time/train_time"):
                    key, train_key = jax.random.split(key)
                    (
                        agent.encoder_params,
                        agent.decoder_params,
                        agent.actor_params,
                        agent.qfs_params,
                        agent.target_encoder_params,
                        agent.target_qfs_params,
                        agent.log_alpha,
                        actor_opt,
                        qf_opt,
                        alpha_opt,
                        encoder_opt,
                        decoder_opt,
                        grad_counter,
                        metrics,
                    ) = train_fn(
                        agent.encoder_params,
                        agent.decoder_params,
                        agent.actor_params,
                        agent.qfs_params,
                        agent.target_encoder_params,
                        agent.target_qfs_params,
                        agent.log_alpha,
                        actor_opt,
                        qf_opt,
                        alpha_opt,
                        encoder_opt,
                        decoder_opt,
                        grad_counter,
                        data,
                        train_key,
                    )
                    chunk_metrics.append((chunk_steps, metrics))  # device array; fetched once below
                cumulative_per_rank_gradient_steps += chunk_steps
            if per_rank_gradient_steps > 0:
                train_step += num_processes  # one "train event" per update
                # off-policy: non-blocking refresh, params land a block later
                player.stream_attr("encoder_params", agent.encoder_params)
                player.stream_attr("actor_params", agent.actor_params)
                if cfg.metric.log_level > 0:
                    metrics = weighted_chunk_metrics(chunk_metrics)
                    aggregator.update("Loss/value_loss", float(metrics[0]))
                    aggregator.update("Loss/policy_loss", float(metrics[1]))
                    aggregator.update("Loss/alpha_loss", float(metrics[2]))
                    aggregator.update("Loss/reconstruction_loss", float(metrics[3]))

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

        if (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every) or (
            update == num_updates and cfg.checkpoint.save_last
        ):
            last_checkpoint = policy_step
            ckpt_state = {
                "agent": {
                    "encoder": jax.device_get(agent.encoder_params),
                    "decoder": jax.device_get(agent.decoder_params),
                    "actor": jax.device_get(agent.actor_params),
                    "qfs": jax.device_get(agent.qfs_params),
                    "target_encoder": jax.device_get(agent.target_encoder_params),
                    "target_qfs": jax.device_get(agent.target_qfs_params),
                    "log_alpha": jax.device_get(agent.log_alpha),
                },
                "qf_optimizer": jax.device_get(qf_opt),
                "actor_optimizer": jax.device_get(actor_opt),
                "alpha_optimizer": jax.device_get(alpha_opt),
                "encoder_optimizer": jax.device_get(encoder_opt),
                "decoder_optimizer": jax.device_get(decoder_opt),
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
