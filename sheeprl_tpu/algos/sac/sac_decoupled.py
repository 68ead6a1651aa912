"""SAC, decoupled player/trainer topology (reference:
sheeprl/algos/sac/sac_decoupled.py:33-583) — TPU-native.

Same role split as ``ppo_decoupled``: process 0 is the PLAYER — it owns the
environments AND the replay buffer (reference :33-352), samples the training
batches and ships them; processes 1..N-1 are TRAINERS on their own mesh
running the fused SAC update of ``sac.make_train_fn`` with gradient ``pmean``
over the trainer mesh (reference trainer branch :352-542).

Per-update protocol on the host-object plane (both sides always make both
calls, so the collectives stay aligned even on no-train updates):

1. ``broadcast_object(batches | None, src=0)`` — the sampled ``[G, B, ...]``
   chunks (reference buffer-chunk scatter, :303-330),
2. ``broadcast_object(payload | None, src=1)`` — updated actor params for
   the player's policy (+ the full agent/optimizer state on checkpoint
   updates, reference on_checkpoint_player).
"""

from __future__ import annotations

import os
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.ops.optim import build_tx
from sheeprl_tpu.algos.ppo.ppo_decoupled import _ckpt_schedule, _trainer_devices
from sheeprl_tpu.algos.sac.agent import SACPlayer, build_agent
from sheeprl_tpu.algos.sac.sac import make_train_fn
from sheeprl_tpu.algos.sac.utils import AGGREGATOR_KEYS, prepare_obs, test
from sheeprl_tpu.data import ReplayBuffer
from sheeprl_tpu.envs import build_vector_env
from sheeprl_tpu.parallel.collectives import broadcast_object
from sheeprl_tpu.parallel.submesh import LocalFabric, SubMeshFabric, probe_spaces
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, save_configs


@register_algorithm(decoupled=True)
def main(fabric, cfg: Dict[str, Any]):
    if jax.process_count() < 2:
        raise RuntimeError(
            "sac_decoupled requires at least 2 processes: one player and one or more trainers "
            "(reference sac_decoupled.py:552-556)"
        )
    # every process restores from the same checkpoint file (reference
    # sac_decoupled.py resume; see also ppo_decoupled.py:45-46,104-116)
    state = fabric.load(cfg.checkpoint.resume_from) if cfg.checkpoint.resume_from else None
    if len(cfg.algo.cnn_keys.encoder) > 0:
        cfg.algo.cnn_keys.encoder = []
    if jax.process_index() == 0:
        _player(fabric, cfg, state)
    else:
        _trainer(fabric, cfg, state)


def _counters(cfg, num_envs):
    policy_steps_per_update = num_envs
    num_updates = int(cfg.algo.total_steps // policy_steps_per_update) if not cfg.dry_run else 1
    learning_starts = cfg.algo.learning_starts // policy_steps_per_update if not cfg.dry_run else 0
    return policy_steps_per_update, num_updates, learning_starts


def _player(fabric, cfg, state=None):
    log_dir = get_log_dir(cfg)
    logger = get_logger(cfg, log_dir)
    fabric.logger = logger
    logger.log_hyperparams(cfg.to_dict() if hasattr(cfg, "to_dict") else dict(cfg))
    print(f"Log dir: {log_dir}")

    num_envs = int(cfg.env.num_envs)
    trainer_devs = _trainer_devices()
    policy_steps_per_update, num_updates, learning_starts = _counters(cfg, num_envs)
    start_update = state["update"] + 1 if state else 1
    ckpt_updates = _ckpt_schedule(
        cfg,
        num_updates,
        policy_steps_per_update,
        start_update=start_update,
        last_checkpoint=state["last_checkpoint"] if state else 0,
    )
    per_rank_batch_size = int(cfg.algo.per_rank_batch_size)

    envs = build_vector_env(cfg, 0, log_dir, "train")
    action_space = envs.single_action_space
    observation_space = envs.single_observation_space
    if not isinstance(action_space, gym.spaces.Box):
        raise ValueError("Only continuous action space is supported for the SAC agent")
    mlp_keys = list(cfg.algo.mlp_keys.encoder)

    agent, player = build_agent(
        LocalFabric(fabric), cfg, observation_space, action_space, state["agent"] if state else None
    )

    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = MetricAggregator(cfg.metric.get("aggregator", {}).get("metrics", {}) or {})
    for k in AGGREGATOR_KEYS - set(aggregator.metrics):
        aggregator.add(k, "mean")

    buffer_size = cfg.buffer.size // num_envs if not cfg.dry_run else 1
    rb = ReplayBuffer(
        buffer_size,
        num_envs,
        obs_keys=("observations",),
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", "rank_0"),
        seed=cfg.seed,
    )
    if state:
        if cfg.buffer.checkpoint and "rb" in state:
            from sheeprl_tpu.utils.checkpoint import select_buffer

            rb = select_buffer(state["rb"], 0, 1)
        else:
            # without the buffer, refill before training resumes
            learning_starts += start_update

    ratio = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)
    if state and "ratio" in state:
        ratio.load_state_dict(state["ratio"])
    key = jax.random.PRNGKey(int(cfg.seed))
    if state and "rng_key" in state:
        key = jnp.asarray(state["rng_key"])
    # action keys live on the player's device so a host-pinned player
    # never blocks on a chip round trip per env step
    from sheeprl_tpu.parallel.fabric import put_tree as _put_tree

    from sheeprl_tpu.parallel.fabric import _ParamStreamer

    # flat-vector receive lane matching the trainer's actor pack
    actor_lane_player = _ParamStreamer(
        jax.device_get(player.params), player.device or jax.devices()[0]
    )
    player_key = _put_tree(jax.random.fold_in(key, 1), player.device)
    if state and "player_rng_key" in state:
        # continue the pre-resume action-sampling stream
        player_key = _put_tree(jnp.asarray(state["player_rng_key"]), player.device)

    policy_step = (start_update - 1) * num_envs
    last_log = state["last_log"] if state else 0
    obs, _ = envs.reset(seed=cfg.seed)
    step_data: Dict[str, np.ndarray] = {}
    cumulative_per_rank_gradient_steps = 0

    for update in range(start_update, num_updates + 1):
        policy_step += num_envs

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

        # sample the trainers' batches from the player-owned buffer
        # (reference :303-330)
        data = None
        # NOTE: this path still ships per_rank_gradient_steps
        # in ONE [G, B, ...] block — the trainer's fused scan recompiles per
        # distinct G and the first post-warmup G repays the whole warmup debt
        # (see utils.gradient_step_chunks, applied to the coupled loops);
        # chunking here needs a protocol change (multiple data broadcasts
        # per update), so keep learning_starts small where compiles are slow.
        if update >= learning_starts:
            per_rank_gradient_steps = ratio(policy_step)
            if per_rank_gradient_steps > 0:
                sample = rb.sample(
                    batch_size=per_rank_batch_size * len(trainer_devs),
                    n_samples=per_rank_gradient_steps,
                    sample_next_obs=cfg.buffer.sample_next_obs,
                )
                data = {k: np.asarray(v, np.float32) for k, v in sample.items()}
                cumulative_per_rank_gradient_steps += per_rank_gradient_steps
        broadcast_object(data, src=0)
        payload = broadcast_object(None, src=1)
        if payload is not None:
            player.params = actor_lane_player.finish(payload["actor_flat"])
            if cfg.metric.log_level > 0:
                aggregator.update("Loss/value_loss", float(payload["metrics"][0]))
                aggregator.update("Loss/policy_loss", float(payload["metrics"][1]))
                aggregator.update("Loss/alpha_loss", float(payload["metrics"][2]))

        if cfg.metric.log_level > 0 and (policy_step - last_log >= cfg.metric.log_every or update == num_updates):
            logger.log_metrics(aggregator.compute(), policy_step)
            aggregator.reset()
            timer.reset()
            last_log = policy_step

        # skip scheduled checkpoints that landed on a no-train update — a
        # .ckpt with no model state would crash evaluation on load
        if update in ckpt_updates and payload is not None and payload.get("state") is not None:
            # payload["state"] carries {agent, qf_optimizer, actor_optimizer,
            # alpha_optimizer} — merged flat to match the coupled SAC format
            ckpt_state = {
                **payload["state"],
                "update": update,
                "batch_size": per_rank_batch_size * len(trainer_devs),
                "last_log": last_log,
                "last_checkpoint": policy_step,
                "ratio": ratio.state_dict(),
                "rng_key": jax.device_get(key),
                "player_rng_key": jax.device_get(player_key),
            }
            ckpt_path = os.path.join(log_dir, "checkpoint", f"ckpt_{policy_step}_0.ckpt")
            fabric.call(
                "on_checkpoint_player",
                ckpt_path=ckpt_path,
                state=ckpt_state,
                replay_buffer=rb if cfg.buffer.checkpoint else None,
            )

    envs.close()
    if cfg.algo.run_test:
        test(player, fabric, cfg, log_dir)
    logger.finalize()


def _trainer(fabric, cfg, state=None):
    get_log_dir(cfg)  # join the player's log-dir broadcast
    num_envs = int(cfg.env.num_envs)
    trainer_devs = _trainer_devices()
    tfabric = SubMeshFabric(fabric, trainer_devs)
    policy_steps_per_update, num_updates, learning_starts = _counters(cfg, num_envs)
    start_update = state["update"] + 1 if state else 1
    ckpt_updates = _ckpt_schedule(
        cfg,
        num_updates,
        policy_steps_per_update,
        start_update=start_update,
        last_checkpoint=state["last_checkpoint"] if state else 0,
    )
    per_rank_batch_size = int(cfg.algo.per_rank_batch_size)

    observation_space, action_space = probe_spaces(cfg)
    agent, _player_handle = build_agent(
        tfabric, cfg, observation_space, action_space, state["agent"] if state else None
    )

    critic_tx = build_tx(cfg.algo.critic.optimizer)
    actor_tx = build_tx(cfg.algo.actor.optimizer)
    alpha_tx = build_tx(cfg.algo.alpha.optimizer)
    if state:
        critic_opt = tfabric.replicate(jax.tree.map(jnp.asarray, state["qf_optimizer"]))
        actor_opt = tfabric.replicate(jax.tree.map(jnp.asarray, state["actor_optimizer"]))
        alpha_opt = tfabric.replicate(jax.tree.map(jnp.asarray, state["alpha_optimizer"]))
    else:
        critic_opt = tfabric.replicate(critic_tx.init(jax.device_get(agent.critic_params)))
        actor_opt = tfabric.replicate(actor_tx.init(jax.device_get(agent.actor_params)))
        alpha_opt = tfabric.replicate(alpha_tx.init(jax.device_get(agent.log_alpha)))

    # the fused SAC update over the trainer-only mesh (reference trainer DDP
    # over optimization_pg, :352-542)
    train_fn = make_train_fn(tfabric, agent, actor_tx, critic_tx, alpha_tx, cfg)

    key = jax.random.PRNGKey(int(cfg.seed) + jax.process_index())
    if state:
        # the trainer key is not checkpointed; fold in the resume point so the
        # post-resume train_key stream does not replay the pre-checkpoint one
        key = jax.random.fold_in(key, start_update)
    grad_counter = jnp.zeros((), jnp.int32)
    my_dev_idx = [i for i, d in enumerate(trainer_devs) if d.process_index == jax.process_index()]

    from sheeprl_tpu.parallel.fabric import _ParamStreamer

    # flat-vector send lane for the per-update actor refresh
    actor_lane = _ParamStreamer(jax.device_get(agent.actor_params), trainer_devs[0])

    for update in range(start_update, num_updates + 1):
        data = broadcast_object(None, src=0)
        payload = None
        if data is not None:
            # this process's slice of the global batch: the contiguous blocks
            # of the devices it hosts
            cols = np.concatenate(
                [np.arange(i * per_rank_batch_size, (i + 1) * per_rank_batch_size) for i in my_dev_idx]
            )
            local = {k: v[:, cols] for k, v in data.items()}
            gdata = tfabric.make_global(local, (None, tfabric.data_axis))
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
            ) = train_fn(
                agent.actor_params,
                agent.critic_params,
                agent.target_critic_params,
                agent.log_alpha,
                actor_opt,
                critic_opt,
                alpha_opt,
                grad_counter,
                gdata,
                train_key,
            )
            if jax.process_index() == 1:
                payload = {
                    "actor_flat": np.asarray(actor_lane.begin(agent.actor_params)),
                    "metrics": np.asarray(jax.device_get(metrics)),
                    "state": None,
                }
                if update in ckpt_updates:
                    payload["state"] = {
                        "agent": {
                            "actor": jax.device_get(agent.actor_params),
                            "critics": jax.device_get(agent.critic_params),
                            "target_critics": jax.device_get(agent.target_critic_params),
                            "log_alpha": jax.device_get(agent.log_alpha),
                        },
                        "qf_optimizer": jax.device_get(critic_opt),
                        "actor_optimizer": jax.device_get(actor_opt),
                        "alpha_optimizer": jax.device_get(alpha_opt),
                    }
        broadcast_object(payload, src=1)
