"""Recurrent PPO (reference: sheeprl/algos/ppo_recurrent/ppo_recurrent.py:31-531)
— TPU-native.

The redesign:

- **Sequence-chunked rollouts with static shapes.** The reference splits the
  rollout into variable-length episodes, chunks them, and pads to the max
  length. Here every chunk is padded to exactly ``per_rank_sequence_length``
  and the sequence COUNT is padded to a multiple of
  ``devices * per_rank_num_batches`` with fully-masked dummies — the jitted
  update only recompiles when that padded count changes, not every update.
- **Whole-update fusion**: epochs x shuffled sequence-minibatches run as two
  nested ``lax.scan``s inside one ``shard_map``-ped XLA program; sequences
  are sharded across the mesh's data axis and gradients ``pmean``-reduced
  over ICI (the reference's DDP+Join, :45-56).
- **Masked losses** replace ``pack_padded_sequence``: padded steps contribute
  zero to every loss term (reference masks via boolean indexing, :77-101).
- Hidden states are reset on done during the rollout
  (``reset_recurrent_state_on_done``, reference :367-371), and sequences
  restart the LSTM from the STORED per-step states (``prev_hx/prev_cx``,
  reference :72).
"""

from __future__ import annotations

import os
from functools import partial
from typing import Any, Dict, List, Tuple

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from sheeprl_tpu.algos.ppo.loss import entropy_loss, policy_loss, value_loss
from sheeprl_tpu.algos.ppo.ppo import (
    resolve_fused_rollout_spec,
    resolve_scenario_family,
    scenario_theta_matrix,
)
from sheeprl_tpu.algos.ppo_recurrent.agent import (
    RecurrentPPOPlayer,
    build_agent,
    evaluate_actions,
    evaluate_actions_resettable,
    recurrent_rollout_step,
)
from sheeprl_tpu.algos.ppo_recurrent.utils import AGGREGATOR_KEYS, prepare_obs, test
from sheeprl_tpu.config.compose import instantiate
from sheeprl_tpu.envs import build_vector_env
from sheeprl_tpu.envs.variants import ScenarioFamily
from sheeprl_tpu.obs import (
    log_sps_and_heartbeat,
    telemetry_advance,
    telemetry_mark_warm,
    telemetry_register_flops,
    telemetry_run_metrics,
    telemetry_train_window,
)
from sheeprl_tpu.ops.math import gae
from sheeprl_tpu.ops.rollout_scan import (
    ENV_STREAM_SALT,
    init_recurrent_env_carry,
    make_recurrent_onpolicy_superstep_fn,
)
from sheeprl_tpu.ops.superstep import fused_fallback, reset_fused_fallback_warnings
from sheeprl_tpu.parallel.shard_map import shard_map
from sheeprl_tpu.resilience import RunResilience
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.prealloc import RolloutStore
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import polynomial_decay, save_configs


def build_sequences(
    local_data: Dict[str, np.ndarray],
    train_keys: List[str],
    seq_len: int,
    num_envs: int,
    pad_multiple: int,
    carry_keys: Tuple[str, ...] = ("prev_hx", "prev_cx"),
) -> Dict[str, np.ndarray]:
    """Split the ``[T, E, ...]`` rollout into per-episode chunks of at most
    ``seq_len`` steps (reference :406-444), pad each chunk to ``seq_len`` and
    the chunk count to a multiple of ``pad_multiple``. Only ``train_keys``
    are shipped as ``[seq_len, N_pad, ...]`` arrays; what the sequence core
    carried into each chunk's first step (``carry_keys``, stored per step as
    ``prev_<name>``: the LSTM's ``hx``/``cx``, or the decoder core's cache
    length and row) is emitted once per sequence as ``<name>0`` ``[N_pad,
    ...]`` (the update reads nothing else from them), plus a ``mask`` of
    valid steps."""
    T = next(iter(local_data.values())).shape[0]
    chunks: List[Dict[str, np.ndarray]] = []
    starts: List[Tuple[int, int]] = []  # (env, t) of each chunk's first step
    for e in range(num_envs):
        env_data = {k: local_data[k][:, e] for k in train_keys}
        ends = np.nonzero(local_data["dones"][:, e, 0])[0].tolist()
        ends.append(T - 1)
        start = 0
        for end in ends:
            stop = min(end + 1, T)  # include the done step
            if stop <= start:
                continue
            for i in range(start, stop, seq_len):
                chunks.append({k: v[i : min(i + seq_len, stop)] for k, v in env_data.items()})
                starts.append((e, i))
            start = stop
    n = len(chunks)
    n_pad = ((n + pad_multiple - 1) // pad_multiple) * pad_multiple
    out: Dict[str, np.ndarray] = {}
    for k in train_keys:
        proto = chunks[0][k]
        arr = np.zeros((seq_len, n_pad, *proto.shape[1:]), proto.dtype)
        for j, ch in enumerate(chunks):
            arr[: ch[k].shape[0], j] = ch[k]
        out[k] = arr
    mask = np.zeros((seq_len, n_pad, 1), np.float32)
    lengths = [ch[train_keys[0]].shape[0] for ch in chunks]
    for j, ln in enumerate(lengths):
        mask[:ln, j] = 1.0
    out["mask"] = mask
    for key in carry_keys:
        stored = local_data[key]
        first = np.zeros((n_pad, *stored.shape[2:]), stored.dtype)
        for j, (e, t) in enumerate(starts):
            first[j] = stored[t, e]
        out[key[len("prev_") :] + "0"] = first
    return out


def make_local_train(fabric, agent, tx, cfg, obs_keys, *, use_mesh: bool, sequence_dones: bool = False):
    """The UNJITTED masked-sequence update body (replaces reference train(),
    :31-116).  ``use_mesh`` guards the collectives (and the per-shard key
    fork) so the same body serves the ``shard_map``-ped host-path update and
    the fused superstep's embedded call.  ``sequence_dones`` marks batches
    whose sequences are FIXED windows that may cross episode boundaries (the
    fused rollout): the replay then resets the LSTM carry at the stored
    per-step dones (``evaluate_actions_resettable``) instead of assuming
    episode-aligned chunks."""
    update_epochs = int(cfg.algo.update_epochs)
    num_batches = max(1, int(cfg.algo.per_rank_num_batches))
    vf_coef = float(cfg.algo.vf_coef)
    clip_vloss = bool(cfg.algo.clip_vloss)
    normalize_adv = bool(cfg.algo.normalize_advantages)
    reduction = str(cfg.algo.loss_reduction)
    reset_on_done = bool(cfg.algo.reset_recurrent_state_on_done)
    data_axis = fabric.data_axis

    def local_train(params, opt_state, data, hx0, cx0, key, clip_coef, ent_coef):
        if use_mesh:
            key = jax.random.fold_in(key, lax.axis_index(data_axis))
        n_local = data["mask"].shape[1]
        bs = n_local // num_batches

        def minibatch_step(carry, xs):
            params, opt_state = carry
            batch, h0, c0 = xs

            def loss_fn(p):
                obs = {k: batch[k] for k in obs_keys}
                if sequence_dones:
                    logprobs, entropy, values = evaluate_actions_resettable(
                        agent,
                        p,
                        obs,
                        batch["prev_actions"],
                        h0,
                        c0,
                        batch["actions"],
                        batch["dones"],
                        reset_on_done=reset_on_done,
                    )
                else:
                    logprobs, entropy, values = evaluate_actions(
                        agent,
                        p,
                        obs,
                        batch["prev_actions"],
                        h0,
                        c0,
                        batch["actions"],
                    )
                mask = batch["mask"]
                msum = mask.sum() + 1e-8
                adv = batch["advantages"]
                if normalize_adv:
                    mean = (adv * mask).sum() / msum
                    var = (jnp.square(adv - mean) * mask).sum() / jnp.maximum(msum - 1, 1.0)
                    adv = (adv - mean) / (jnp.sqrt(var) + 1e-8)
                # the reference hardcodes 'mean' for the policy/value terms;
                # cfg.algo.loss_reduction only affects the entropy term
                # (reference train(), :82-101)
                pg = (policy_loss(logprobs, batch["logprobs"], adv, clip_coef, "none") * mask).sum() / msum
                v = (
                    value_loss(values, batch["values"], batch["returns"], clip_coef, clip_vloss, "none") * mask
                ).sum() / msum
                ent = (entropy_loss(entropy, "none") * mask).sum()
                if reduction == "mean":
                    ent = ent / msum
                return pg + vf_coef * v + ent_coef * ent, (pg, v, ent)

            (_, (pg, v, ent)), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            if use_mesh:
                grads = lax.pmean(grads, data_axis)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state), jnp.stack([pg, v, ent])

        def epoch_step(carry, _):
            params, opt_state, key = carry
            key, perm_key = jax.random.split(key)
            perm = jax.random.permutation(perm_key, n_local)[: num_batches * bs]
            minibatches = jax.tree.map(
                lambda x: jnp.moveaxis(
                    x[:, perm].reshape(x.shape[0], num_batches, bs, *x.shape[2:]), 1, 0
                ),
                data,
            )
            mb_h0 = hx0[perm].reshape(num_batches, bs, -1)
            mb_c0 = cx0[perm].reshape(num_batches, bs, -1)
            (params, opt_state), metrics = lax.scan(
                minibatch_step, (params, opt_state), (minibatches, mb_h0, mb_c0)
            )
            return (params, opt_state, key), metrics

        (params, opt_state, _), metrics = lax.scan(
            epoch_step, (params, opt_state, key), None, length=update_epochs
        )
        metrics = metrics.mean(axis=(0, 1))
        if use_mesh:
            metrics = lax.pmean(metrics, data_axis)
        return params, opt_state, metrics

    return local_train


def make_train_fn(fabric, agent, tx, cfg, obs_keys):
    """The host-path jitted update: :func:`make_local_train` ``shard_map``-ped
    over the data axis (sequences sharded, params/opt replicated, gradient
    ``pmean`` as the DDP all-reduce)."""
    data_axis = fabric.data_axis
    local_train = make_local_train(fabric, agent, tx, cfg, obs_keys, use_mesh=True)
    train_fn = shard_map(
        local_train,
        mesh=fabric.mesh,
        in_specs=(P(), P(), P(None, data_axis), P(data_axis), P(data_axis), P(), P(), P()),
        out_specs=(P(), P(), P()),
    )
    return jax.jit(train_fn, donate_argnums=(0, 1))


def _aggregator(cfg: Dict[str, Any]) -> MetricAggregator:
    aggregator = MetricAggregator(cfg.metric.get("aggregator", {}).get("metrics", {}) or {})
    for k in AGGREGATOR_KEYS - set(aggregator.metrics):
        aggregator.add(k, "mean")
    return aggregator


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    if cfg.checkpoint.resume_from:
        state = fabric.load(cfg.checkpoint.resume_from)

    if "minedojo" in str(cfg.env.wrapper.get("_target_", "")).lower():
        raise ValueError(
            "MineDojo is not currently supported by PPO Recurrent agent, since it does not take "
            "into consideration the action masks provided by the environment."
        )

    log_dir = get_log_dir(cfg)
    logger = get_logger(cfg, log_dir)
    fabric.logger = logger
    logger.log_hyperparams(cfg.to_dict() if hasattr(cfg, "to_dict") else dict(cfg))
    print(f"Log dir: {log_dir}")

    # preemption watcher + non-finite sentinel + checkpoint rollback
    resil = RunResilience(fabric, cfg, log_dir)

    initial_clip_coef = float(cfg.algo.clip_coef)
    initial_ent_coef = float(cfg.algo.ent_coef)

    rank = fabric.process_index
    num_envs = int(cfg.env.num_envs)
    envs = build_vector_env(cfg, rank, log_dir if rank == 0 else None, "train")
    observation_space = envs.single_observation_space
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

    if str((cfg.algo.get("core") or {}).get("name", "lstm")) == "decoder":
        # the decoder core (a policy over tokens): another carry, another loop body, the same main around it
        from sheeprl_tpu.algos.ppo_recurrent.token_policy import run_token_policy

        preempted = run_token_policy(
            fabric, cfg, envs, state if cfg.checkpoint.resume_from else None, log_dir, logger, resil, _aggregator(cfg)
        )
        envs.close()
        logger.finalize()
        resil.close()
        if preempted:
            resil.exit_preempted()
        return

    is_continuous = isinstance(envs.single_action_space, gym.spaces.Box)
    is_multidiscrete = isinstance(envs.single_action_space, gym.spaces.MultiDiscrete)
    actions_dim = tuple(
        envs.single_action_space.shape
        if is_continuous
        else (envs.single_action_space.nvec.tolist() if is_multidiscrete else [envs.single_action_space.n])
    )
    n_actions = int(np.sum(actions_dim))

    # scenario variants ride the fused rollout only (same contract as PPO);
    # `distractors` widens the observation the agent is built against
    # resolved unconditionally: enabled variants with the fused path off must
    # hit the loud RuntimeError below, never silently train the base env
    scenario_family = resolve_scenario_family(cfg)
    obs_widened = False
    if scenario_family is not None and not cnn_keys and len(mlp_keys) == 1:
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
    from sheeprl_tpu.parallel.fabric import resolve_player_device

    player = RecurrentPPOPlayer(
        agent, params, device=resolve_player_device(cfg.algo.get("player_device", "auto"))
    )

    rollout_steps = int(cfg.algo.rollout_steps)
    seq_len = int(cfg.algo.per_rank_sequence_length)
    world_size = fabric.data_parallel_size  # batch-split width: the data axis (= device count on a 1-D mesh)
    policy_steps_per_update = num_envs * rollout_steps * fabric.num_processes
    num_updates = int(cfg.algo.total_steps) // policy_steps_per_update if not cfg.dry_run else 1
    pad_multiple = world_size * max(1, int(cfg.algo.per_rank_num_batches))

    opt_cfg = dict(cfg.algo.optimizer.to_dict() if hasattr(cfg.algo.optimizer, "to_dict") else cfg.algo.optimizer)
    if cfg.algo.max_grad_norm and float(cfg.algo.max_grad_norm) > 0:
        opt_cfg["max_grad_norm"] = float(cfg.algo.max_grad_norm)
    if cfg.algo.anneal_lr:
        steps_per_update = int(cfg.algo.update_epochs) * max(1, int(cfg.algo.per_rank_num_batches))
        opt_cfg["schedule"] = optax.linear_schedule(
            float(opt_cfg.get("lr", 1e-3)), 0.0, num_updates * steps_per_update
        )
    tx = instantiate(opt_cfg)
    opt_state = fabric.replicate(tx.init(jax.device_get(params)))
    if cfg.checkpoint.resume_from:
        opt_state = fabric.replicate(
            jax.tree.map(jnp.asarray, state["opt_state"], is_leaf=lambda x: isinstance(x, np.ndarray))
        )

    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = _aggregator(cfg)

    train_fn = make_train_fn(fabric, agent, tx, cfg, obs_keys)
    gae_fn = jax.jit(partial(gae, gamma=float(cfg.algo.gamma), gae_lambda=float(cfg.algo.gae_lambda)))

    # fused on-policy collection (`algo.fused_rollout`, ported from PPO): the
    # T-step rollout — LSTM state carried through the scan — plus GAE and the
    # whole epochs x minibatches update compile into ONE donated jit
    num_batches = max(1, int(cfg.algo.per_rank_num_batches))
    fused_rollout = bool(cfg.algo.get("fused_rollout", False))
    reset_fused_fallback_warnings()
    fused_spec = None
    if fused_rollout:
        fused_spec = resolve_fused_rollout_spec(
            cfg, fabric, cnn_keys, mlp_keys, observation_space, is_continuous, is_multidiscrete, actions_dim
        )
        if fused_spec is not None and rollout_steps % seq_len != 0:
            fused_fallback(
                "recurrent_seq",
                f"algo.rollout_steps ({rollout_steps}) must be a multiple of "
                f"per_rank_sequence_length ({seq_len}) for fixed-window fused sequences",
            )
            fused_spec = None
        if fused_spec is not None and num_envs % world_size != 0:
            fused_fallback(
                "env_shard", f"env.num_envs ({num_envs}) must be divisible by the device count ({world_size})"
            )
            fused_spec = None
        if fused_spec is not None:
            n_seq_local = (rollout_steps // seq_len) * (num_envs // world_size)
            if n_seq_local % num_batches != 0:
                # the in-graph minibatch permutation truncates to
                # num_batches * bs — an indivisible count would drop sequences
                fused_fallback(
                    "sequence_batches",
                    f"per-shard sequence count ({n_seq_local}) must be divisible by "
                    f"per_rank_num_batches ({num_batches})",
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
        superstep_fn = make_recurrent_onpolicy_superstep_fn(
            fused_spec,
            policy_fn=partial(recurrent_rollout_step, agent),
            value_fn=lambda p, o, pa, hx, cx: agent.apply(p, o, pa, hx, cx)[1],
            local_train=make_local_train(
                fabric, agent, tx, cfg, obs_keys, use_mesh=True, sequence_dones=True
            ),
            obs_key=mlp_keys[0],
            rollout_steps=rollout_steps,
            seq_len=seq_len,
            step_increment=num_envs * fabric.num_processes,
            gamma=float(cfg.algo.gamma),
            gae_lambda=float(cfg.algo.gae_lambda),
            reset_on_done=bool(cfg.algo.reset_recurrent_state_on_done),
            mesh=fabric.mesh,
            data_axis=fabric.data_axis,
        )

    start_update = (state["update"] + 1) if cfg.checkpoint.resume_from else 1
    policy_step = state["update"] * policy_steps_per_update if cfg.checkpoint.resume_from else 0
    last_log = state["last_log"] if cfg.checkpoint.resume_from else 0
    last_checkpoint = state["last_checkpoint"] if cfg.checkpoint.resume_from else 0
    train_step = 0
    last_train = 0

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

    clip_coef = float(cfg.algo.clip_coef)
    ent_coef = float(cfg.algo.ent_coef)
    reset_on_done = bool(cfg.algo.reset_recurrent_state_on_done)

    next_obs, _ = envs.reset(seed=cfg.seed)
    next_obs = prepare_obs(next_obs, cnn_keys=cnn_keys, num_envs=num_envs)
    hx = np.zeros((num_envs, agent.lstm_hidden_size), np.float32)
    cx = np.zeros((num_envs, agent.lstm_hidden_size), np.float32)
    prev_actions = np.zeros((num_envs, n_actions), np.float32)

    def ckpt_state_fn(completed_update: int) -> Dict[str, Any]:
        # shared by the periodic save and the preemption drain's emergency
        # save: reads the loop's CURRENT bindings at call time
        return {
            "agent": jax.device_get(params),
            "opt_state": jax.device_get(opt_state),
            "update": completed_update,
            "batch_size": int(cfg.algo.per_rank_batch_size) * world_size,
            "last_log": last_log,
            "last_checkpoint": last_checkpoint,
            "rng_key": jax.device_get(key),
            "player_rng_key": jax.device_get(player_key),
        }

    def ckpt_path_fn(step: int) -> str:
        return os.path.join(log_dir, "checkpoint", f"ckpt_{step}_{rank}.ckpt")

    def drain_if_preempted(update: int) -> bool:
        # the update has NOT run yet: the emergency checkpoint records
        # update-1 so auto-resume replays from exactly this boundary
        nonlocal last_checkpoint
        if not resil.preempt_requested():
            return False
        last_checkpoint = policy_step
        resil.emergency_checkpoint(ckpt_path_fn(policy_step), ckpt_state_fn(update - 1))
        return True

    # a crash anywhere in the loop gets the preemption treatment too
    resil.arm_crash_guard(
        path_fn=lambda: ckpt_path_fn(policy_step),
        state_fn=lambda: ckpt_state_fn(update - 1),
    )
    preempted = False
    steps_per_dispatch = int(cfg.algo.update_epochs) * num_batches
    if superstep_fn is not None:
        # ------------------------------------------------------------------
        # fused on-policy path: the rollout (LSTM carry riding the scan),
        # GAE, sequence windowing and the epochs x minibatches update are ONE
        # donated jit; the metrics fetch is the only host sync per update
        # ------------------------------------------------------------------
        def place_carry(carry):
            return jax.tree.map(lambda x: jax.device_put(x, fabric.batch_sharding), carry)

        key = jax.device_put(key, fabric.replicated)
        # one scenario row per env for the run's lifetime (PPO's contract)
        thetas = (
            scenario_theta_matrix(cfg, fused_spec, num_envs)
            if isinstance(fused_spec, ScenarioFamily)
            else None
        )
        env_carry = place_carry(
            init_recurrent_env_carry(
                fused_spec,
                num_envs,
                jax.random.fold_in(jax.random.PRNGKey(int(cfg.seed)), ENV_STREAM_SALT),
                hidden_size=agent.lstm_hidden_size,
                action_dim=n_actions,
                thetas=thetas,
            )
        )
        for update in range(start_update, num_updates + 1):
            telemetry_advance(policy_step)
            if drain_if_preempted(update):
                preempted = True
                break
            if update == start_update + 1:
                # no bench probe in this loop — warm the recompile watchdog here
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
                    # host numpy scalars — jnp.float32 would materialize them
                    # on the default backend every update (see ppo.py)
                    np.float32(clip_coef),
                    np.float32(ent_coef),
                )
                policy_step += policy_steps_per_update
                metrics = np.asarray(metrics)
            telemetry_train_window(1, steps_per_dispatch)
            train_step += world_size
            if update == start_update:
                # one dispatch covers collection AND all gradient steps, so
                # scale the program flops down to per-gradient-step for MFU
                telemetry_register_flops(
                    superstep_fn,
                    params,
                    opt_state,
                    env_carry,
                    update_key,
                    key,
                    np.uint32(step_before),
                    np.float32(clip_coef),
                    np.float32(ent_coef),
                    scale=1.0 / steps_per_dispatch,
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
                aggregator.update("Loss/entropy_loss", float(metrics[2]))
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
                        train_invocations=(train_step - last_train) // world_size,
                    )
                    last_log = policy_step
                    last_train = train_step
            if cfg.algo.anneal_clip_coef:
                clip_coef = polynomial_decay(
                    update, initial=initial_clip_coef, final=0.0, max_decay_steps=num_updates, power=1.0
                )
            if cfg.algo.anneal_ent_coef:
                ent_coef = polynomial_decay(
                    update, initial=initial_ent_coef, final=0.0, max_decay_steps=num_updates, power=1.0
                )
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
        # list appends (or the defensive hx/cx/prev_actions .copy()s: the indexed
        # write is itself the copy), no end-of-window np.stack
        store = RolloutStore(rollout_steps)
        for update in range(start_update, num_updates + 1):
            if drain_if_preempted(update):
                preempted = True
                break
            buf = store.begin(update)
            with timer("Time/env_interaction_time"):
                # fused rollout step: key folding, sampling and the real-action
                # conversion in one jitted dispatch + one fetch per env step
                update_key = player_key
                for t in range(rollout_steps):
                    policy_step += num_envs * fabric.num_processes
                    obs_t = {k: v[None] for k, v in next_obs.items()}
                    actions, real_actions, logprobs, values, new_hx, new_cx = player.rollout_actions(
                        obs_t, prev_actions[None], hx, cx, update_key, policy_step
                    )
                    actions_np, real_actions, logprobs_np, values_np, new_hx, new_cx = jax.device_get(
                        (actions, real_actions, logprobs, values, new_hx, new_cx)
                    )
                    actions_np = actions_np[0]
                    logprobs_np = logprobs_np[0]
                    values_np = values_np[0]
                    real_actions = real_actions[0]
                    if not is_continuous and real_actions.shape[-1] == 1 and not is_multidiscrete:
                        real_actions = real_actions[..., 0]

                    obs, rewards, terminated, truncated, info = envs.step(
                        real_actions.reshape(envs.action_space.shape)
                    )
                    rewards = np.asarray(rewards, dtype=np.float32).reshape(num_envs, 1)

                    # truncation bootstrap with the POST-step recurrent state
                    # (reference :312-336)
                    truncated_envs = np.nonzero(truncated)[0]
                    if len(truncated_envs) > 0 and "final_obs" in info:
                        final_obs = {
                            k: np.stack([np.asarray(info["final_obs"][e][k]) for e in truncated_envs])
                            for k in obs_keys
                        }
                        final_obs = prepare_obs(final_obs, cnn_keys=cnn_keys, num_envs=len(truncated_envs))
                        vals = np.asarray(
                            player.get_values(
                                {k: v[None] for k, v in final_obs.items()},
                                actions_np[truncated_envs][None],
                                new_hx[truncated_envs],
                                new_cx[truncated_envs],
                            )
                        ).reshape(len(truncated_envs))
                        rewards[truncated_envs, 0] += float(cfg.algo.gamma) * vals

                    dones = np.logical_or(terminated, truncated).reshape(num_envs, 1).astype(np.float32)
                    step_values = {k: next_obs[k] for k in obs_keys}
                    step_values["dones"] = dones
                    step_values["values"] = values_np
                    step_values["actions"] = actions_np
                    step_values["logprobs"] = logprobs_np
                    step_values["rewards"] = rewards
                    step_values["prev_hx"] = hx
                    step_values["prev_cx"] = cx
                    step_values["prev_actions"] = prev_actions
                    buf.put(t, step_values)

                    prev_actions = (1 - dones) * actions_np
                    if reset_on_done:
                        hx = (1 - dones) * new_hx
                        cx = (1 - dones) * new_cx
                    else:
                        hx, cx = new_hx, new_cx
                    next_obs = prepare_obs(obs, cnn_keys=cnn_keys, num_envs=num_envs)

                    if cfg.metric.log_level > 0 and "final_info" in info:
                        ep = info["final_info"].get("episode")
                        if ep is not None:
                            for i in np.nonzero(ep.get("_r", []))[0]:
                                aggregator.update("Rewards/rew_avg", float(ep["r"][i]))
                                aggregator.update("Game/ep_len_avg", float(ep["l"][i]))
                                print(f"Rank-0: policy_step={policy_step}, reward_env_{i}={ep['r'][i]}")

            local_data = buf.arrays()  # [T, E, ...]

            # GAE on device (reference :386-398)
            next_values = np.asarray(
                player.get_values({k: v[None] for k, v in next_obs.items()}, prev_actions[None], hx, cx)
            )[0]
            returns, advantages = gae_fn(
                jnp.asarray(local_data["rewards"]),
                jnp.asarray(local_data["values"]),
                jnp.asarray(local_data["dones"]),
                jnp.asarray(next_values),
            )
            local_data["returns"] = np.asarray(returns)
            local_data["advantages"] = np.asarray(advantages)

            # episode split + fixed-length chunking + padding (reference :406-444)
            train_keys = [*obs_keys, "actions", "logprobs", "values", "returns", "advantages", "prev_actions"]
            sequences = build_sequences(local_data, train_keys, seq_len, num_envs, pad_multiple)
            hx0 = sequences.pop("hx0")
            cx0 = sequences.pop("cx0")
            if fabric.num_processes > 1:
                # every process must contribute the SAME padded count to the
                # global array — agree on the max and pad with masked dummies
                from sheeprl_tpu.parallel.collectives import all_gather_object

                n_here = sequences["mask"].shape[1]
                n_target = max(all_gather_object(n_here))
                if n_here < n_target:
                    extra = n_target - n_here
                    sequences = {
                        k: np.concatenate(
                            [v, np.zeros((v.shape[0], extra, *v.shape[2:]), v.dtype)], axis=1
                        )
                        for k, v in sequences.items()
                    }
                    hx0 = np.concatenate([hx0, np.zeros((extra, hx0.shape[1]), hx0.dtype)], axis=0)
                    cx0 = np.concatenate([cx0, np.zeros((extra, cx0.shape[1]), cx0.dtype)], axis=0)
                sequences = fabric.make_global(sequences, (None, fabric.data_axis))
                hx0 = fabric.make_global(hx0, (fabric.data_axis,))
                cx0 = fabric.make_global(cx0, (fabric.data_axis,))

            with timer("Time/train_time"):
                key, train_key = jax.random.split(key)
                params, opt_state, metrics = train_fn(
                    params,
                    opt_state,
                    sequences,
                    hx0,
                    cx0,
                    train_key,
                    # host numpy scalars — jnp.float32 would materialize them on
                    # the default backend every update (see ppo.py)
                    np.float32(clip_coef),
                    np.float32(ent_coef),
                )
                # one host fetch serves the sync point and the three aggregator
                # scalars below — block_until_ready plus a second asarray (or a
                # blocking transfer per float()) would each be an extra round trip
                metrics = np.asarray(metrics)
            player.params = params
            train_step += world_size

            if cfg.metric.log_level > 0:
                aggregator.update("Loss/policy_loss", float(metrics[0]))
                aggregator.update("Loss/value_loss", float(metrics[1]))
                aggregator.update("Loss/entropy_loss", float(metrics[2]))

                if policy_step - last_log >= cfg.metric.log_every or update == num_updates:
                    metrics_dict = aggregator.compute()
                    logger.log_metrics(metrics_dict, policy_step)
                    aggregator.reset()
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
                                        (policy_step - last_log) * cfg.env.action_repeat
                                    )
                                    / timer_metrics["Time/env_interaction_time"]
                                },
                                policy_step,
                            )
                        timer.reset()
                    last_log = policy_step
                    last_train = train_step

            if cfg.algo.anneal_clip_coef:
                clip_coef = polynomial_decay(
                    update, initial=initial_clip_coef, final=0.0, max_decay_steps=num_updates, power=1.0
                )
            if cfg.algo.anneal_ent_coef:
                ent_coef = polynomial_decay(
                    update, initial=initial_ent_coef, final=0.0, max_decay_steps=num_updates, power=1.0
                )

            if (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every) or (
                update == num_updates and cfg.checkpoint.save_last
            ):
                last_checkpoint = policy_step
                fabric.call(
                    "on_checkpoint_coupled", ckpt_path=ckpt_path_fn(policy_step), state=ckpt_state_fn(update)
                )

    envs.close()
    if fabric.is_global_zero and cfg.algo.run_test and not preempted:
        if obs_widened:
            import warnings

            warnings.warn("skipping run_test: env.variants widened the observation past the host env's")
        else:
            test(player, fabric, cfg, log_dir)
    logger.finalize()
    resil.close()
    if preempted:
        resil.exit_preempted()
