"""Dreamer-V3 (reference: sheeprl/algos/dreamer_v3/dreamer_v3.py:48-776) —
TPU-native.

The redesign (SURVEY.md §7 hard parts, all addressed here):

- **RSSM + imagination as ``lax.scan``** inside ONE jitted train step per
  gradient step — the reference runs two Python loops over GRU cells
  (dreamer_v3.py:134-145, :235-241).
- **All three optimizations fused**: world model, actor, critic updates (plus
  the Moments percentile sync) execute in a single XLA program; the
  reference dispatches dozens of kernels per phase.
- **DP via shard_map**: the batch axis of the ``[T, B, ...]`` sequence batch
  is split across the mesh's data axis; per-minibatch gradient ``pmean`` and
  the Moments ``all_gather`` (reference ``fabric.all_gather``,
  utils.py:57) are mesh collectives over ICI.
- **Variable replay ratio stays on host**: ``Ratio`` yields G gradient steps
  per policy step; the host loops G times over the jitted step (fixed
  shapes), exactly the reference's structure (dreamer_v3.py:657-693).
- Pixels stay uint8 through the buffer and PCIe; normalization happens
  in-graph (encoder) and in the loss targets.
"""

from __future__ import annotations

import collections
import os
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from sheeprl_tpu.parallel.shard_map import shard_map
from jax.sharding import PartitionSpec as P

from sheeprl_tpu.algos.dreamer_v3.agent import (
    WorldModel,
    actor_logprob_entropy,
    build_agent,
    rssm_scan,
    rssm_scan_kernels,
    sample_actor_actions,
)
from sheeprl_tpu.algos.dreamer_v3.loss import reconstruction_loss
from sheeprl_tpu.algos.dreamer_v3.utils import AGGREGATOR_KEYS, prepare_obs, test
from sheeprl_tpu.ops.optim import build_tx
from sheeprl_tpu.data.device_buffer import (
    DeviceReplayBuffer,
    adapt_restored_buffer,
    draw_sequence_batch,
    make_sequential_replay,
)
from sheeprl_tpu.data.prefetch import sampled_batches
from sheeprl_tpu.ops.superstep import (
    fold_sample_key,
    fused_fallback,
    make_superstep_fn,
    periodic_target_ema,
    pregathered,
    reset_fused_fallback_warnings,
)
from sheeprl_tpu.envs import build_vector_env
from sheeprl_tpu.ops.distributions import (
    Bernoulli,
    Independent,
    MSEDistribution,
    OneHotCategorical,
    SymlogDistribution,
    TwoHotEncodingDistribution,
)
from sheeprl_tpu.obs import (
    log_sps_and_heartbeat,
    telemetry_advance,
    telemetry_counters,
    telemetry_mark_warm_after_warmup,
    telemetry_register_flops,
    telemetry_run_metrics,
    telemetry_train_window,
)
from sheeprl_tpu.ops.math import MomentsState, compute_lambda_values, init_moments, update_moments
from sheeprl_tpu.resilience import RunResilience
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, save_configs

METRIC_ORDER = (
    "Loss/world_model_loss",
    "Loss/observation_loss",
    "Loss/reward_loss",
    "Loss/state_loss",
    "Loss/continue_loss",
    "State/kl",
    "State/post_entropy",
    "State/prior_entropy",
    "Loss/policy_loss",
    "Loss/value_loss",
    "Grads/world_model",
    "Grads/actor",
    "Grads/critic",
)


#: arguments of the per-step train program whose buffers its results take over:
#: the three parameter trees, the three optimizer states and the moments
TRAIN_STEP_DONATED = (0, 1, 2, 4, 5, 6, 7)


def result_buffers(args: Sequence[Any], out: Any, donated: Sequence[int]) -> Dict[str, int]:
    """How many result leaves of a jitted call can take over the buffer of a
    donated argument leaf (one of the same shape and dtype, each used once, as
    ``jax.jit`` pairs them), and how many the runtime has to allocate afresh at
    every dispatch."""
    free = collections.Counter(
        (leaf.shape, jnp.dtype(leaf.dtype)) for i in donated for leaf in jax.tree.leaves(args[i])
    )
    results = collections.Counter((leaf.shape, jnp.dtype(leaf.dtype)) for leaf in jax.tree.leaves(out))
    total = sum(results.values())
    aliased = sum(min(n, free[aval]) for aval, n in results.items())
    return {"result_leaves": total, "aliased_result_leaves": aliased, "fresh_result_leaves": total - aliased}


def make_train_step(
    fabric,
    wm: WorldModel,
    actor,
    critic,
    world_tx,
    actor_tx,
    critic_tx,
    cfg: Dict[str, Any],
    is_continuous: bool,
    actions_dim: Sequence[int],
):
    """The raw (un-jitted) single-gradient-step body over a ``[T, B_local]``
    sequence batch (replaces reference train(), dreamer_v3.py:48-354).
    Returns ``(local_train, use_shard_map)`` — :func:`make_train_fn` wraps it
    in shard_map/jit for the per-step path, :func:`make_fused_train_fn`
    scans it inside one fused superstep dispatch.

    The body's parts carry one ``jax.named_scope`` each, so a device trace
    says where the step's time goes (howto/telemetry.md lists the names):
    ``dv3/wm/encode``, ``dv3/wm/rssm_scan``, ``dv3/wm/decode``,
    ``dv3/wm/optimizer``, ``dv3/behaviour/imagine``,
    ``dv3/behaviour/actor_loss``, ``dv3/behaviour/optimizer``,
    ``dv3/critic/loss``, ``dv3/critic/optimizer``. A scope's backward ops
    carry it inside ``transpose(jvp(...))``."""
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
    moments_cfg = algo.actor.moments
    data_axis = fabric.data_axis
    multi_device = fabric.world_size > 1
    # Two multi-device modes: pure DP uses shard_map + explicit collectives;
    # a mesh with a `model` axis instead jits the GLOBAL computation and lets
    # GSPMD partition it from the committed input shardings (params placed by
    # fabric.shard_params, batch on the data axis) — explicit pmean/all_gather
    # would be wrong there because the jitted program already has global
    # semantics.
    use_shard_map = multi_device and fabric.model_axis is None

    def pmean(x):
        return lax.pmean(x, data_axis) if use_shard_map else x

    def local_train(
        wm_params,
        actor_params,
        critic_params,
        target_params,
        world_opt,
        actor_opt,
        critic_opt,
        moments_state,
        data,
        key,
    ):
        if use_shard_map:
            key = jax.random.fold_in(key, lax.axis_index(data_axis))
        k_scan, k_img = jax.random.split(key)
        sg = lax.stop_gradient

        T = data["rewards"].shape[0]
        B = data["rewards"].shape[1]
        is_first = data["is_first"].at[0].set(1.0)
        # shift actions right: a_t in the RSSM input is the action LEADING to o_t
        batch_actions = jnp.concatenate(
            [jnp.zeros_like(data["actions"][:1]), data["actions"][:-1]], axis=0
        )
        batch_obs = {k: data[k] for k in cnn_keys + mlp_keys}
        # loss targets (decoder outputs are normalized pixels)
        obs_targets = {k: data[k].astype(jnp.float32) / 255.0 - 0.5 for k in cnn_dec_keys}
        obs_targets.update({k: data[k].astype(jnp.float32) for k in mlp_dec_keys})

        # ---------------- world model step (Eq. 4/5) ---------------- #
        def world_loss_fn(p):
            with jax.named_scope("dv3/wm/encode"):
                embedded = wm.apply(p, batch_obs, method=WorldModel.encode)
            # as the step is traced, so once for each program built on it: whose gradient leaves the scan's backward loop
            telemetry_counters("dv3/rssm_scan", **rssm_scan_kernels(wm, p, embedded, batch_actions, is_first, k_scan))
            with jax.named_scope("dv3/wm/rssm_scan"):
                hs, zs, post_logits, prior_logits = rssm_scan(wm, p, embedded, batch_actions, is_first, k_scan)
            with jax.named_scope("dv3/wm/decode"):
                latents = jnp.concatenate([zs, hs], axis=-1)
                recon = wm.apply(p, latents, method=WorldModel.decode)
                po = {k: MSEDistribution(recon[k], dims=3) for k in cnn_dec_keys}
                po.update({k: SymlogDistribution(recon[k], dims=1) for k in mlp_dec_keys})
                pr = TwoHotEncodingDistribution(wm.apply(p, latents, method=WorldModel.reward_logits), dims=1)
                pc = Independent(Bernoulli(logits=wm.apply(p, latents, method=WorldModel.continue_logits)), 1)
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
        with jax.named_scope("dv3/wm/optimizer"):
            wm_grads = pmean(wm_grads)
            wm_gnorm = optax.global_norm(wm_grads)
            wm_updates, world_opt = world_tx.update(wm_grads, world_opt, wm_params)
            wm_params = optax.apply_updates(wm_params, wm_updates)

        # ---------------- behaviour learning ---------------- #
        # imagination starts from every (t, b) posterior, flattened
        start_z = sg(zs).reshape(T * B, -1)
        start_h = sg(hs).reshape(T * B, -1)
        true_continue = (1 - data["terminated"]).reshape(T * B, 1)

        def imagine(actor_params, key):
            """Imagination rollout (reference dreamer_v3.py:203-241):
            ``lats[i]`` is the i-th latent, ``acts[i]`` the action sampled at
            it; the scan body advances to ``lats[i+1]`` — H+1 entries."""

            def step(carry, _):
                z, h, lat, key = carry
                key, k_act, k_state = jax.random.split(key, 3)
                action = sample_actor_actions(actor, actor_params, sg(lat), k_act)
                z, h = wm.apply(wm_params, z, h, action, k_state, method=WorldModel.imagination)
                new_lat = jnp.concatenate([z, h], axis=-1)
                return (z, h, new_lat, key), (lat, action)

            with jax.named_scope("dv3/behaviour/imagine"):
                lat0 = jnp.concatenate([start_z, start_h], axis=-1)
                _, (lats, acts) = lax.scan(step, (start_z, start_h, lat0, key), None, length=horizon + 1)
            return lats, acts

        def actor_loss_fn(p):
            trajectories, imagined_actions = imagine(p, k_img)  # [H+1, N, L] / [H+1, N, A]

            with jax.named_scope("dv3/behaviour/actor_loss"):
                values = TwoHotEncodingDistribution(critic.apply(critic_params, trajectories), dims=1).mean
                rewards = TwoHotEncodingDistribution(
                    wm.apply(wm_params, trajectories, method=WorldModel.reward_logits), dims=1
                ).mean
                continues = Independent(
                    Bernoulli(logits=wm.apply(wm_params, trajectories, method=WorldModel.continue_logits)), 1
                ).mode
                continues = jnp.concatenate([true_continue[None], continues[1:]], axis=0)

                lambda_values = compute_lambda_values(rewards[1:], values[1:], continues[1:] * gamma, lmbda)
                discount = sg(jnp.cumprod(continues * gamma, axis=0) / gamma)

                new_moments, (offset, invscale) = update_moments(
                    moments_state,
                    lambda_values,
                    decay=float(moments_cfg.decay),
                    max_=float(moments_cfg.max),
                    percentile_low=float(moments_cfg.percentile.low),
                    percentile_high=float(moments_cfg.percentile.high),
                    axis_name=data_axis if use_shard_map else None,
                )
                baseline = values[:-1]
                normed_lambda = (lambda_values - offset) / invscale
                normed_baseline = (baseline - offset) / invscale
                advantage = normed_lambda - normed_baseline
                logp, entropy = actor_logprob_entropy(actor, p, sg(trajectories), sg(imagined_actions))
                if is_continuous:
                    objective = advantage
                else:
                    objective = logp[..., None][:-1] * sg(advantage)
                policy_loss = -jnp.mean(
                    sg(discount[:-1]) * (objective + ent_coef * entropy[..., None][:-1])
                )
            return policy_loss, (trajectories, lambda_values, discount, new_moments)

        (policy_loss, (trajectories, lambda_values, discount, moments_state)), actor_grads = jax.value_and_grad(
            actor_loss_fn, has_aux=True
        )(actor_params)
        with jax.named_scope("dv3/behaviour/optimizer"):
            actor_grads = pmean(actor_grads)
            actor_gnorm = optax.global_norm(actor_grads)
            actor_updates, actor_opt = actor_tx.update(actor_grads, actor_opt, actor_params)
            actor_params = optax.apply_updates(actor_params, actor_updates)

        # ---------------- critic step (Eq. 10) ---------------- #
        with jax.named_scope("dv3/critic/loss"):
            traj_in = sg(trajectories[:-1])
            target_values = TwoHotEncodingDistribution(critic.apply(target_params, traj_in), dims=1).mean

            def critic_loss_fn(p):
                qv = TwoHotEncodingDistribution(critic.apply(p, traj_in), dims=1)
                value_loss = -qv.log_prob(sg(lambda_values)) - qv.log_prob(sg(target_values))
                return jnp.mean(value_loss * sg(discount[:-1]).squeeze(-1))

            value_loss, critic_grads = jax.value_and_grad(critic_loss_fn)(critic_params)
        with jax.named_scope("dv3/critic/optimizer"):
            critic_grads = pmean(critic_grads)
            critic_gnorm = optax.global_norm(critic_grads)
            critic_updates, critic_opt = critic_tx.update(critic_grads, critic_opt, critic_params)
            critic_params = optax.apply_updates(critic_params, critic_updates)

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
                    policy_loss,
                    value_loss,
                    wm_gnorm,
                    actor_gnorm,
                    critic_gnorm,
                ]
            )
        )
        return (
            wm_params,
            actor_params,
            critic_params,
            world_opt,
            actor_opt,
            critic_opt,
            moments_state,
            metrics,
        )

    return local_train, use_shard_map


def make_train_fn(
    fabric,
    wm: WorldModel,
    actor,
    critic,
    world_tx,
    actor_tx,
    critic_tx,
    cfg: Dict[str, Any],
    is_continuous: bool,
    actions_dim: Sequence[int],
):
    """One fused gradient step over a ``[T, B_local]`` sequence batch
    (replaces reference train(), dreamer_v3.py:48-354)."""
    local_train, use_shard_map = make_train_step(
        fabric, wm, actor, critic, world_tx, actor_tx, critic_tx, cfg, is_continuous, actions_dim
    )
    if use_shard_map:
        data_axis = fabric.data_axis
        train_fn = shard_map(
            local_train,
            mesh=fabric.mesh,
            in_specs=(P(), P(), P(), P(), P(), P(), P(), P(), P(None, data_axis), P()),
            out_specs=(P(), P(), P(), P(), P(), P(), P(), P()),
        )
    else:
        # single device, or a model-axis mesh: GSPMD partitions the global
        # program from the inputs' committed shardings
        train_fn = local_train
    # Every argument that comes back as a result is donated, the three
    # parameter trees too, so each result leaf but the metrics vector lands in
    # the buffer its argument held: a fresh result buffer costs the host some
    # 47 us to allocate, 174 of them at XL at the head of every train window
    # (howto/telemetry.md, ``dv3/train_step_buffers``). Argument 3, the target
    # critic, is no result and stays. What makes that sound is the loops' rule
    # that no handle on a parameter tree outlives the next train dispatch, and
    # what each reader of the parameters does about it:
    # - the player on the learner's device holds the step's own result arrays;
    #   all it enqueues on them is enqueued before the next window's first
    #   dispatch, which the runtime orders behind those reads, and it is handed
    #   the newest trees right behind the window's last dispatch;
    # - the host player's stream packs a tree into one vector with a program
    #   enqueued before ``update_params`` returns and keeps that vector, never
    #   a leaf (``fabric._StreamPipe``);
    # - the target refresh is a program of its own (``dv3_target_ema``),
    #   enqueued before the next dispatch, and its result is fresh;
    # - checkpoints ``device_get`` the loop's current bindings, the newest
    #   trees; after a dispatch that raised those are gone, and the crash
    #   guard's checkpoint fails as one that could not be written;
    # - a NaN rollback restores from the newest committed checkpoint and reads
    #   of the live trees only where they are placed (``resil.place_like``).
    def dv3_train_step(*args):
        # the jitted function's name is the XLA module's (``jit_dv3_train_step``):
        # what a device trace files the step's ops under
        out = train_fn(*args)
        # as the step is traced, so once for each program built on it
        telemetry_counters("dv3/train_step_buffers", **result_buffers(args, out, TRAIN_STEP_DONATED))
        return out

    return jax.jit(dv3_train_step, donate_argnums=TRAIN_STEP_DONATED)


def make_fused_train_fn(
    fabric,
    wm: WorldModel,
    actor,
    critic,
    world_tx,
    actor_tx,
    critic_tx,
    cfg: Dict[str, Any],
    is_continuous: bool,
    actions_dim: Sequence[int],
    gather,
    num_steps: int,
    ctx_spec=None,
    carry_specs=None,
    check_finite: bool = False,
):
    """``num_steps`` gradient steps — replay gather, EMA target refresh and
    train body — fused into ONE donated dispatch (``algo.fused_gradient_steps``;
    see :mod:`sheeprl_tpu.ops.superstep`). On a pure data-parallel mesh the
    whole scan runs under shard_map over ``fabric.data_axis``: the body is
    the same ``local_train`` the per-step sharded path uses (it pmeans
    gradients and metrics), ``gather`` must draw shard-locally
    (``fold_sample_key(..., axis_name=fabric.data_axis)``), and ``ctx_spec``
    gives the sample context's partition spec.

    On a 2-D ``(data, model)`` mesh the scan is one GSPMD program instead:
    pass ``carry_specs=(param_specs, aux_specs)`` (PartitionSpec trees from
    ``fabric.match_partition_rules`` over the exact ``params``/``aux``
    tuples) so the jitted superstep commits params AND their optimizer/EMA
    twins to the model-axis layout and keeps each W2 shard device-resident
    across the window; the body is the same GSPMD ``local_train`` the
    per-step model-axis path uses (no pmean), and ``gather`` must be the
    :func:`~sheeprl_tpu.ops.superstep.pregathered` host stack (the device
    replay ring is pure-DP only).

    The jitted fn's signature is ``(params, aux, counter, sample_ctx, key) ->
    (params, aux, key, metrics[num_steps, len(METRIC_ORDER)])`` with
    ``params = (wm, actor, critic, target_critic)`` (un-donated) and ``aux =
    (world_opt, actor_opt, critic_opt, moments_state)`` (donated).
    ``check_finite=True`` appends the superstep's ``[num_steps]`` finite
    vector (resilience NaN sentinel) as a fifth output."""
    local_train, use_shard_map = make_train_step(
        fabric, wm, actor, critic, world_tx, actor_tx, critic_tx, cfg, is_continuous, actions_dim
    )
    freq = max(1, int(cfg.algo.critic.per_rank_target_network_update_freq))
    tau = float(cfg.algo.critic.tau)

    def train_body(params, aux, batch, key):
        wm_p, a_p, c_p, t_p = params
        wm_p, a_p, c_p, w_o, a_o, c_o, m_s, metrics = local_train(
            wm_p, a_p, c_p, t_p, *aux, batch, key
        )
        return (wm_p, a_p, c_p, t_p), (w_o, a_o, c_o, m_s), metrics

    def pre_step(params, aux, counter):
        # the host loop refreshes the target BEFORE the step on the same
        # schedule (cumulative % freq == 0, hard copy at step 0)
        wm_p, a_p, c_p, t_p = params
        t_p = periodic_target_ema(counter, c_p, t_p, freq, tau)
        return (wm_p, a_p, c_p, t_p), aux

    model_axis = fabric.model_axis if carry_specs is not None else None
    # fabric.aot_cache_dir persists the fused-window executable: the
    # fingerprint digests the algo node + precision (every constant baked
    # into the train graph — lr, tau, horizon, loss scales), so a resume
    # with identical config deserializes in seconds while ANY algo tweak
    # misses cleanly and recompiles
    aot_cache = getattr(fabric, "aot_cache", None)
    cache_fingerprint = None
    if aot_cache is not None:
        from sheeprl_tpu.ops.aotcache import config_fingerprint

        cache_fingerprint = config_fingerprint(
            {"algo": cfg.algo, "precision": str(getattr(fabric, "precision", ""))}
        )
    return make_superstep_fn(
        train_body,
        gather,
        num_steps,
        pre_step=pre_step,
        mesh=fabric.mesh if (use_shard_map or model_axis is not None) else None,
        data_axis=fabric.data_axis if use_shard_map else None,
        ctx_spec=ctx_spec,
        model_axis=model_axis,
        carry_specs=carry_specs,
        check_finite=check_finite,
        aot_cache=aot_cache,
        cache_tag="superstep.dreamer_v3",
        cache_fingerprint=cache_fingerprint,
    )


class QueuedForward(NamedTuple):
    """The next turn's player forward, dispatched behind this turn's train
    steps; what a redo after a rollback needs is kept beside the action."""

    action: Any  # on the device, its copy to the host started
    player_key: Any  # the player's key once that turn has split it
    action_key: Any
    state_before: Tuple[Any, Any, Any]  # the player's h / z / actions the forward started from
    obs: Dict[str, np.ndarray]


@jax.jit
def dv3_target_ema(cp, tcp, tau):
    """EMA update for the target critic (reference dreamer_v3.py:670-675);
    ``jit_dv3_target_ema`` in a device trace."""
    return jax.tree.map(lambda c, t: tau * c + (1 - tau) * t, cp, tcp)


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    if cfg.checkpoint.resume_from:
        state = fabric.load(cfg.checkpoint.resume_from)

    # these arguments cannot be changed (reference dreamer_v3.py:366-369)
    cfg.env.frame_stack = 1
    if 2 ** int(np.log2(cfg.env.screen_size)) != cfg.env.screen_size:
        raise ValueError(f"The screen size must be a power of 2, got: {cfg.env.screen_size}")

    log_dir = get_log_dir(cfg)
    logger = get_logger(cfg, log_dir)
    fabric.logger = logger
    logger.log_hyperparams(cfg.to_dict() if hasattr(cfg, "to_dict") else dict(cfg))
    print(f"Log dir: {log_dir}")
    resil = RunResilience(fabric, cfg, log_dir)

    rank = fabric.process_index
    num_envs = int(cfg.env.num_envs)
    # batch split width = the DATA axis only (on a [data, model] mesh the
    # model peers co-own each batch shard rather than adding to it)
    world_size = fabric.data_parallel_size
    num_processes = fabric.num_processes  # hosts: sets the env-step accounting

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

    wm, wm_params, actor, actor_params, critic, critic_params, target_critic_params, player = build_agent(
        fabric,
        actions_dim,
        is_continuous,
        cfg,
        observation_space,
        state["world_model"] if cfg.checkpoint.resume_from else None,
        state["actor"] if cfg.checkpoint.resume_from else None,
        state["critic"] if cfg.checkpoint.resume_from else None,
        state["target_critic"] if cfg.checkpoint.resume_from else None,
    )

    world_tx = build_tx(cfg.algo.world_model.optimizer, cfg.algo.world_model.clip_gradients)
    actor_tx = build_tx(cfg.algo.actor.optimizer, cfg.algo.actor.clip_gradients)
    critic_tx = build_tx(cfg.algo.critic.optimizer, cfg.algo.critic.clip_gradients)
    # shard_params co-shards Adam moments with their params on a model-axis
    # mesh and replicates on a pure-DP one
    world_opt = fabric.shard_params(world_tx.init(jax.device_get(wm_params)))
    actor_opt = fabric.shard_params(actor_tx.init(jax.device_get(actor_params)))
    critic_opt = fabric.shard_params(critic_tx.init(jax.device_get(critic_params)))
    moments_state: MomentsState = init_moments()
    if cfg.checkpoint.resume_from:
        world_opt = fabric.shard_params(jax.tree.map(jnp.asarray, state["world_optimizer"]))
        actor_opt = fabric.shard_params(jax.tree.map(jnp.asarray, state["actor_optimizer"]))
        critic_opt = fabric.shard_params(jax.tree.map(jnp.asarray, state["critic_optimizer"]))
        moments_state = MomentsState(
            low=jnp.asarray(state["moments"]["low"]), high=jnp.asarray(state["moments"]["high"])
        )
    # commit the only still-host train-state leaves (the moments scalars) to
    # the mesh now: an uncommitted input in window 1 vs the committed train
    # output in window 2 keys a SECOND executable of the whole train step
    moments_state = fabric.replicate(moments_state)

    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = MetricAggregator(cfg.metric.get("aggregator", {}).get("metrics", {}) or {})
    for k in AGGREGATOR_KEYS - set(aggregator.metrics):
        aggregator.add(k, "mean")

    buffer_size = cfg.buffer.size // int(num_envs * num_processes) if not cfg.dry_run else 2
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
    use_device_rb = isinstance(rb, DeviceReplayBuffer)
    if cfg.checkpoint.resume_from and cfg.buffer.checkpoint:
        from sheeprl_tpu.utils.checkpoint import select_buffer

        # checkpoints from either buffer mode resume into this run's mode
        rb = adapt_restored_buffer(
            select_buffer(state["rb"], rank, num_processes),
            use_device_rb,
            seed=cfg.seed,
            memmap=cfg.buffer.memmap,
            memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}"),
        )

    train_fn = make_train_fn(
        fabric, wm, actor, critic, world_tx, actor_tx, critic_tx, cfg, is_continuous, actions_dim
    )

    # counters (reference dreamer_v3.py:491-516)
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

    # ---- fused training supersteps (algo.fused_gradient_steps) ----
    # K > 0 chunks each train window into ceil(G / K) superstep dispatches:
    # replay gather, EMA target refresh and K gradient steps in ONE donated
    # XLA program (ops.superstep). 0 keeps the per-step path above.
    fused_k = int(cfg.algo.get("fused_gradient_steps", 0) or 0)
    if fused_k > 0:
        reset_fused_fallback_warnings()
        if fabric.num_processes > 1:
            fused_fallback(
                "multi_process",
                "algo.fused_gradient_steps cannot span processes "
                f"(num_processes={fabric.num_processes}); falling back to the "
                "per-step train path",
            )
            fused_k = 0
    # model-axis meshes fuse via GSPMD (the scan's carry shardings pin each
    # W2 / Adam / EMA shard device-resident — no shard_map, no pmean);
    # pure-DP meshes keep the explicit-collective shard_map scan
    fused_gspmd = fused_k > 0 and fabric.model_axis is not None
    fused_sharded = fused_k > 0 and fabric.world_size > 1 and not fused_gspmd
    fused_fns: Dict[int, Any] = {}  # one compiled superstep per distinct scan length
    fused_batch_size = per_rank_batch_size * fabric.local_data_parallel_size
    fused_draw_size = fused_batch_size // (fabric.data_parallel_size if fused_sharded else 1)
    fused_axis = fabric.data_axis if fused_sharded else None

    if use_device_rb:

        def fused_gather(ctx, gather_key, i):
            del i  # fresh indices come from the folded per-step key
            bufs, pos, full = ctx
            return draw_sequence_batch(
                bufs,
                pos,
                full,
                fold_sample_key(gather_key, axis_name=fused_axis),
                fused_draw_size,
                sequence_length,
            )

    else:
        fused_gather = pregathered

    fused_ctx_spec = None
    if fused_sharded:
        # ring: (bufs, pos, full) all env-axis sharded; pregathered stack:
        # [n, T, B, ...] sharded along the batch axis
        fused_ctx_spec = (
            (P(fused_axis), P(fused_axis), P(fused_axis))
            if use_device_rb
            else P(None, None, fused_axis)
        )
    elif fused_gspmd:
        # GSPMD scan: the pre-gathered [n, T, B, ...] stack is batch-sharded
        # over the data axis (the model peers co-own each shard)
        fused_ctx_spec = P(None, None, fabric.data_axis)

    # (data, model) superstep carries: one spec per leaf of the exact
    # params/aux tuples the superstep scans over, so optimizer and EMA
    # twins ride model-sharded instead of silently replicated
    fused_carry_specs = None
    if fused_gspmd:
        fused_carry_specs = (
            fabric.match_partition_rules(
                (wm_params, actor_params, critic_params, target_critic_params)
            ),
            fabric.match_partition_rules((world_opt, actor_opt, critic_opt, moments_state)),
        )

    def get_fused_fn(n: int):
        fn = fused_fns.get(n)
        if fn is None:
            fn = fused_fns[n] = make_fused_train_fn(
                fabric,
                wm,
                actor,
                critic,
                world_tx,
                actor_tx,
                critic_tx,
                cfg,
                is_continuous,
                actions_dim,
                fused_gather,
                n,
                ctx_spec=fused_ctx_spec,
                carry_specs=fused_carry_specs,
                check_finite=resil.finite_checks,
            )
        return fn

    def fused_pregather_ctx(n: int):
        # host-buffer fallback: draw the chunk's n batches with the buffer's
        # own RNG (the unfused sampling distribution and stream) and ship
        # them once as a stacked [n, T, B, ...] pytree — batch-axis sharded
        # on a mesh so the shard_map'd superstep slices it without a copy
        from sheeprl_tpu.data.buffers import to_device

        sample = rb.sample(fused_batch_size, sequence_length=sequence_length, n_samples=n)
        batch_axis = fabric.data_axis if (fused_sharded or fused_gspmd) else None
        return to_device(
            {k: (v if k in cnn_keys else v.astype(np.float32)) for k, v in sample.items()},
            sharding=fabric.sharding(None, None, batch_axis) if batch_axis else None,
        )

    key = jax.random.PRNGKey(int(cfg.seed))
    if cfg.checkpoint.resume_from and "rng_key" in state:
        key = jnp.asarray(state["rng_key"])
    if fused_gspmd:
        # same zero-recompile reasoning as the moments above: the superstep
        # returns the key mesh-committed, so it must enter window 1 that way
        key = fabric.replicate(key)
    # action sampling draws from its own stream committed to the player's
    # device, so a host-pinned player (agent.PlayerDV3 device) never waits on
    # an accelerator round trip for a key
    from sheeprl_tpu.obs.telemetry import telemetry_resolved
    from sheeprl_tpu.parallel.fabric import put_tree, tree_devices

    player_key = put_tree(jax.random.fold_in(key, 1), player.device)
    if cfg.checkpoint.resume_from and "player_rng_key" in state:
        # continue the pre-resume action-sampling stream
        player_key = put_tree(jnp.asarray(state["player_rng_key"]), player.device)

    # first observation (reference dreamer_v3.py:534-543)
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

    def ckpt_state_fn(completed_update: int) -> Dict[str, Any]:
        return {
            "world_model": jax.device_get(wm_params),
            "actor": jax.device_get(actor_params),
            "critic": jax.device_get(critic_params),
            "target_critic": jax.device_get(target_critic_params),
            "world_optimizer": jax.device_get(world_opt),
            "actor_optimizer": jax.device_get(actor_opt),
            "critic_optimizer": jax.device_get(critic_opt),
            "moments": {
                "low": np.asarray(jax.device_get(moments_state.low)),
                "high": np.asarray(jax.device_get(moments_state.high)),
            },
            "ratio": ratio.state_dict(),
            "update": completed_update,
            "batch_size": per_rank_batch_size * world_size,
            "last_log": last_log,
            "last_checkpoint": last_checkpoint,
            "rng_key": jax.device_get(key),
            "player_rng_key": jax.device_get(player_key),
        }

    def ckpt_path_fn(step: int) -> str:
        return os.path.join(log_dir, "checkpoint", f"ckpt_{step}_{rank}.ckpt")

    def nan_rollback(at_update: int) -> None:
        # restore the full train state (params, target, the three optimizers,
        # return-normalizer moments, replay ratio) from the newest committed
        # checkpoint and fork the sample key away from the stream that
        # diverged; the env/replay side is NOT rolled back — the buffer only
        # ever holds observations, which a NaN train step cannot poison
        nonlocal wm_params, actor_params, critic_params, target_critic_params
        nonlocal world_opt, actor_opt, critic_opt, moments_state, key
        nonlocal queued_forward, queued_window
        restored = resil.rollback(update=at_update)
        wm_params = resil.place_like(restored["world_model"], wm_params)
        actor_params = resil.place_like(restored["actor"], actor_params)
        critic_params = resil.place_like(restored["critic"], critic_params)
        target_critic_params = resil.place_like(restored["target_critic"], target_critic_params)
        world_opt = resil.place_like(restored["world_optimizer"], world_opt)
        actor_opt = resil.place_like(restored["actor_optimizer"], actor_opt)
        critic_opt = resil.place_like(restored["critic_optimizer"], critic_opt)
        moments_state = MomentsState(
            low=resil.place_like(np.asarray(restored["moments"]["low"]), moments_state.low),
            high=resil.place_like(np.asarray(restored["moments"]["high"]), moments_state.high),
        )
        ratio.load_state_dict(restored["ratio"])
        if "rng_key" in restored:
            key = resil.place_like(restored["rng_key"], key)
        key = resil.resalt_key(key)
        pending_metrics.clear()  # the poisoned window must not reach the logger
        player.update_params(wm_params, actor_params)
        queued_window = None  # made from the poisoned critic and the key that diverged
        if queued_forward is not None:
            # the next turn's forward ran on the poisoned weights: made again
            # on the restored ones, from the state and with the key it had
            q = queued_forward
            player.h, player.z, player.actions = q.state_before
            queued_forward = q._replace(action=dispatch_forward(q.obs, q.action_key))
            prequeue["forwards_redone"] += 1

    def dispatch_forward(prepared_obs: Dict[str, np.ndarray], action_key: Any) -> Any:
        mask = {k: v for k, v in prepared_obs.items() if k.startswith("mask")}
        return player.get_actions(prepared_obs, action_key, mask=mask or None, fetch=False)

    def queue_forward(prepared_obs: Dict[str, np.ndarray], ahead: bool = True) -> None:
        # ``player_key`` itself moves on when the turn takes the action: a
        # checkpoint written before then holds the key of before this split
        nonlocal queued_forward
        next_player_key, action_key = jax.random.split(player_key)
        state_before = (player.h, player.z, player.actions)
        action = dispatch_forward(prepared_obs, action_key)
        queued_forward = QueuedForward(action, next_player_key, action_key, state_before, prepared_obs)
        prequeue["forwards_queued"] += ahead

    def target_and_keys() -> Tuple[Any, Any, Any, int]:
        # what a gradient step of the per-step path takes beside its batch: the
        # target critic, refreshed when the step count says so, and the split key
        target, ema_dispatches = target_critic_params, 0
        if cumulative_per_rank_gradient_steps % cfg.algo.critic.per_rank_target_network_update_freq == 0:
            tau = 1.0 if cumulative_per_rank_gradient_steps == 0 else float(cfg.algo.critic.tau)
            target, ema_dispatches = dv3_target_ema(critic_params, target, tau), 1
        next_key, train_key = jax.random.split(key)
        return target, next_key, train_key, ema_dispatches

    # a crash anywhere in the loop gets the preemption treatment too: the
    # lambdas read the loop's CURRENT policy_step/update at crash time
    resil.arm_crash_guard(
        path_fn=lambda: ckpt_path_fn(policy_step),
        state_fn=lambda: ckpt_state_fn(update - 1),
        replay_buffer_fn=lambda: rb if cfg.buffer.checkpoint else None,
    )
    preempted = False
    cumulative_per_rank_gradient_steps = 0
    pending_metrics: list = []  # device-resident metric vectors, fetched at log time
    # The host waits for the accelerator in three places a turn: the action's
    # fetch, ``train/block`` (only while timers are on) and the finite check's
    # fetch. Before the last two it queues what the next turn needs and it can
    # already issue, so that runs directly behind the train steps:
    queued_forward: Optional[QueuedForward] = None  # the next turn's player forward
    # the next window's first target critic and key split: (target, key, train_key, EMA dispatches)
    queued_window: Optional[Tuple[Any, Any, Any, int]] = None
    prequeue = {"turns": 0, "forwards_queued": 0, "forwards_landed": 0, "forwards_redone": 0}

    def report_prequeue() -> None:
        # counts since the last report; in steady state queued = turns, redone = 0
        if prequeue["turns"]:
            telemetry_counters("dv3/prequeue", **prequeue)
            prequeue.update(dict.fromkeys(prequeue, 0))

    # with the waits off the fence keeps the host at most a few train blocks
    # ahead, so the dispatch/transfer queues stay bounded
    from sheeprl_tpu.parallel.fabric import DispatchFence

    fence = DispatchFence(depth=int(cfg.algo.get("dispatch_fence_depth", 4) or 4))
    last_grad_steps = 0  # heartbeat window: train_fn invocations since last log
    placement_recorded = False
    # A turn's work lies in spans other than the two window spans
    # (howto/telemetry.md): ``loop/head``, ``loop/store_step``, ``train/plan``
    # and ``loop/tail`` around them and leaves inside them, which a window span
    # joins with a few bindings of glue
    for update in range(start_step, num_updates + 1):
        with timer("loop/head"):
            telemetry_advance(policy_step)
            if resil.preempt_requested():
                # drain the dispatch queue before snapshotting: the state fn's
                # device_get would otherwise fetch mid-flight donated buffers
                fence.drain()
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
            on_policy = update > learning_starts or cfg.checkpoint.resume_from is not None
            if on_policy:
                if queued_forward is None:
                    # the first turn on the policy, or the first after a resume
                    queue_forward(prepare_obs(obs, cnn_keys=cnn_keys, num_envs=num_envs), ahead=False)
                pending, player_key = queued_forward.action, queued_forward.player_key
                queued_forward = None
                prequeue["turns"] += 1
                prequeue["forwards_landed"] += bool(pending.is_ready())
                with timer("player/get_actions"):
                    # the fetch of a forward that the last turn queued behind its train steps
                    actions = np.asarray(pending)
            with timer("player/to_env"):
                # the action in the env's form, and in the ring's
                if not on_policy:
                    real_actions = actions = np.array(envs.action_space.sample())
                    if not is_continuous:
                        actions = np.concatenate(
                            [
                                np.eye(act_dim, dtype=np.float32)[act.reshape(-1)]
                                for act, act_dim in zip(actions.reshape(len(actions_dim), -1), actions_dim)
                            ],
                            axis=-1,
                        )
                elif is_continuous:
                    real_actions = actions
                else:
                    splits = np.cumsum(actions_dim)[:-1]
                    real_actions = np.stack(
                        [p.argmax(-1) for p in np.split(actions, splits, axis=-1)], axis=-1
                    )
                    if real_actions.shape[-1] == 1 and not is_multidiscrete:
                        real_actions = real_actions[..., 0]
                step_data["actions"] = np.asarray(actions, np.float32).reshape(1, num_envs, -1)
            with timer("ring/add"):
                rb.add(step_data, validate_args=cfg.buffer.validate_args)

            with timer("env/step"):
                next_obs, rewards, terminated, truncated, infos = envs.step(
                    real_actions.reshape(envs.action_space.shape)
                )
            dones = np.logical_or(terminated, truncated).astype(np.uint8)

        # host work between the env step and the train section: final-obs copy,
        # prepare_obs, the next step_data, the reset add, the player's reset
        with timer("loop/store_step"):
            step_data["is_first"] = np.zeros_like(step_data["terminated"])
            if "restart_on_exception" in infos:
                for i, roe in enumerate(np.asarray(infos["restart_on_exception"]).reshape(-1)):
                    if roe and not dones[i]:
                        # patch the last stored step to a truncation and restart the
                        # episode (reference dreamer_v3.py:591-604)
                        if use_device_rb:
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

            # the final obs of finished episodes (SAME_STEP autoreset provides it)
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
                # store the terminal transition with the true final obs, zero
                # action, then reset per-env episode state
                # (reference dreamer_v3.py:635-653)
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
                with timer("ring/add"):
                    rb.add(reset_data, dones_idxes, validate_args=cfg.buffer.validate_args)

                step_data["rewards"][:, dones_idxes] = 0.0
                step_data["terminated"][:, dones_idxes] = 0.0
                step_data["truncated"][:, dones_idxes] = 0.0
                step_data["is_first"][:, dones_idxes] = 1.0
                player.init_states(dones_idxes)

        # ---------------- training ---------------- #
        with timer("train/plan"):
            # the turn after this one acts on the policy, if there is one: all its
            # forward needs is on the host (``prepared_next``) or queued (the weights)
            acts_next = update < num_updates and (
                update >= learning_starts or cfg.checkpoint.resume_from is not None
            )
            per_rank_gradient_steps = ratio(policy_step / num_processes) if update >= learning_starts else 0
            window_finite: list = []  # fused path: [chunk] bool vectors, one per dispatch
        if per_rank_gradient_steps > 0:
            with timer("Time/train_time"):
                if fused_k > 0:
                    # fused path: the whole window is ceil(G / K) superstep
                    # dispatches — gather + EMA + train scanned inside XLA
                    window_dispatches = 0
                    n_left = per_rank_gradient_steps
                    while n_left > 0:
                        chunk = min(fused_k, n_left)
                        n_left -= chunk
                        superstep = get_fused_fn(chunk)
                        ctx = (
                            rb.superstep_inputs(sequence_length)
                            if use_device_rb
                            else fused_pregather_ctx(chunk)
                        )
                        params = (wm_params, actor_params, critic_params, target_critic_params)
                        aux = (world_opt, actor_opt, critic_opt, moments_state)
                        counter = jnp.int32(cumulative_per_rank_gradient_steps)
                        if cumulative_per_rank_gradient_steps == 0:
                            # shapes only; scaled so the heartbeat's MFU stays
                            # per-gradient-step (invocations count steps)
                            telemetry_register_flops(
                                superstep, params, aux, counter, ctx, key, scale=1.0 / chunk
                            )
                        with timer("train/dispatch"):
                            if resil.finite_checks:
                                # the sentinel rides the same dispatch: a [chunk]
                                # finite vector instead of an extra program
                                params, aux, key, metrics, chunk_finite = superstep(
                                    params, aux, counter, ctx, key
                                )
                                window_finite.append(chunk_finite)
                            else:
                                params, aux, key, metrics = superstep(params, aux, counter, ctx, key)
                        wm_params, actor_params, critic_params, target_critic_params = params
                        world_opt, actor_opt, critic_opt, moments_state = aux
                        cumulative_per_rank_gradient_steps += chunk
                        window_dispatches += 1
                        if cfg.metric.log_level > 0:
                            # [chunk, len(METRIC_ORDER)] on device, one fetch
                            # per log interval for the whole window
                            pending_metrics.append(metrics)
                else:
                    # each process samples its share of the global batch
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
                    window_ema_dispatches = 0
                    for batch in batches:
                        with timer("train/keys"):
                            # the window's first come from the end of the last window
                            target_critic_params, key, train_key, ema_dispatches = queued_window or target_and_keys()
                            queued_window = None
                            window_ema_dispatches += ema_dispatches
                        with timer("train/dispatch"):
                            (
                                wm_params,
                                actor_params,
                                critic_params,
                                world_opt,
                                actor_opt,
                                critic_opt,
                                moments_state,
                                metrics,
                            ) = train_fn(
                                wm_params,
                                actor_params,
                                critic_params,
                                target_critic_params,
                                world_opt,
                                actor_opt,
                                critic_opt,
                                moments_state,
                                batch,
                                train_key,
                            )
                        cumulative_per_rank_gradient_steps += 1
                        if cumulative_per_rank_gradient_steps == 1:
                            # shapes only — the batch itself is not pinned
                            telemetry_register_flops(
                                train_fn,
                                wm_params,
                                actor_params,
                                critic_params,
                                target_critic_params,
                                world_opt,
                                actor_opt,
                                critic_opt,
                                moments_state,
                                batch,
                                train_key,
                            )
                    # per-step dispatch shape: one train call per gradient step,
                    # plus the on-device gather per batch and the EMA refreshes
                    window_dispatches = (
                        per_rank_gradient_steps * (2 if use_device_rb else 1) + window_ema_dispatches
                    )
                    if cfg.metric.log_level > 0:
                        # keep the metric vector ON DEVICE: fetching here would
                        # serialize the async train dispatch against the host
                        # loop (one device round trip per train block); the queue
                        # drains at log time instead
                        pending_metrics.append(metrics)
                with timer("train/queue_next"):
                    # the per-step program took the trees the player held: it gets
                    # the window's newest before anything touches it again. Then,
                    # behind the train steps and before the host waits for them:
                    # the next turn's forward, then the next window's first target
                    # refresh and key split, which depend on nothing newer either
                    player.update_params(wm_params, actor_params)
                    if acts_next:
                        queue_forward(prepared_next)
                    if fused_k == 0:
                        queued_window = target_and_keys()
                    fence.push(metrics)
                    telemetry_train_window(window_dispatches, per_rank_gradient_steps)
                    train_step += num_processes
                if not timer.disabled:
                    # only when timing: wait so Time/train_time measures
                    # the chip, not the async dispatch. What the wait is
                    # for is whatever the device still has queued: the
                    # turn's ring write and the gathers as well as the
                    # train steps (``train/block`` is the host's wait)
                    with timer("train/block"):
                        jax.block_until_ready(wm_params)

        with timer("loop/tail"):
            if per_rank_gradient_steps > 0:
                if fused_k > 0:
                    # one tiny fetch per window: the [chunk] finite vectors the
                    # superstep computed in-dispatch, reduced on the host
                    window_ok = resil.window_ok(
                        all(bool(np.all(np.asarray(jax.device_get(f)))) for f in window_finite), update
                    )
                else:
                    # the window's LAST metric vector: NaNs in params propagate
                    # to every later loss, so one fetch per window suffices
                    window_ok = not resil.finite_checks or resil.check_finite(
                        np.asarray(jax.device_get(metrics)), update
                    )
                if not window_ok:
                    nan_rollback(update)
                    continue
            elif acts_next:
                queue_forward(prepared_next)

            if cumulative_per_rank_gradient_steps > 0 and not placement_recorded:
                # once, after the first train window: where the state that the
                # run pays for actually lives (run record: resolved.state_devices)
                placement_recorded = True
                telemetry_resolved(
                    "state_devices",
                    {
                        "params": tree_devices((wm_params, actor_params, critic_params, target_critic_params)),
                        "optimizer": tree_devices((world_opt, actor_opt, critic_opt)),
                        "replay": rb.devices() if use_device_rb else ["host"],
                        "player_params": tree_devices((player.wm_params, player.actor_params)),
                    },
                )

            # ---------------- logging ---------------- #
            if cfg.metric.log_level > 0 and (policy_step - last_log >= cfg.metric.log_every or update == num_updates):
                if pending_metrics:
                    # stack ON DEVICE first: one transfer for the whole window
                    # instead of one round trip per train block; fused entries
                    # are already [chunk, |METRIC_ORDER|] blocks
                    stacked = jnp.concatenate(
                        [m if m.ndim == 2 else m[None] for m in pending_metrics], axis=0
                    )
                    for metrics_np in np.asarray(jax.device_get(stacked)):
                        for name, value in zip(METRIC_ORDER, metrics_np):
                            aggregator.update(name, float(value))
                    pending_metrics.clear()
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
                    train_invocations=cumulative_per_rank_gradient_steps - last_grad_steps,
                )
                last_log = policy_step
                last_train = train_step
                last_grad_steps = cumulative_per_rank_gradient_steps
                report_prequeue()

            # ---------------- checkpoint ---------------- #
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

    # drain materializes the newest fence marker too: the device has
    # finished every queued train dispatch before the closing bookkeeping
    fence.drain()
    report_prequeue()

    # land any in-flight async param stream so the final evaluation and
    # model registration use the last update's weights
    player.flush_stream_attrs()
    envs.close()
    if fabric.is_global_zero and cfg.algo.run_test and not preempted:
        test(player, fabric, cfg, log_dir, greedy=False)
    logger.finalize()
    resil.close()
    if preempted:
        resil.exit_preempted()
