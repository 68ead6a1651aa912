"""PPO, coupled (reference: sheeprl/algos/ppo/ppo.py:30-452) — TPU-native.

Differences from the reference that are the point of the redesign:

- **One SPMD process per host, no launcher.** The reference spawns DDP ranks
  (cli.py:190); here the rollout data ``[T*E, ...]`` is sharded across the
  mesh's data axis and the whole optimization (epochs x minibatches) runs as
  a single jitted ``shard_map`` — the per-minibatch gradient ``pmean`` over
  ICI is the DDP all-reduce (ppo.py:93 ``fabric.backward``).
- **Whole-update fusion.** The reference's Python epoch/minibatch loops with
  per-batch optimizer steps become two nested ``lax.scan``s inside one XLA
  program: one dispatch per update instead of epochs*minibatches.
- **GAE on device** as a reverse ``lax.scan`` (reference utils.py:63-100 is
  a Python loop over T).
- **uint8 to the MXU.** Pixels cross PCIe as bytes; normalization happens
  inside the agent (agent.py CNNEncoder), not in ``normalize_obs``.
- Annealed coefficients (clip/entropy) are *dynamic scalars* fed to the
  jitted step — annealing never recompiles.
- **Fused on-policy collection** (``algo.fused_rollout``): when the env has a
  jittable twin (``envs/jittable.py``) the whole T-step rollout, truncation
  bootstrap, autoreset, GAE and the fused update run as ONE dispatch per
  update (``ops/rollout_scan.py``); infeasible configs fall back to the host
  loop with a ``fused_fallback`` telemetry breadcrumb.
- **Overlapped collection** (``algo.overlap_collection``): the host loop
  dispatches the update asynchronously and collects the next rollout with
  one-update-stale player params while it executes (the decoupled-PPO
  staleness contract; the PPO ratio corrects against stored logprobs).  The
  blocking metrics wait is attributed to ``Time/train_wait_time`` so the
  heartbeat reports the overlap fraction directly.
"""

from __future__ import annotations

import os
import warnings
from functools import partial
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import PartitionSpec as P
from sheeprl_tpu.parallel.shard_map import shard_map

from sheeprl_tpu.algos.ppo.agent import PPOPlayer, build_agent, evaluate_actions, rollout_step
from sheeprl_tpu.algos.ppo.loss import entropy_loss, policy_loss, value_loss
from sheeprl_tpu.algos.ppo.utils import AGGREGATOR_KEYS, prepare_obs, test
from sheeprl_tpu.config.compose import instantiate
from sheeprl_tpu.envs import build_vector_env, get_jittable_env
from sheeprl_tpu.envs.variants import (
    ScenarioFamily,
    compose_variant_env_id,
    make_scenario_family,
    sample_scenario_matrix,
)
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
from sheeprl_tpu.parallel.fabric import put_tree, resolve_player_device, resolve_train_device
from sheeprl_tpu.resilience import RunResilience
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.prealloc import RolloutStore
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import polynomial_decay, save_configs


def make_local_train(fabric, agent, tx, cfg, obs_keys, n_local: int, *, use_mesh: bool):
    """The UNJITTED fused-update body: epochs x shuffled minibatches with the
    per-minibatch gradient ``pmean`` when ``use_mesh`` (replaces reference
    train(), ppo.py:30-102).  ``make_train_fn`` jits it standalone; the fused
    on-policy superstep (``ops/rollout_scan.py``) embeds it after the scanned
    rollout so collection+GAE+update compile into ONE dispatch."""
    batch_size = int(cfg.algo.per_rank_batch_size)
    update_epochs = int(cfg.algo.update_epochs)
    num_minibatches = n_local // batch_size
    if num_minibatches == 0:
        raise ValueError(
            f"per_rank_batch_size ({batch_size}) is larger than the per-device rollout ({n_local})"
        )
    dropped = n_local - num_minibatches * batch_size
    if dropped:
        warnings.warn(
            f"{dropped} of {n_local} per-device rollout samples are dropped each epoch because "
            f"per_rank_batch_size ({batch_size}) does not divide the per-device rollout; "
            "choose rollout_steps*num_envs divisible by (devices*batch_size) to use all data."
        )
    vf_coef = float(cfg.algo.vf_coef)
    clip_vloss = bool(cfg.algo.clip_vloss)
    normalize_adv = bool(cfg.algo.normalize_advantages)
    reduction = str(cfg.algo.loss_reduction)
    data_axis = fabric.data_axis

    def pmean(x):
        return lax.pmean(x, data_axis) if use_mesh else x

    def local_train(params, opt_state, data, key, clip_coef, ent_coef):
        if use_mesh:
            # distinct permutation stream per device (reference: per-rank sampler)
            key = jax.random.fold_in(key, lax.axis_index(data_axis))

        def minibatch_step(carry, batch):
            params, opt_state = carry

            def loss_fn(p):
                obs = {k: batch[k] for k in obs_keys}
                new_logprobs, entropy, new_values = evaluate_actions(agent, p, obs, batch["actions"])
                adv = batch["advantages"]
                if normalize_adv:
                    adv = (adv - adv.mean()) / (adv.std(ddof=1) + 1e-8)
                pg = policy_loss(new_logprobs, batch["logprobs"], adv, clip_coef, reduction)
                v = value_loss(new_values, batch["values"], batch["returns"], clip_coef, clip_vloss, reduction)
                ent = entropy_loss(entropy, reduction)
                return pg + vf_coef * v + ent_coef * ent, (pg, v, ent)

            (_, (pg, v, ent)), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            grads = pmean(grads)  # the DDP all-reduce, over ICI
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state), jnp.stack([pg, v, ent])

        def epoch_step(carry, _):
            params, opt_state, key = carry
            key, perm_key = jax.random.split(key)
            perm = jax.random.permutation(perm_key, n_local)[: num_minibatches * batch_size]
            minibatches = jax.tree.map(
                lambda x: x[perm].reshape(num_minibatches, batch_size, *x.shape[1:]), data
            )
            (params, opt_state), metrics = lax.scan(minibatch_step, (params, opt_state), minibatches)
            return (params, opt_state, key), metrics

        (params, opt_state, _), metrics = lax.scan(
            epoch_step, (params, opt_state, key), None, length=update_epochs
        )
        # [epochs, minibatches, 3] -> [3], identical on every device after pmean
        return params, opt_state, pmean(metrics.mean(axis=(0, 1)))

    return local_train


def make_train_fn(fabric, agent, tx, cfg, obs_keys, n_local: int, host_device=None, donate_params: bool = True):
    """Build the fused update: epochs x shuffled minibatches, grad-pmean'd
    over the data axis, one jit (replaces reference train(), ppo.py:30-102).

    ``host_device``: single-device escape hatch (``resolve_train_device``) —
    the same program without mesh collectives, jitted for the host CPU so a
    tiny model's update never pays an accelerator round trip.

    ``donate_params=False`` keeps the params buffers alive past the call: the
    overlap_collection loop dispatches update N and then lets the player keep
    sampling from one-update-stale params while N executes, so those buffers
    must survive the dispatch even when player and train share a device."""
    use_mesh = host_device is None
    local_train = make_local_train(fabric, agent, tx, cfg, obs_keys, n_local, use_mesh=use_mesh)
    if not use_mesh:
        # inputs are committed to the host device by the caller, so the jit
        # executes entirely on the host CPU backend. Donate ONLY opt_state:
        # the host-pinned player aliases the very params buffers passed in
        # here (update_params hands them over without a copy), so donating
        # them would leave the player holding deleted arrays.
        return jax.jit(local_train, donate_argnums=(1,))
    train_fn = shard_map(
        local_train,
        mesh=fabric.mesh,
        in_specs=(P(), P(), P(fabric.data_axis), P(), P(), P()),
        out_specs=(P(), P(), P()),
    )
    return jax.jit(train_fn, donate_argnums=(0, 1) if donate_params else (1,))


def scenario_variant_cfg(cfg):
    """Parse the ``env.variants.*`` node: ``(names, kwargs, ranges, seed)``.

    ``names`` is the enabled-variant tuple (empty when the node is absent or
    disabled), ``kwargs`` the static family knobs for
    :func:`make_scenario_family`."""
    node = cfg.env.get("variants", None) if hasattr(cfg.env, "get") else None
    if node is None:
        return (), {}, {}, None
    names = tuple(str(n) for n in (node.get("enabled", None) or ()))
    if not names:
        return (), {}, {}, None
    kwargs = {
        "distractor_dims": int(node.get("distractor_dims", 4)),
        "reward_max_delay": int(node.get("reward_max_delay", 4)),
    }
    ranges = {
        str(k): (float(v[0]), float(v[1])) for k, v in dict(node.get("ranges", None) or {}).items()
    }
    seed = node.get("seed", None)
    return names, kwargs, ranges, (None if seed is None else int(seed))


def resolve_scenario_family(cfg) -> ScenarioFamily | None:
    """The :class:`ScenarioFamily` for ``env.id`` + ``env.variants.enabled``,
    or ``None`` when no variants are enabled or the base env has no jittable
    twin (the fused feasibility gate then emits the breadcrumb)."""
    names, kwargs, _, _ = scenario_variant_cfg(cfg)
    if not names:
        return None
    return make_scenario_family(str(cfg.env.id), names, **kwargs)


def scenario_theta_matrix(cfg, family: ScenarioFamily, num_envs: int) -> jax.Array:
    """Sample the ``[num_envs, P]`` scenario matrix from ``env.variants``."""
    _, _, ranges, seed = scenario_variant_cfg(cfg)
    key = jax.random.PRNGKey(int(cfg.seed) if seed is None else seed)
    return sample_scenario_matrix(key, num_envs, family.variant_names, ranges)


def resolve_fused_rollout_spec(
    cfg, fabric, cnn_keys, mlp_keys, observation_space, is_continuous, is_multidiscrete, actions_dim
):
    """Feasibility gate for ``algo.fused_rollout``: return the jittable env
    spec (or :class:`ScenarioFamily` when ``env.variants`` are enabled) when
    the whole rollout can run in-graph, else emit one ``fused_fallback``
    telemetry event and return ``None`` (host loop)."""
    env_id = str(cfg.env.id)
    variant_names, family_kwargs, _, _ = scenario_variant_cfg(cfg)
    spec = get_jittable_env(env_id)
    if spec is None:
        # name the full variant-composed id so sweep triage can grep which
        # scenario (not just which base env) was skipped
        missing = compose_variant_env_id(env_id, variant_names) if variant_names else env_id
        fused_fallback("jittable_env", f"no jittable twin registered for env id '{missing}'")
        return None
    if variant_names:
        spec = make_scenario_family(env_id, variant_names, **family_kwargs)
    if fabric.num_processes > 1:
        fused_fallback("multi_process", "fused rollout is single-process (env state is process-local)")
        return None
    if fabric.model_axis is not None:
        fused_fallback("model_axis", "fused rollout shards envs over the data axis only")
        return None
    if cnn_keys or len(mlp_keys) != 1:
        fused_fallback(
            "obs_keys",
            f"fused rollout needs exactly one MLP obs key and no CNN keys, got cnn={cnn_keys} mlp={mlp_keys}",
        )
        return None
    obs_shape = tuple(observation_space[mlp_keys[0]].shape)
    if obs_shape != (spec.obs_dim,):
        fused_fallback(
            "obs_space",
            f"env obs {obs_shape} != jittable twin {(spec.obs_dim,)} — wrappers changed the observation",
        )
        return None
    if is_multidiscrete or bool(is_continuous) != bool(spec.is_continuous) or tuple(actions_dim) != (
        spec.action_dim,
    ):
        fused_fallback(
            "action_space",
            f"env actions {tuple(actions_dim)} (continuous={is_continuous}) != jittable twin "
            f"({spec.action_dim}, continuous={spec.is_continuous})",
        )
        return None
    if int(cfg.env.action_repeat) != 1:
        fused_fallback("action_repeat", "jittable twins model single-step dynamics only")
        return None
    return spec


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    if cfg.checkpoint.resume_from:
        state = fabric.load(cfg.checkpoint.resume_from)

    log_dir = get_log_dir(cfg)
    logger = get_logger(cfg, log_dir)
    fabric.logger = logger
    logger.log_hyperparams(cfg.to_dict() if hasattr(cfg, "to_dict") else dict(cfg))
    print(f"Log dir: {log_dir}")

    # preemption watcher + non-finite sentinel + checkpoint rollback
    resil = RunResilience(fabric, cfg, log_dir)

    initial_clip_coef = float(cfg.algo.clip_coef)
    initial_ent_coef = float(cfg.algo.ent_coef)

    # environment setup (reference ppo.py:137-163); SAME_STEP autoreset keeps
    # the 0.29 semantics the algorithms were specified against
    rank = fabric.process_index
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

    is_continuous = isinstance(envs.single_action_space, gym.spaces.Box)
    is_multidiscrete = isinstance(envs.single_action_space, gym.spaces.MultiDiscrete)
    actions_dim = tuple(
        envs.single_action_space.shape
        if is_continuous
        else (envs.single_action_space.nvec.tolist() if is_multidiscrete else [envs.single_action_space.n])
    )

    # scenario variants (env.variants.*) run through the fused rollout only;
    # the `distractors` variant widens the observation, so the agent must be
    # built against the family's obs_dim, not the base vector env's
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
    player = PPOPlayer(
        agent, params, device=resolve_player_device(cfg.algo.get("player_device", "auto"))
    )

    num_envs = int(cfg.env.num_envs)
    rollout_steps = int(cfg.algo.rollout_steps)
    # batch split width = the DATA axis only: shard_map's P(data_axis)
    # in_spec delivers n_global/data_width rows per device, so on a 2-D
    # (data, model) mesh dividing by world_size would silently train on a
    # fraction of each shard
    world_size = fabric.data_parallel_size
    policy_steps_per_update = num_envs * rollout_steps * fabric.num_processes
    num_updates = int(cfg.algo.total_steps) // policy_steps_per_update if not cfg.dry_run else 1

    # global rollout spans every process's envs; shard over all devices
    n_global = rollout_steps * num_envs * fabric.num_processes
    if n_global % world_size != 0:
        raise ValueError(
            f"rollout_steps*num_envs*processes ({n_global}) must be divisible by the device count ({world_size})"
        )
    n_local = n_global // world_size
    num_minibatches = max(1, n_local // int(cfg.algo.per_rank_batch_size))
    update_epochs = int(cfg.algo.update_epochs)

    # optimizer; lr annealing is an optax schedule (reference PolynomialLR)
    opt_cfg = dict(cfg.algo.optimizer.to_dict() if hasattr(cfg.algo.optimizer, "to_dict") else cfg.algo.optimizer)
    if cfg.algo.max_grad_norm and float(cfg.algo.max_grad_norm) > 0:
        opt_cfg["max_grad_norm"] = float(cfg.algo.max_grad_norm)
    if cfg.algo.anneal_lr:
        steps_per_update = int(cfg.algo.update_epochs) * num_minibatches
        opt_cfg["schedule"] = optax.linear_schedule(
            float(opt_cfg.get("lr", 1e-3)), 0.0, num_updates * steps_per_update
        )
    tx = instantiate(opt_cfg)
    # host-train escape hatch (resolve_train_device): when the dispatch round
    # trip is above 5 ms, tiny models train on the host core, so the env loop,
    # player AND update never wait on the accelerator
    train_device = resolve_train_device(
        cfg.algo.get("train_device", "auto"), params, fabric.world_size
    )
    if train_device is not None:
        params = put_tree(jax.device_get(params), train_device)
        player.update_params(params)
    # resume state stays host numpy until the ONE placement below — routing
    # it through jnp.asarray would upload the whole optimizer state to the
    # default backend only to fetch it straight back for host training;
    # fresh init runs on the params' own device (host-committed when
    # train_device is set), so the moment tensors never touch the default
    # backend just to be fetched back
    opt_state = state["opt_state"] if cfg.checkpoint.resume_from else tx.init(params)
    opt_state = (
        put_tree(opt_state, train_device) if train_device is not None else fabric.replicate(opt_state)
    )

    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = MetricAggregator(cfg.metric.get("aggregator", {}).get("metrics", {}) or {})
    for k in AGGREGATOR_KEYS - set(aggregator.metrics):
        aggregator.add(k, "mean")

    if cfg.buffer.size < rollout_steps:
        raise ValueError(
            f"The size of the buffer ({cfg.buffer.size}) cannot be lower "
            f"than the rollout steps ({rollout_steps})"
        )
    # the rollout is consumed in-place each update (on-policy); unlike the
    # reference there is no staging ReplayBuffer copy — host lists are the
    # only transient storage

    # fused on-policy collection (`algo.fused_rollout`): when the env has a
    # jittable twin the whole rollout+GAE+update runs as ONE dispatch; any
    # infeasibility falls back to the host loop with a telemetry breadcrumb
    fused_rollout = bool(cfg.algo.get("fused_rollout", False))
    overlap_collection = bool(cfg.algo.get("overlap_collection", False))
    reset_fused_fallback_warnings()
    fused_spec = None
    if fused_rollout:
        fused_spec = resolve_fused_rollout_spec(
            cfg, fabric, cnn_keys, mlp_keys, observation_space, is_continuous, is_multidiscrete, actions_dim
        )
        if fused_spec is not None and train_device is None and num_envs % world_size != 0:
            fused_fallback(
                "env_shard", f"env.num_envs ({num_envs}) must be divisible by the device count ({world_size})"
            )
            fused_spec = None
    if scenario_family is not None and fused_spec is None:
        # the agent may be built against the widened scenario obs and the host
        # loop cannot apply variants — fail loudly instead of silently
        # training the un-randomized base env
        raise RuntimeError(
            "env.variants requires the fused rollout path; set "
            "algo.fused_rollout=True (if it is set, the fused_fallback "
            "telemetry event names the gate that failed)"
        )
    # fused rollout subsumes overlap (there is no host collection to overlap)
    overlap_collection = overlap_collection and fused_spec is None

    train_fn = make_train_fn(
        fabric, agent, tx, cfg, obs_keys, n_local, host_device=train_device, donate_params=not overlap_collection
    )
    gae_fn = jax.jit(partial(gae, gamma=float(cfg.algo.gamma), gae_lambda=float(cfg.algo.gae_lambda)))
    superstep_fn = None
    if fused_spec is not None:
        use_mesh_fused = train_device is None
        superstep_fn = make_onpolicy_superstep_fn(
            fused_spec,
            policy_fn=partial(rollout_step, agent),
            value_fn=lambda p, o: agent.apply(p, o)[1],
            local_train=make_local_train(fabric, agent, tx, cfg, obs_keys, n_local, use_mesh=use_mesh_fused),
            obs_key=mlp_keys[0],
            rollout_steps=rollout_steps,
            step_increment=num_envs * fabric.num_processes,
            gamma=float(cfg.algo.gamma),
            gae_lambda=float(cfg.algo.gae_lambda),
            mesh=fabric.mesh if use_mesh_fused else None,
            data_axis=fabric.data_axis if use_mesh_fused else None,
        )

    # counters (reference ppo.py:214-231)
    start_update = (state["update"] + 1) if cfg.checkpoint.resume_from else 1
    policy_step = state["update"] * policy_steps_per_update if cfg.checkpoint.resume_from else 0
    last_log = state["last_log"] if cfg.checkpoint.resume_from else 0
    last_checkpoint = state["last_checkpoint"] if cfg.checkpoint.resume_from else 0
    train_step = 0
    last_train = 0

    key = jax.random.PRNGKey(int(cfg.seed))
    if cfg.checkpoint.resume_from and "rng_key" in state:
        # host numpy from the checkpoint; placed exactly once below
        key = np.asarray(state["rng_key"])
    if train_device is not None:
        # the train key chain lives on the train device: a mixed-device
        # committed-input set would error, and splitting on the accelerator
        # would re-insert a per-update round trip
        key = put_tree(key, train_device)
    elif cfg.checkpoint.resume_from and "rng_key" in state:
        key = jnp.asarray(key)
    # rollout action keys live on the player's device so a host-pinned
    # player never blocks on a chip round trip per env step
    player_key = put_tree(jax.random.fold_in(key, 1), player.device)
    if cfg.checkpoint.resume_from and "player_rng_key" in state:
        # continue the pre-resume action-sampling stream
        player_key = put_tree(jnp.asarray(state["player_rng_key"]), player.device)

    clip_coef = float(cfg.algo.clip_coef)
    ent_coef = float(cfg.algo.ent_coef)

    next_obs, _ = envs.reset(seed=cfg.seed)
    next_obs = prepare_obs(next_obs, cnn_keys=cnn_keys, num_envs=num_envs)

    def ckpt_state_fn(completed_update: int) -> Dict[str, Any]:
        # shared by the periodic save, the preemption drain's emergency save
        # and (structurally) the rollback restore — reads the loop's CURRENT
        # bindings at call time
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

    # per-update blocks shared by the fused and host update loops; they read
    # the loop's CURRENT bindings at call time
    def rollback_state(at_update: int) -> None:
        # restore the newest committed checkpoint in place of the poisoned
        # params/opt state and fork the sample key away from the stream that
        # diverged — the loop's counters keep advancing so the run completes
        nonlocal params, opt_state, key
        restored = resil.rollback(update=at_update)
        params = resil.place_like(restored["agent"], params)
        opt_state = resil.place_like(restored["opt_state"], opt_state)
        if "rng_key" in restored:
            key = resil.place_like(restored["rng_key"], key)
        key = resil.resalt_key(key)
        player.update_params(params)

    def update_loss_metrics(metrics_np) -> None:
        if cfg.metric.log_level > 0:
            aggregator.update("Loss/policy_loss", float(metrics_np[0]))
            aggregator.update("Loss/value_loss", float(metrics_np[1]))
            aggregator.update("Loss/entropy_loss", float(metrics_np[2]))

    def maybe_heartbeat(final: bool) -> None:
        nonlocal last_log, last_train
        if cfg.metric.log_level > 0 and (policy_step - last_log >= cfg.metric.log_every or final):
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

    def anneal_coefs() -> None:
        # anneal coefficients (reference ppo.py:414-424)
        nonlocal clip_coef, ent_coef
        if cfg.algo.anneal_clip_coef:
            clip_coef = polynomial_decay(
                update, initial=initial_clip_coef, final=0.0, max_decay_steps=num_updates, power=1.0
            )
        if cfg.algo.anneal_ent_coef:
            ent_coef = polynomial_decay(
                update, initial=initial_ent_coef, final=0.0, max_decay_steps=num_updates, power=1.0
            )

    def maybe_checkpoint() -> None:
        nonlocal last_checkpoint
        if (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every) or (
            update == num_updates and cfg.checkpoint.save_last
        ):
            last_checkpoint = policy_step
            fabric.call("on_checkpoint_coupled", ckpt_path=ckpt_path_fn(policy_step), state=ckpt_state_fn(update))

    # a crash anywhere in the loop gets the preemption treatment too: the
    # lambdas read the loop's CURRENT policy_step/update at crash time
    resil.arm_crash_guard(
        path_fn=lambda: ckpt_path_fn(policy_step),
        state_fn=lambda: ckpt_state_fn(update - 1),
    )
    preempted = False
    if superstep_fn is not None:
        # ------------------------------------------------------------------
        # fused on-policy path: rollout + GAE + epochs x minibatches update
        # compile into ONE donated jit — the metrics fetch below is the only
        # host sync per update (the vector env above stays reset-only; it
        # provides spaces for the agent and the eval env at the end)
        # ------------------------------------------------------------------
        # env reset/transition stream is rooted off the run seed, salted away
        # from the action/train key streams (ops/rollout_scan.py discipline)
        if use_mesh_fused:
            # pin the inputs to the exact shardings the superstep outputs —
            # an uncommitted first-call carry/key would make call 2 (committed
            # jit outputs) re-lower the whole fused program, putting a second
            # multi-second compile inside the measured steady-state window
            def place_carry(carry):
                return jax.tree.map(lambda x: jax.device_put(x, fabric.batch_sharding), carry)

            key = jax.device_put(key, fabric.replicated)
        else:

            def place_carry(carry):
                return put_tree(carry, train_device)

        # one scenario row per env for the run's lifetime: domain
        # randomization persists across autoresets and update boundaries
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
        steps_per_dispatch = update_epochs * num_minibatches
        for update in range(start_update, num_updates + 1):
            telemetry_advance(policy_step)
            if resil.preempt_requested():
                last_checkpoint = policy_step
                resil.emergency_checkpoint(ckpt_path_fn(policy_step), ckpt_state_fn(update - 1))
                preempted = True
                break
            if update == start_update + 1:
                telemetry_mark_warm()
            # same fold schedule as the host player: rollout_actions folds
            # policy_step on top of the per-update key inside the superstep
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
                    np.float32(clip_coef),
                    np.float32(ent_coef),
                )
                policy_step += policy_steps_per_update
                metrics = np.asarray(metrics)
            telemetry_train_window(1, steps_per_dispatch)
            if not resil.check_finite(metrics, update):
                rollback_state(update)
                # fresh episodes: poisoned params may have driven the carried
                # env state non-finite too
                env_carry = place_carry(
                    init_env_carry(fused_spec, num_envs, jax.random.fold_in(key, update), thetas=thetas)
                )
                continue
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
            update_loss_metrics(metrics)
            maybe_heartbeat(update == num_updates)
            anneal_coefs()
            maybe_checkpoint()
        # the player sampled nothing during the fused loop; publish the final
        # params once for the eval rollout below
        player.update_params(params)
    else:
        # ------------------------------------------------------------------
        # host loop: jitted player per env step + fused update per window
        # ------------------------------------------------------------------
        pending = None  # overlap_collection: (device metrics, update index) in flight
        # double-buffer under overlap: the async dispatch may still read
        # update N's arrays (jax can alias host numpy zero-copy on CPU) while
        # the loop writes N+1
        store = RolloutStore(rollout_steps, slots=2 if overlap_collection else 1)
        # host-synchronized dispatches per update: T player steps + the
        # next-values critic call + GAE + the fused train step — the contrast
        # the fused path's 1-per-update counter is measured against
        host_dispatches_per_update = rollout_steps + 3

        def finalize_pending() -> bool:
            # the overlap path's ONE sync point: wait for the in-flight
            # update's metrics (attributed to train-wait, not collection),
            # run the NaN sentinel, then hand the already-dispatched params
            # to the player — collection keeps running one update stale and
            # the PPO ratio corrects against the stored logprobs
            nonlocal pending, train_step
            if pending is None:
                return True
            pending_metrics, pending_update = pending
            pending = None
            with timer("Time/train_wait_time"):
                metrics_np = np.asarray(pending_metrics)
            if not resil.check_finite(metrics_np, pending_update):
                rollback_state(pending_update)
                return False
            player.update_params(params)
            train_step += world_size
            update_loss_metrics(metrics_np)
            return True

        for update in range(start_update, num_updates + 1):
            telemetry_advance(policy_step)
            if resil.preempt_requested():
                # update has NOT run yet: the emergency checkpoint records
                # update-1 so auto-resume replays from exactly this boundary
                last_checkpoint = policy_step
                resil.emergency_checkpoint(ckpt_path_fn(policy_step), ckpt_state_fn(update - 1))
                preempted = True
                break
            if update == start_update + 1:
                telemetry_mark_warm()
            buf = store.begin(update)
            with timer("Time/env_interaction_time"):
                # one jitted dispatch + ONE device->host fetch per env step: key
                # folding, sampling and the one-hot->index conversion are fused
                # (agent.rollout_step); the base key crosses to the player device
                # once per update. Separate fetches would each cost a device
                # round trip, and the saved dispatches are a measurable slice
                # of the host's step budget.
                # fold the update index into the base key so action-stream
                # uniqueness holds even if policy_step bookkeeping ever repeats a
                # value across a resume (rollout_actions folds policy_step on top)
                update_key = jax.random.fold_in(player_key, update)
                for t in range(rollout_steps):
                    policy_step += num_envs * fabric.num_processes
                    actions, real_actions, logprobs, values = player.rollout_actions(
                        next_obs, update_key, policy_step
                    )
                    actions_np, real_actions, logprobs_np, values_np = jax.device_get(
                        (actions, real_actions, logprobs, values)
                    )
                    if not is_continuous and real_actions.shape[-1] == 1 and not is_multidiscrete:
                        real_actions = real_actions[..., 0]

                    obs, rewards, terminated, truncated, info = envs.step(
                        real_actions.reshape(envs.action_space.shape)
                    )
                    rewards = np.asarray(rewards, dtype=np.float32).reshape(num_envs, 1)

                    # truncation bootstrap (reference ppo.py:286-305)
                    truncated_envs = np.nonzero(truncated)[0]
                    if len(truncated_envs) > 0 and "final_obs" in info:
                        final_obs = {
                            k: np.stack([np.asarray(info["final_obs"][e][k]) for e in truncated_envs])
                            for k in obs_keys
                        }
                        final_obs = prepare_obs(final_obs, cnn_keys=cnn_keys, num_envs=len(truncated_envs))
                        vals = np.asarray(player.get_values(final_obs)).reshape(len(truncated_envs))
                        rewards[truncated_envs, 0] += float(cfg.algo.gamma) * vals

                    dones = np.logical_or(terminated, truncated).reshape(num_envs, 1).astype(np.float32)
                    # in-place writes into the preallocated [T, ...] arrays —
                    # the write is the copy; no list-append + np.stack pass
                    step_values = {k: next_obs[k] for k in obs_keys}
                    step_values["dones"] = dones
                    step_values["values"] = values_np
                    step_values["actions"] = actions_np
                    step_values["logprobs"] = logprobs_np
                    step_values["rewards"] = rewards
                    buf.put(t, step_values)

                    next_obs = prepare_obs(obs, cnn_keys=cnn_keys, num_envs=num_envs)

                    if cfg.metric.log_level > 0 and "final_info" in info:
                        ep = info["final_info"].get("episode")
                        if ep is not None:
                            for i in np.nonzero(ep.get("_r", []))[0]:
                                aggregator.update("Rewards/rew_avg", float(ep["r"][i]))
                                aggregator.update("Game/ep_len_avg", float(ep["l"][i]))
                                print(f"Rank-0: policy_step={policy_step}, reward_env_{i}={ep['r'][i]}")

            local_data = buf.arrays()  # [T, E, ...]

            # GAE on the player's device (reference ppo.py:345-360) — rollout
            # arrays are host-side already, so with a host-pinned player the
            # whole advantage pass stays off the chip's round-trip path
            next_values = np.asarray(player.get_values(next_obs))  # [E, 1]
            returns, advantages = gae_fn(
                put_tree(local_data["rewards"], player.device),
                put_tree(local_data["values"], player.device),
                put_tree(local_data["dones"], player.device),
                put_tree(next_values, player.device),
            )
            local_data["returns"] = np.asarray(returns)
            local_data["advantages"] = np.asarray(advantages)

            # flatten [T, E, ...] -> [T*E, ...]; shard_map splits over devices;
            # multi-host runs assemble the per-process blocks into a global array
            flat = {k: v.reshape(v.shape[0] * v.shape[1], *v.shape[2:]) for k, v in local_data.items()}
            if fabric.num_processes > 1:
                flat = fabric.make_global(flat, (fabric.data_axis,))

            telemetry_train_window(host_dispatches_per_update, update_epochs * num_minibatches)
            if overlap_collection and not finalize_pending():
                # the in-flight update rolled back; this rollout was collected
                # against the poisoned stream, drop it too
                continue

            with timer("Time/train_time"):
                key, train_key = jax.random.split(key)
                params, opt_state, metrics = train_fn(
                    params,
                    opt_state,
                    flat,
                    train_key,
                    # host numpy scalars: jnp.float32 would materialize them on
                    # the DEFAULT backend every update — with a host-pinned train
                    # device that is a blocking accelerator fetch per update,
                    # more than the round trips host-training saves
                    np.float32(clip_coef),
                    np.float32(ent_coef),
                )
                if not overlap_collection:
                    # ONE fetch syncs the dispatch and serves both the NaN
                    # sentinel and the aggregator scalars below (the old
                    # block_until_ready + asarray pair was two device syncs)
                    metrics = np.asarray(metrics)
            if update == start_update:
                # shapes are fixed from here on; register the MFU flops source
                # off the first real invocation (resolved lazily at heartbeat)
                telemetry_register_flops(
                    train_fn, params, opt_state, flat, train_key, np.float32(clip_coef), np.float32(ent_coef)
                )
            if overlap_collection:
                # do NOT wait: the next collection overlaps this update's
                # device execution; the player keeps the stale params
                pending = (metrics, update)
            else:
                if not resil.check_finite(metrics, update):
                    rollback_state(update)
                    continue
                player.update_params(params)
                train_step += world_size
                update_loss_metrics(metrics)

            maybe_heartbeat(update == num_updates)
            anneal_coefs()
            maybe_checkpoint()

        # drain the last in-flight update so its params/metrics are committed
        # before eval and the final checkpointed state
        finalize_pending()

    envs.close()
    if fabric.is_global_zero and cfg.algo.run_test and not preempted:
        if obs_widened:
            # the agent expects the scenario family's widened observation; the
            # host eval env emits the base one — there is nothing to evaluate
            warnings.warn("skipping run_test: env.variants widened the observation past the host env's")
        else:
            test(player, fabric, cfg, log_dir)
    logger.finalize()
    resil.close()
    if preempted:
        resil.exit_preempted()
