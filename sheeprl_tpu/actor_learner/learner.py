"""Learner process: continuous donated updates over staleness-admitted slabs.

``run_actor_learner`` is the single-process entrypoint of the disaggregated
topology (``ppo_decoupled`` dispatches here when there is no jax.distributed
process group): it owns the devices, the trajectory ring, the param lane and
the actor supervisor, and runs the admission loop

    poll ring → admit (staleness bound) → fused donated update →
    bump version → publish packed params → repeat

until ``num_updates`` slabs have trained. Every slab is a complete training
batch (the actors run GAE), so the learner never blocks on collection — its
idle time is exactly the slab-starved wait, reported as
``Time/train_wait_time`` so the heartbeat's ``overlap_fraction`` reads the
topology's health directly (→ 1.0 when actors keep the ring fed).

Fault surface wired here: the resilience crash guard + preemption watcher
(SIGTERM → emergency checkpoint → quiesce actors → exit 77), the NaN
sentinel/rollback, the actor supervisor's budgeted restarts (budget
exhaustion aborts the run with :class:`ActorBudgetExhausted` → outcome
``actor_exhausted``), and the learner-side halves of the scripted drills
(``learner_kill``, ``param_lane_stall``).
"""

from __future__ import annotations

import os
import signal
import time
from typing import Any, Dict, Optional

import gymnasium as gym
import jax
import numpy as np
import optax

from sheeprl_tpu.actor_learner.config import ActorLearnerConfig, actor_learner_config_from_cfg, admit
from sheeprl_tpu.actor_learner.fault_injection import LearnerFaultSchedule, actor_faults_for
from sheeprl_tpu.actor_learner.ring import SlabLayout
from sheeprl_tpu.actor_learner.supervisor import ActorSupervisor
from sheeprl_tpu.net.transport import build_learner_transport
from sheeprl_tpu.algos.ppo.agent import PPOPlayer, build_agent
from sheeprl_tpu.algos.ppo.ppo import make_train_fn
from sheeprl_tpu.algos.ppo.utils import AGGREGATOR_KEYS, test
from sheeprl_tpu.config.compose import instantiate
from sheeprl_tpu.obs import (
    telemetry_actor_restart,
    telemetry_advance,
    telemetry_child_file,
    telemetry_mark_warm,
    telemetry_register_flops,
    telemetry_run_metrics,
    telemetry_slab,
    telemetry_slab_lag,
    telemetry_torn_slabs,
    telemetry_train_window,
)
from sheeprl_tpu.obs.telemetry import get_telemetry
from sheeprl_tpu.obs.trace import set_trace_role, trace_event
from sheeprl_tpu.parallel.fabric import _ParamStreamer, put_tree, resolve_player_device, resolve_train_device
from sheeprl_tpu.parallel.submesh import probe_spaces
from sheeprl_tpu.resilience import RunResilience
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.utils import polynomial_decay, save_configs


def build_slab_layout(obs_space, cnn_keys, mlp_keys, actions_width: int, rows: int) -> SlabLayout:
    """The slab wire format both ends agree on: prepared obs (cnn stack-folded
    uint8, mlp float32), the flattened training columns, and the 3-word
    episode-stats tail ``[ret_sum, len_sum, ep_count]``."""
    fields: Dict[str, Any] = {}
    for k in cnn_keys:
        shape = obs_space[k].shape  # [S,H,W,C] (stacked) or [H,W,C]
        if len(shape) == 4:
            s, h, w, c = shape
            shape = (h, w, s * c)
        fields[k] = ((rows, *shape), "uint8")
    for k in mlp_keys:
        fields[k] = ((rows, *obs_space[k].shape), "float32")
    fields["actions"] = ((rows, actions_width), "float32")
    for k in ("logprobs", "values", "returns", "advantages"):
        fields[k] = ((rows, 1), "float32")
    fields["ep_stats"] = ((3,), "float32")
    return SlabLayout(fields)


def run_actor_learner(fabric, cfg: Dict[str, Any], state: Optional[Dict[str, Any]] = None):
    log_dir = get_log_dir(cfg)
    logger = get_logger(cfg, log_dir)
    fabric.logger = logger
    logger.log_hyperparams(cfg.to_dict() if hasattr(cfg, "to_dict") else dict(cfg))
    print(f"Log dir: {log_dir}")

    resil = RunResilience(fabric, cfg, log_dir)
    alcfg: ActorLearnerConfig = actor_learner_config_from_cfg(cfg)
    # name this process's track on the merged cross-process timeline; actors
    # hand their standalone recorders their own roles (actor<i>)
    set_trace_role("learner")
    # actors get a trace dir only when the run is telemetered — their
    # flush-per-event recorders exist to be merged with telemetry.jsonl
    trace_dir = log_dir if get_telemetry() is not None else None

    num_envs = int(cfg.env.num_envs)
    rollout_steps = int(cfg.algo.rollout_steps)
    envs_per_actor = alcfg.envs_per_actor(num_envs)
    slab_rows = rollout_steps * envs_per_actor

    observation_space, action_space = probe_spaces(cfg)
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
    is_continuous = isinstance(action_space, gym.spaces.Box)
    is_multidiscrete = isinstance(action_space, gym.spaces.MultiDiscrete)
    actions_dim = tuple(
        action_space.shape
        if is_continuous
        else (action_space.nvec.tolist() if is_multidiscrete else [action_space.n])
    )

    agent, params = build_agent(
        fabric, actions_dim, is_continuous, cfg, observation_space, state["agent"] if state else None
    )
    player = PPOPlayer(agent, params, device=resolve_player_device(cfg.algo.get("player_device", "auto")))

    world_size = fabric.data_parallel_size
    if slab_rows % world_size != 0:
        raise ValueError(
            f"rollout_steps*envs_per_actor ({slab_rows}) must be divisible by the device count ({world_size})"
        )
    n_local = slab_rows // world_size
    num_minibatches = max(1, n_local // int(cfg.algo.per_rank_batch_size))
    update_epochs = int(cfg.algo.update_epochs)
    # each admitted slab is one update worth slab_rows env steps
    policy_steps_per_update = slab_rows
    num_updates = int(cfg.algo.total_steps) // policy_steps_per_update if not cfg.dry_run else 1

    opt_cfg = dict(cfg.algo.optimizer.to_dict() if hasattr(cfg.algo.optimizer, "to_dict") else cfg.algo.optimizer)
    if cfg.algo.max_grad_norm and float(cfg.algo.max_grad_norm) > 0:
        opt_cfg["max_grad_norm"] = float(cfg.algo.max_grad_norm)
    if cfg.algo.anneal_lr:
        opt_cfg["schedule"] = optax.linear_schedule(
            float(opt_cfg.get("lr", 1e-3)), 0.0, num_updates * update_epochs * num_minibatches
        )
    tx = instantiate(opt_cfg)
    train_device = resolve_train_device(cfg.algo.get("train_device", "auto"), params, fabric.world_size)
    if train_device is not None:
        params = put_tree(jax.device_get(params), train_device)
        player.update_params(params)
    opt_state = state["opt_state"] if state else tx.init(params)
    opt_state = put_tree(opt_state, train_device) if train_device is not None else fabric.replicate(opt_state)

    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = MetricAggregator(cfg.metric.get("aggregator", {}).get("metrics", {}) or {})
    for k in AGGREGATOR_KEYS - set(aggregator.metrics):
        aggregator.add(k, "mean")

    train_fn = make_train_fn(fabric, agent, tx, cfg, obs_keys, n_local, host_device=train_device)

    # ------------------------------------------------------------- transport
    layout = build_slab_layout(observation_space, cnn_keys, mlp_keys, int(sum(actions_dim)), slab_rows)
    pack_device = train_device if train_device is not None else jax.local_devices()[0]
    streamer = _ParamStreamer(jax.device_get(params), pack_device)
    transport = build_learner_transport(
        alcfg.transport,
        payload_bytes=layout.nbytes,
        num_slots=alcfg.num_actors * alcfg.slots_per_actor,
        slots_per_actor=alcfg.slots_per_actor,
        param_nbytes=streamer.nbytes,
        host=alcfg.bind_host,
        port=alcfg.bind_port,
    )

    precision_name = fabric.precision.name

    def make_blob(actor_index: int, generation: int) -> bytes:
        import cloudpickle

        # scripted faults ride ONLY the generation-0 blob: a respawned actor
        # must not re-fire the drill that killed it (crash loop)
        faults = (
            [f.to_wire() for f in actor_faults_for(alcfg.faults, actor_index)] if generation == 0 else []
        )
        return cloudpickle.dumps(
            {
                "cfg": cfg,
                "generation": generation,
                "slots": alcfg.actor_slots(actor_index),
                "envs_per_actor": envs_per_actor,
                "rollout_steps": rollout_steps,
                "faults": faults,
                "precision": precision_name,
                "transport": transport.actor_wire(actor_index),
                "layout": layout.to_wire(),
                "trace_dir": trace_dir,
                # seq-disjoint generations keep the fold_in action streams
                # unique across restarts
                "start_seq": generation * (1 << 20),
            }
        )

    version = 0
    transport.publish_params(np.asarray(streamer.begin(params)), version)
    trace_event("param_publish", version=version)

    supervisor = ActorSupervisor(alcfg, transport, make_blob, on_restart=telemetry_actor_restart)
    if trace_dir is not None:
        # declare the child trace files up front so the registry record names
        # the run's full file set even if an actor dies before its first slab
        for i in range(alcfg.num_actors):
            telemetry_child_file(os.path.join(trace_dir, f"trace.actor{i}.jsonl"))

    # --------------------------------------------------------------- counters
    start_update = (state["update"] + 1) if state else 1
    policy_step = state["update"] * policy_steps_per_update if state else 0
    last_log = state["last_log"] if state else 0
    last_checkpoint = state["last_checkpoint"] if state else 0
    train_step = 0
    last_train = 0
    update = start_update - 1  # completed updates

    key = jax.random.PRNGKey(int(cfg.seed))
    if state and "rng_key" in state:
        key = np.asarray(state["rng_key"])
    if train_device is not None:
        key = put_tree(key, train_device)
    elif state and "rng_key" in state:
        import jax.numpy as jnp

        key = jnp.asarray(key)

    clip_coef = float(cfg.algo.clip_coef)
    ent_coef = float(cfg.algo.ent_coef)
    initial_clip_coef, initial_ent_coef = clip_coef, ent_coef

    def ckpt_state_fn(completed_update: int) -> Dict[str, Any]:
        return {
            "agent": jax.device_get(params),
            "opt_state": jax.device_get(opt_state),
            "update": completed_update,
            "batch_size": int(cfg.algo.per_rank_batch_size) * world_size,
            "last_log": last_log,
            "last_checkpoint": last_checkpoint,
            "rng_key": jax.device_get(key),
        }

    def ckpt_path_fn(step: int) -> str:
        return os.path.join(log_dir, "checkpoint", f"ckpt_{step}_{fabric.process_index}.ckpt")

    def rollback_state(at_update: int) -> None:
        # restore the newest committed checkpoint and fork the train key away
        # from the diverged stream; the actors never saw the poisoned params
        # (publish happens only after the finite check), so the lane stays on
        # the last good version
        nonlocal params, opt_state, key
        restored = resil.rollback(update=at_update)
        params = resil.place_like(restored["agent"], params)
        opt_state = resil.place_like(restored["opt_state"], opt_state)
        if "rng_key" in restored:
            key = resil.place_like(restored["rng_key"], key)
        key = resil.resalt_key(key)

    def maybe_checkpoint() -> None:
        nonlocal last_checkpoint
        if (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every) or (
            update == num_updates and cfg.checkpoint.save_last
        ):
            last_checkpoint = policy_step
            fabric.call("on_checkpoint_coupled", ckpt_path=ckpt_path_fn(policy_step), state=ckpt_state_fn(update))

    resil.arm_crash_guard(
        path_fn=lambda: ckpt_path_fn(policy_step),
        state_fn=lambda: ckpt_state_fn(update),
    )

    fault_sched = LearnerFaultSchedule(alcfg.faults)
    stall_until = 0.0  # param_lane_stall: monotonic deadline; 0 = not stalled
    published_version = version

    # window accumulators for the heartbeat: env time is credited from the
    # slabs' COLLECT_US stamps (the actors' wall clock), wait time is the
    # learner's slab-starved idle, train time is measured around the update.
    # Idle before the FIRST admitted slab is actor boot (process spawn + jax
    # import + jit warmup) — the analogue of the sync loop's pre-loop env
    # construction, which its timers never see either — so it is reported as
    # its own spawn_wait event instead of polluting the steady-state
    # overlap_fraction.
    win_env_s = 0.0
    win_env_steps = 0
    win_train_s = 0.0
    win_wait_s = 0.0
    spawn_wait_s = 0.0
    torn_seen = 0
    admitted = 0
    dropped_stale = 0

    def sync_torn() -> None:
        nonlocal torn_seen
        total = transport.torn_detected + supervisor.torn_reclaimed
        if total > torn_seen:
            telemetry_torn_slabs(total - torn_seen, source=transport.kind)
            torn_seen = total
        # terminate each victim's causal chain on the merged timeline: its
        # trace ends at `torn`, never at `slab_train`
        for tid in transport.drain_torn_trace_ids():
            trace_event("torn", tid, source=transport.kind)

    def maybe_heartbeat(final: bool = False) -> None:
        nonlocal last_log, last_train, win_env_s, win_env_steps, win_train_s, win_wait_s
        if cfg.metric.log_level <= 0 or (policy_step - last_log < cfg.metric.log_every and not final):
            return
        metrics_dict = aggregator.compute()
        logger.log_metrics(metrics_dict, policy_step)
        telemetry_run_metrics(metrics_dict)
        aggregator.reset()
        sps = {}
        if win_train_s > 0:
            sps["Time/sps_train"] = (train_step - last_train) / win_train_s
        if win_env_s > 0:
            sps["Time/sps_env_interaction"] = win_env_steps / win_env_s
        if sps:
            logger.log_metrics(sps, policy_step)
        tel = get_telemetry()
        if tel is not None:
            tel.heartbeat(
                logger,
                step=policy_step,
                env_steps=win_env_steps,
                train_steps=train_step - last_train,
                train_invocations=(train_step - last_train) // world_size,
                timer_window={
                    "Time/env_interaction_time": win_env_s,
                    "Time/train_time": win_train_s,
                    "Time/train_wait_time": win_wait_s,
                },
            )
        last_log = policy_step
        last_train = train_step
        win_env_s = win_env_steps = 0
        win_train_s = win_wait_s = 0.0

    preempted = False
    try:
        supervisor.spawn_all()
        while update < num_updates:
            if resil.preempt_requested():
                last_checkpoint = policy_step
                resil.emergency_checkpoint(ckpt_path_fn(policy_step), ckpt_state_fn(update))
                preempted = True
                break

            # an expired param_lane_stall: catch the lane up to the current
            # version so actors stop sampling the stalled one
            if stall_until and time.monotonic() >= stall_until:
                stall_until = 0.0
                if published_version < version:
                    transport.publish_params(np.asarray(streamer.begin(params)), version)
                    trace_event("param_publish", version=version, after_stall=True)
                    published_version = version

            meta = transport.poll()
            sync_torn()
            if meta is None:
                t0 = time.perf_counter()
                supervisor.check_health()
                time.sleep(alcfg.poll_interval_s)
                if admitted:
                    win_wait_s += time.perf_counter() - t0
                else:
                    spawn_wait_s += time.perf_counter() - t0
                continue

            staleness = version - meta.param_version
            ok = admit(meta.param_version, version, alcfg.max_staleness)
            telemetry_slab(staleness=staleness, occupancy=transport.occupancy(), admitted=ok)
            # commit→admit ring wait from the slab header's epoch-µs commit
            # stamp (same host, so the epoch clocks agree)
            ring_wait_us = (
                max(0, int(time.time() * 1e6) - meta.commit_t_us) if meta.commit_t_us else 0
            )
            if not ok:
                # count, drop, free the slot — the owning actor refills it
                # against a fresher version
                dropped_stale += 1
                if meta.trace_id:
                    trace_event(
                        "slab_drop_stale",
                        meta.trace_id,
                        actor=meta.actor_id,
                        seq=meta.seq,
                        param_version=meta.param_version,
                        staleness=staleness,
                    )
                transport.release(meta)
                continue
            if meta.trace_id:
                trace_event(
                    "slab_admit",
                    meta.trace_id,
                    slot=meta.slot,
                    actor=meta.actor_id,
                    seq=meta.seq,
                    param_version=meta.param_version,
                    staleness=staleness,
                    ring_wait_us=ring_wait_us,
                )

            if admitted == 0 and spawn_wait_s > 0:
                # the first slab just landed: everything the learner waited
                # through so far was actor boot, not slab starvation
                tel = get_telemetry()
                if tel is not None:
                    tel.emit("spawn_wait", seconds=spawn_wait_s)

            flat = layout.unpack(transport.payload(meta))  # copies out
            transport.release(meta)
            ep_stats = flat.pop("ep_stats")

            telemetry_advance(policy_step)
            if update == start_update:
                telemetry_mark_warm()
            t0 = time.perf_counter()
            key, train_key = jax.random.split(key)
            params, opt_state, metrics = train_fn(
                params,
                opt_state,
                flat,
                train_key,
                np.float32(clip_coef),
                np.float32(ent_coef),
            )
            metrics = np.asarray(metrics)
            train_dt = time.perf_counter() - t0
            win_train_s += train_dt
            telemetry_train_window(1, update_epochs * num_minibatches)

            if not resil.check_finite(metrics, update + 1):
                rollback_state(update + 1)
                continue

            update += 1
            train_step += world_size
            policy_step += meta.n_rows
            win_env_s += meta.collect_us / 1e6
            win_env_steps += meta.env_steps
            if meta.trace_id:
                train_us = int(train_dt * 1e6)
                trace_event("slab_train", meta.trace_id, train_us=train_us, update=update)
                telemetry_slab_lag(
                    collect_us=meta.collect_us, ring_wait_us=ring_wait_us, train_us=train_us
                )
            if update == start_update:
                telemetry_register_flops(
                    train_fn, params, opt_state, flat, train_key, np.float32(clip_coef), np.float32(ent_coef)
                )

            if cfg.metric.log_level > 0:
                aggregator.update("Loss/policy_loss", float(metrics[0]))
                aggregator.update("Loss/value_loss", float(metrics[1]))
                aggregator.update("Loss/entropy_loss", float(metrics[2]))
                if ep_stats[2] > 0:
                    aggregator.update("Rewards/rew_avg", float(ep_stats[0] / ep_stats[2]))
                    aggregator.update("Game/ep_len_avg", float(ep_stats[1] / ep_stats[2]))

            # versioned broadcast: the bump precedes the publish, and a
            # scripted lane stall suppresses ONLY the publish — admission
            # keeps counting against the bumped version, which is what drives
            # the staleness drill's count/drop/refill path
            version += 1
            for f in fault_sched.pop_due(admitted):
                if f.kind == "param_lane_stall":
                    stall_until = time.monotonic() + f.duration_s
                elif f.kind == "learner_kill":
                    os.kill(os.getpid(), signal.SIGTERM)
            if not stall_until:
                transport.publish_params(np.asarray(streamer.begin(params)), version)
                trace_event("param_publish", version=version)
                published_version = version
            admitted += 1

            if cfg.algo.anneal_clip_coef:
                clip_coef = polynomial_decay(
                    update, initial=initial_clip_coef, final=0.0, max_decay_steps=num_updates, power=1.0
                )
            if cfg.algo.anneal_ent_coef:
                ent_coef = polynomial_decay(
                    update, initial=initial_ent_coef, final=0.0, max_decay_steps=num_updates, power=1.0
                )
            maybe_heartbeat(update == num_updates)
            maybe_checkpoint()
    finally:
        # BOTH exits — clean and crash — must leave zero orphaned actors and
        # zero leaked shm segments; the cli's crash drain runs after this
        try:
            supervisor.quiesce_all()
        except Exception:
            pass
        sync_torn()
        transport.close()

    maybe_heartbeat(final=True)
    if fabric.is_global_zero and cfg.algo.run_test and not preempted:
        player.update_params(params)
        test(player, fabric, cfg, log_dir)
    logger.finalize()
    resil.close()
    if preempted:
        resil.exit_preempted()
