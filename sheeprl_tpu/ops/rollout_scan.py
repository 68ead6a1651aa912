"""Fused on-policy collection: the whole rollout+GAE+update as ONE dispatch.

The coupled PPO host loop pays one jitted dispatch plus one device->host fetch
per env step, then a GAE dispatch, then the fused update: host bookkeeping
around a jitted player, step after step.
This module closes the gap for envs with a jittable twin
(:mod:`sheeprl_tpu.envs.jittable`): the T-step rollout (agent forward, env
transition, truncation bootstrap, autoreset, per-step bookkeeping) runs as a
``lax.scan``, GAE as the existing reverse scan (:func:`sheeprl_tpu.ops.math.
gae`), and the result feeds the fused epochs x minibatches update — all inside
one donated jit, zero host round trips per update.

Host-loop parity contract (the numerical-equivalence test pins all of it):

- the action key for step ``t`` is ``fold_in(update_key, policy_step_t)`` with
  ``policy_step_t`` incremented *before* sampling — exactly
  ``PPOPlayer.rollout_actions``'s schedule;
- rewards of truncated envs are bootstrapped with ``gamma * V(final_obs)``
  for ANY truncated env (terminated-and-truncated included), matching the
  host loop's ``info["final_obs"]`` block;
- the train key is ``key, k_train = jax.random.split(key)`` once per update
  and the evolved ``key`` is returned, so chunked supersteps continue the
  same stream the host loop would have produced.

Env randomness is a parallel stream: per-step, per-env keys are derived from
``update_key`` via a salted ``fold_in`` chain (never from the action/train
streams), so the policy's sample stream is untouched by autoreset timing.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from sheeprl_tpu.envs.jittable import JittableEnvSpec
from sheeprl_tpu.envs.variants import ScenarioFamily
from sheeprl_tpu.ops.math import gae
from sheeprl_tpu.parallel.shard_map import shard_map

# salt separating the env reset/transition stream from the action stream that
# shares the same ``update_key`` root (superstep.py's 0x5EED discipline)
ENV_STREAM_SALT = 0x0E5E

Pytree = Any


def _spec_vmaps(spec, is_family: bool):
    """Batched ``observation``/``step``/``init`` with a leading theta slot:
    for a :class:`ScenarioFamily` the theta rows vmap with the env state
    (every env is a distinct randomized instance); for a plain spec the slot
    is broadcast (and ignored) so call sites are shape-agnostic."""
    if is_family:
        v_observation = jax.vmap(lambda th, s: spec.instantiate(th).observation(s))
        v_step = jax.vmap(lambda th, s, a, k: spec.instantiate(th).step(s, a, k))
        v_init = jax.vmap(lambda th, k: spec.instantiate(th).init(k))
    else:
        v_observation = jax.vmap(lambda th, s: spec.observation(s), in_axes=(None, 0))
        v_step = jax.vmap(lambda th, s, a, k: spec.step(s, a, k), in_axes=(None, 0, 0, 0))
        v_init = jax.vmap(lambda th, k: spec.init(k), in_axes=(None, 0))
    return v_observation, v_step, v_init


def init_env_carry(
    spec: JittableEnvSpec,
    num_envs: int,
    key: jax.Array,
    thetas: Optional[jax.Array] = None,
) -> Dict[str, Pytree]:
    """Reset ``num_envs`` jittable envs and build the cross-update carry:
    batched env state plus running episode-return/length accumulators
    (episodes span update boundaries, so these ride the carry).  The current
    observation is deliberately NOT carried — it is a pure function of the
    state, and for identity-observation envs (CartPole) a carried copy would
    alias the state buffer and break the superstep's carry donation.

    When ``spec`` is a :class:`ScenarioFamily`, ``thetas`` is the ``[E, P]``
    scenario matrix: row i parameterizes env i for its whole lifetime
    (randomization persists across autoresets).  The matrix rides the carry so
    the mesh variant shards it over the data axis with the env state."""
    env_ids = jnp.arange(num_envs, dtype=jnp.uint32)
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(key, env_ids)
    if isinstance(spec, ScenarioFamily):
        if thetas is None:
            raise ValueError("a ScenarioFamily carry needs the [E, P] theta matrix")
        if thetas.shape != (num_envs, spec.param_dim):
            raise ValueError(
                f"theta matrix shape {thetas.shape} != ({num_envs}, {spec.param_dim})"
            )
        state = jax.vmap(lambda th, k: spec.instantiate(th).init(k))(thetas, keys)
        carry: Dict[str, Pytree] = {"state": state, "theta": thetas}
    else:
        if thetas is not None:
            raise ValueError("theta matrix given but spec is not a ScenarioFamily")
        carry = {"state": jax.vmap(spec.init)(keys)}
    carry["ep_ret"] = jnp.zeros((num_envs,), jnp.float32)
    carry["ep_len"] = jnp.zeros((num_envs,), jnp.int32)
    return carry


def init_recurrent_env_carry(
    spec: JittableEnvSpec,
    num_envs: int,
    key: jax.Array,
    *,
    hidden_size: int,
    action_dim: int,
    thetas: Optional[jax.Array] = None,
) -> Dict[str, Pytree]:
    """:func:`init_env_carry` plus the recurrent player's cross-update state:
    the LSTM hidden/cell pair and the buffer-layout previous actions, all
    env-major so the mesh variant shards them over the data axis with the env
    state."""
    carry = init_env_carry(spec, num_envs, key, thetas=thetas)
    carry["hx"] = jnp.zeros((num_envs, hidden_size), jnp.float32)
    carry["cx"] = jnp.zeros((num_envs, hidden_size), jnp.float32)
    carry["prev_actions"] = jnp.zeros((num_envs, action_dim), jnp.float32)
    return carry


def make_onpolicy_superstep_fn(
    spec: JittableEnvSpec,
    *,
    policy_fn: Callable,
    value_fn: Callable,
    local_train: Callable,
    obs_key: str,
    rollout_steps: int,
    step_increment: int,
    gamma: float,
    gae_lambda: float,
    mesh=None,
    data_axis: Optional[str] = None,
) -> Callable:
    """Build the fused on-policy superstep.

    ``policy_fn(params, obs_dict, key) -> (actions, real_actions, logprobs,
    values)`` is the agent's rollout head (``agent.rollout_step`` partial);
    ``value_fn(params, obs_dict) -> [E, 1]`` the critic head;
    ``local_train`` the UNJITTED fused update body from
    ``make_train_fn``/``make_local_train`` — embedding it here is what makes
    the whole update one dispatch.  ``step_increment`` is the global
    policy-step bump per scanned step (``num_envs * num_processes``), so the
    in-graph action-key schedule equals the host loop's counter bookkeeping.

    With ``mesh``/``data_axis`` the superstep is ``shard_map``ped: the env
    carry (and hence the envs themselves) shards over the data axis, each
    device collects its own slice, and ``local_train``'s gradient ``pmean``
    is the DDP all-reduce — params/opt state stay replicated.

    ``spec`` may be a :class:`ScenarioFamily` (``envs/variants.py``): the env
    carry then includes the ``[E, P]`` scenario matrix under ``"theta"``, and
    env init/step/observation vmap ``family.instantiate`` over the rows, so
    every env is a *distinct domain-randomized instance* of one compiled
    program.  Because theta is an env-major carry leaf, the mesh variant
    shards the parameter rows over the data axis exactly like the env state —
    batched domain randomization in the same single dispatch.

    Returns a jit with ``donate_argnums=(1,)``: the opt state is consumed
    each call.  Params are NOT donated because the host-pinned player aliases
    them between updates (same contract as the host train fn).  The env carry
    is NOT donated either — it is a few KB, and XLA CSE can legally emit its
    numerically-identical leaves (CartPole's step counter, episode length and
    unit-reward episode return are the same stream) as ONE buffer, which a
    donating call would then try to donate twice.
    """
    if rollout_steps <= 0:
        raise ValueError(f"rollout_steps must be positive, got {rollout_steps}")
    if step_increment <= 0:
        raise ValueError(f"step_increment must be positive, got {step_increment}")
    gamma = float(gamma)
    gae_lambda = float(gae_lambda)
    use_mesh = mesh is not None
    is_family = isinstance(spec, ScenarioFamily)

    def superstep(params, opt_state, env_carry, update_key, key, policy_step, clip_coef, ent_coef):
        # shard-local env count under shard_map; the global count on one host
        num_envs = env_carry["ep_ret"].shape[0]
        env_ids = jnp.arange(num_envs, dtype=jnp.uint32)
        env_root = jax.random.fold_in(update_key, ENV_STREAM_SALT)
        if use_mesh:
            # distinct reset/transition streams per device shard
            env_root = jax.random.fold_in(env_root, lax.axis_index(data_axis))

        # Closing over the shard-local theta rows keeps them out of the scan
        # carry (they are loop-invariant) while still batching env dynamics
        # over the per-instance parameters.
        theta = env_carry["theta"] if is_family else None
        v_observation, v_step, v_init = _spec_vmaps(spec, is_family)

        def step_fn(carry, _):
            state, ep_ret, ep_len, step_counter = carry
            obs = v_observation(theta, state)
            # counter bumps BEFORE sampling — rollout_actions' fold schedule
            step_counter = step_counter + step_increment
            k_act = jax.random.fold_in(update_key, step_counter)
            if use_mesh:
                k_act = jax.random.fold_in(k_act, lax.axis_index(data_axis))
            actions, real_actions, logprobs, values = policy_fn(params, {obs_key: obs}, k_act)
            if spec.is_continuous:
                act = real_actions
            else:
                act = real_actions[..., 0].astype(jnp.int32)

            env_base = jax.random.fold_in(env_root, step_counter)
            per_env = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(env_base, env_ids)
            pair = jax.vmap(jax.random.split)(per_env)  # [E, 2, key]
            next_state, out = v_step(theta, state, act, pair[:, 0])

            raw_reward = out.reward.astype(jnp.float32)
            truncated_f = out.truncated.astype(jnp.float32)
            # truncation bootstrap on the PRE-autoreset observation: the host
            # loop's info["final_obs"] value pass, now a fused critic call
            v_final = value_fn(params, {obs_key: out.obs})
            reward = raw_reward + gamma * v_final[:, 0] * truncated_f
            done = jnp.logical_or(out.terminated, out.truncated)

            ep_ret = ep_ret + raw_reward
            ep_len = ep_len + 1
            ys = {
                obs_key: obs,
                "dones": done[:, None].astype(jnp.float32),
                "values": values,
                "actions": actions,
                "logprobs": logprobs,
                "rewards": reward[:, None],
                "ep_done": done,
                "ep_ret": ep_ret,
                "ep_len": ep_len,
            }

            # SAME_STEP autoreset: done envs restart immediately; the stored
            # transition keeps the terminal reward/done, the next step's obs
            # comes from the fresh episode
            reset_state = v_init(theta, pair[:, 1])

            def _select(reset_leaf, next_leaf):
                d = done.reshape(done.shape + (1,) * (next_leaf.ndim - 1))
                return jnp.where(d, reset_leaf, next_leaf)

            state = jax.tree.map(_select, reset_state, next_state)
            ep_ret = jnp.where(done, 0.0, ep_ret)
            ep_len = jnp.where(done, 0, ep_len)
            return (state, ep_ret, ep_len, step_counter), ys

        carry0 = (
            env_carry["state"],
            env_carry["ep_ret"],
            env_carry["ep_len"],
            policy_step,
        )
        (state, ep_ret, ep_len, _), ys = lax.scan(step_fn, carry0, None, length=rollout_steps)

        ep_stats = {
            "done": ys.pop("ep_done"),  # [T, E] bool
            "ret": ys.pop("ep_ret"),  # [T, E] return-so-far at each step
            "len": ys.pop("ep_len"),  # [T, E]
        }
        next_values = value_fn(params, {obs_key: v_observation(theta, state)})  # [E, 1]
        returns, advantages = gae(
            ys["rewards"], ys["values"], ys["dones"], next_values, gamma=gamma, gae_lambda=gae_lambda
        )
        data = dict(ys)
        data["returns"] = returns
        data["advantages"] = advantages
        flat = jax.tree.map(lambda x: x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:]), data)

        key, k_train = jax.random.split(key)
        params, opt_state, metrics = local_train(params, opt_state, flat, k_train, clip_coef, ent_coef)
        new_carry = {"state": state, "ep_ret": ep_ret, "ep_len": ep_len}
        if is_family:
            new_carry["theta"] = theta
        return params, opt_state, new_carry, key, metrics, ep_stats

    if not use_mesh:
        return jax.jit(superstep, donate_argnums=(1,))
    carry_spec = P(data_axis)  # env-major leaves: shard axis 0 over devices
    stats_spec = P(None, data_axis)  # [T, E] leaves: shard the env axis
    wrapped = shard_map(
        superstep,
        mesh=mesh,
        in_specs=(P(), P(), carry_spec, P(), P(), P(), P(), P()),
        out_specs=(P(), P(), carry_spec, P(), P(), stats_spec),
    )
    return jax.jit(wrapped, donate_argnums=(1,))


def make_recurrent_onpolicy_superstep_fn(
    spec: JittableEnvSpec,
    *,
    policy_fn: Callable,
    value_fn: Callable,
    local_train: Callable,
    obs_key: str,
    rollout_steps: int,
    seq_len: int,
    step_increment: int,
    gamma: float,
    gae_lambda: float,
    reset_on_done: bool,
    mesh=None,
    data_axis: Optional[str] = None,
) -> Callable:
    """The fused superstep for recurrent PPO: the LSTM state rides the scan.

    Same contract as :func:`make_onpolicy_superstep_fn`, with the recurrent
    player's extra state (``hx``/``cx``/``prev_actions``) carried through the
    rollout scan and across updates via the env carry
    (:func:`init_recurrent_env_carry`):

    - ``policy_fn(params, obs_dict [1,E,...], prev_actions [1,E,A], hx, cx,
      key) -> (actions, real_actions, logprobs, values, hx', cx')`` is the
      recurrent rollout head (time-major with a singleton window, the host
      ``rollout_actions`` layout);
    - ``value_fn(params, obs_dict [1,E,...], prev_actions [1,E,A], hx, cx) ->
      [1, E, 1]`` the critic head; the truncation bootstrap uses the
      POST-step hidden state and the CURRENT actions, matching the host
      loop's ``final_obs`` value pass;
    - ``reset_on_done`` mirrors ``algo.reset_recurrent_state_on_done``: done
      envs restart the LSTM from zeros (``prev_actions`` always reset — the
      host loop's ``(1 - dones) * actions``).

    The host loop splits rollouts at episode boundaries into padded chunks;
    in-graph that is replaced by FIXED windows (``rollout_steps`` must be a
    multiple of ``seq_len``): ``N = (T / seq_len) * E`` fully-valid sequences
    whose initial state is the stored per-step ``prev_hx``/``prev_cx`` at each
    window start.  Windows may cross dones, so ``local_train`` receives the
    per-step ``dones`` and must replay the rollout's hidden-state resets
    (``evaluate_actions_resettable``); its signature is the recurrent update
    body's: ``local_train(params, opt_state, seq_data, hx0, cx0, key,
    clip_coef, ent_coef)``.
    """
    if rollout_steps <= 0:
        raise ValueError(f"rollout_steps must be positive, got {rollout_steps}")
    if seq_len <= 0 or rollout_steps % seq_len != 0:
        raise ValueError(
            f"rollout_steps ({rollout_steps}) must be a positive multiple of seq_len ({seq_len})"
        )
    if step_increment <= 0:
        raise ValueError(f"step_increment must be positive, got {step_increment}")
    gamma = float(gamma)
    gae_lambda = float(gae_lambda)
    num_windows = rollout_steps // seq_len
    use_mesh = mesh is not None
    is_family = isinstance(spec, ScenarioFamily)

    def superstep(params, opt_state, env_carry, update_key, key, policy_step, clip_coef, ent_coef):
        num_envs = env_carry["ep_ret"].shape[0]
        env_ids = jnp.arange(num_envs, dtype=jnp.uint32)
        env_root = jax.random.fold_in(update_key, ENV_STREAM_SALT)
        if use_mesh:
            env_root = jax.random.fold_in(env_root, lax.axis_index(data_axis))

        theta = env_carry["theta"] if is_family else None
        v_observation, v_step, v_init = _spec_vmaps(spec, is_family)

        def step_fn(carry, _):
            state, hx, cx, prev_actions, ep_ret, ep_len, step_counter = carry
            obs = v_observation(theta, state)
            step_counter = step_counter + step_increment
            k_act = jax.random.fold_in(update_key, step_counter)
            if use_mesh:
                k_act = jax.random.fold_in(k_act, lax.axis_index(data_axis))
            actions, real_actions, logprobs, values, new_hx, new_cx = policy_fn(
                params, {obs_key: obs[None]}, prev_actions[None], hx, cx, k_act
            )
            actions, real_actions, logprobs, values = (
                actions[0],
                real_actions[0],
                logprobs[0],
                values[0],
            )
            if spec.is_continuous:
                act = real_actions
            else:
                act = real_actions[..., 0].astype(jnp.int32)

            env_base = jax.random.fold_in(env_root, step_counter)
            per_env = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(env_base, env_ids)
            pair = jax.vmap(jax.random.split)(per_env)  # [E, 2, key]
            next_state, out = v_step(theta, state, act, pair[:, 0])

            raw_reward = out.reward.astype(jnp.float32)
            truncated_f = out.truncated.astype(jnp.float32)
            # truncation bootstrap with the POST-step recurrent state and the
            # current actions (the host loop's final_obs value pass)
            v_final = value_fn(params, {obs_key: out.obs[None]}, actions[None], new_hx, new_cx)
            reward = raw_reward + gamma * v_final[0, :, 0] * truncated_f
            done = jnp.logical_or(out.terminated, out.truncated)
            dones_f = done[:, None].astype(jnp.float32)

            ep_ret = ep_ret + raw_reward
            ep_len = ep_len + 1
            ys = {
                obs_key: obs,
                "dones": dones_f,
                "values": values,
                "actions": actions,
                "logprobs": logprobs,
                "rewards": reward[:, None],
                "prev_hx": hx,
                "prev_cx": cx,
                "prev_actions": prev_actions,
                "ep_done": done,
                "ep_ret": ep_ret,
                "ep_len": ep_len,
            }

            reset_state = v_init(theta, pair[:, 1])

            def _select(reset_leaf, next_leaf):
                d = done.reshape(done.shape + (1,) * (next_leaf.ndim - 1))
                return jnp.where(d, reset_leaf, next_leaf)

            state = jax.tree.map(_select, reset_state, next_state)
            prev_actions = (1.0 - dones_f) * actions
            if reset_on_done:
                new_hx = (1.0 - dones_f) * new_hx
                new_cx = (1.0 - dones_f) * new_cx
            ep_ret = jnp.where(done, 0.0, ep_ret)
            ep_len = jnp.where(done, 0, ep_len)
            return (state, new_hx, new_cx, prev_actions, ep_ret, ep_len, step_counter), ys

        carry0 = (
            env_carry["state"],
            env_carry["hx"],
            env_carry["cx"],
            env_carry["prev_actions"],
            env_carry["ep_ret"],
            env_carry["ep_len"],
            policy_step,
        )
        (state, hx, cx, prev_actions, ep_ret, ep_len, _), ys = lax.scan(
            step_fn, carry0, None, length=rollout_steps
        )

        ep_stats = {
            "done": ys.pop("ep_done"),
            "ret": ys.pop("ep_ret"),
            "len": ys.pop("ep_len"),
        }
        next_obs = v_observation(theta, state)
        next_values = value_fn(params, {obs_key: next_obs[None]}, prev_actions[None], hx, cx)[0]
        returns, advantages = gae(
            ys["rewards"], ys["values"], ys["dones"], next_values, gamma=gamma, gae_lambda=gae_lambda
        )
        data = dict(ys)
        data["returns"] = returns
        data["advantages"] = advantages
        # the window-start hidden state is the sequence's initial state (the
        # host loop's hx0/cx0 from the stored prev_hx at chunk starts)
        prev_hx = data.pop("prev_hx")
        prev_cx = data.pop("prev_cx")
        hidden = prev_hx.shape[-1]
        hx0 = prev_hx.reshape(num_windows, seq_len, num_envs, hidden)[:, 0].reshape(
            num_windows * num_envs, hidden
        )
        cx0 = prev_cx.reshape(num_windows, seq_len, num_envs, hidden)[:, 0].reshape(
            num_windows * num_envs, hidden
        )

        def to_seq(x):
            # [T, E, ...] -> [L, W*E, ...]; window w / env e lands at w*E+e,
            # consistent with the hx0/cx0 flattening above
            x = x.reshape((num_windows, seq_len) + x.shape[1:])
            x = jnp.moveaxis(x, 0, 1)
            return x.reshape((seq_len, num_windows * num_envs) + x.shape[3:])

        seq_data = jax.tree.map(to_seq, data)
        # fixed windows are fully valid — the mask exists only to keep the
        # update body shared with the host path's padded chunks
        seq_data["mask"] = jnp.ones((seq_len, num_windows * num_envs, 1), jnp.float32)

        key, k_train = jax.random.split(key)
        params, opt_state, metrics = local_train(
            params, opt_state, seq_data, hx0, cx0, k_train, clip_coef, ent_coef
        )
        new_carry = {
            "state": state,
            "hx": hx,
            "cx": cx,
            "prev_actions": prev_actions,
            "ep_ret": ep_ret,
            "ep_len": ep_len,
        }
        if is_family:
            new_carry["theta"] = theta
        return params, opt_state, new_carry, key, metrics, ep_stats

    if not use_mesh:
        return jax.jit(superstep, donate_argnums=(1,))
    carry_spec = P(data_axis)
    stats_spec = P(None, data_axis)
    wrapped = shard_map(
        superstep,
        mesh=mesh,
        in_specs=(P(), P(), carry_spec, P(), P(), P(), P(), P()),
        out_specs=(P(), P(), carry_spec, P(), P(), stats_spec),
    )
    return jax.jit(wrapped, donate_argnums=(1,))
