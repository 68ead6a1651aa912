"""SAC agent (reference: sheeprl/algos/sac/agent.py:20-373).

flax re-design: the critic ensemble is a single ``SACCritic`` module with
**vmapped stacked params** — the TPU-native replacement for the reference's
per-critic ``nn.ModuleList`` loop (agent.py:248-253); all ensemble members
evaluate in one batched matmul on the MXU. Target critics are a stacked
params copy updated by a jitted EMA.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import flax.linen as nn
import gymnasium
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.parallel.fabric import HostPlayerParams, put_tree

Array = jax.Array

LOG_STD_MAX = 2.0
LOG_STD_MIN = -5.0


class SACCritic(nn.Module):
    """Q(s, a) MLP (reference agent.py:20-54); ensemble via vmapped params."""

    hidden_size: int = 256
    num_critics: int = 1
    dropout: float = 0.0  # used by DroQ
    layer_norm: bool = False  # used by DroQ
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, obs: Array, action: Array, deterministic: bool = True) -> Array:
        x = jnp.concatenate([obs, action], axis=-1).astype(self.dtype)
        for _ in range(2):
            x = nn.Dense(self.hidden_size, dtype=self.dtype, param_dtype=jnp.float32)(x)
            if self.dropout > 0.0:
                x = nn.Dropout(rate=self.dropout)(x, deterministic=deterministic)
            if self.layer_norm:
                x = nn.LayerNorm(dtype=jnp.float32)(x.astype(jnp.float32)).astype(self.dtype)
            x = nn.relu(x)
        return nn.Dense(self.num_critics, dtype=jnp.float32, param_dtype=jnp.float32)(x)


class SACActor(nn.Module):
    """Tanh-squashed Gaussian policy (reference agent.py:57-142)."""

    action_dim: int
    hidden_size: int = 256
    action_low: Tuple[float, ...] = (-1.0,)
    action_high: Tuple[float, ...] = (1.0,)
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, obs: Array) -> Tuple[Array, Array]:
        x = obs.astype(self.dtype)
        for _ in range(2):
            x = nn.Dense(self.hidden_size, dtype=self.dtype, param_dtype=jnp.float32)(x)
            x = nn.relu(x)
        mean = nn.Dense(self.action_dim, dtype=jnp.float32, param_dtype=jnp.float32, name="fc_mean")(x)
        log_std = nn.Dense(self.action_dim, dtype=jnp.float32, param_dtype=jnp.float32, name="fc_logstd")(x)
        return mean, log_std

    @property
    def action_scale(self) -> Array:
        return (jnp.asarray(self.action_high) - jnp.asarray(self.action_low)) / 2.0

    @property
    def action_bias(self) -> Array:
        return (jnp.asarray(self.action_high) + jnp.asarray(self.action_low)) / 2.0


def actor_action_and_log_prob(
    actor: SACActor, params: Any, obs: Array, key: Array
) -> Tuple[Array, Array]:
    """rsample a squashed action and its log-prob (Eq. 26 of the SAC paper;
    reference agent.py:110-142)."""
    mean, log_std = actor.apply(params, obs)
    std = jnp.exp(jnp.clip(log_std, LOG_STD_MIN, LOG_STD_MAX))
    x_t = mean + std * jax.random.normal(key, mean.shape)
    y_t = jnp.tanh(x_t)
    scale, bias = actor.action_scale, actor.action_bias
    action = y_t * scale + bias
    # Normal log-prob minus the tanh+scale change of variables
    log_prob = -0.5 * (jnp.square((x_t - mean) / std) + 2 * jnp.log(std) + jnp.log(2 * jnp.pi))
    log_prob = log_prob - jnp.log(scale * (1 - jnp.square(y_t)) + 1e-6)
    return action, log_prob.sum(-1, keepdims=True)


def actor_greedy_action(actor: SACActor, params: Any, obs: Array) -> Array:
    mean, _ = actor.apply(params, obs)
    return jnp.tanh(mean) * actor.action_scale + actor.action_bias


class SACAgent:
    """Host handle holding modules + param trees (reference SACAgent,
    agent.py:145-267). All numeric paths are pure functions over the trees."""

    def __init__(
        self,
        actor: SACActor,
        critic: SACCritic,
        actor_params: Any,
        critic_params: Any,  # stacked [n_critics, ...]
        target_entropy: float,
        alpha: float = 1.0,
        tau: float = 0.005,
        num_critics: int = 2,
    ) -> None:
        self.actor = actor
        self.critic = critic
        self.actor_params = actor_params
        self.critic_params = critic_params
        self.target_critic_params = jax.tree.map(jnp.copy, critic_params)
        self.log_alpha = jnp.log(jnp.asarray([alpha], jnp.float32))
        self.target_entropy = float(target_entropy)
        self.tau = float(tau)
        self.num_critics = num_critics

    @property
    def alpha(self) -> float:
        return float(jnp.exp(self.log_alpha)[0])


def critic_ensemble_apply(critic: SACCritic, stacked_params: Any, obs: Array, action: Array) -> Array:
    """[n_critics, B, 1] -> [B, n_critics] Q-values in one vmapped call."""
    qs = jax.vmap(lambda p: critic.apply(p, obs, action))(stacked_params)
    return jnp.moveaxis(qs[..., 0], 0, -1)


class SACPlayer(HostPlayerParams):
    """Rollout/eval policy handle (reference SACPlayer, agent.py:270-314).

    ``device`` optionally pins inference to the host CPU backend
    (learner-on-accelerator/actor-on-host; see
    ``parallel.fabric.resolve_player_device``)."""

    _placed_attrs = ("params",)

    def __init__(self, actor: SACActor, params: Any, device: Optional[Any] = None) -> None:
        self.actor = actor
        self.device = device  # must precede the params assignment
        self.params = params
        self._sample = jax.jit(lambda p, o, k: actor_action_and_log_prob(actor, p, o, k)[0])
        self._greedy = jax.jit(lambda p, o: actor_greedy_action(actor, p, o))

    def update_params(self, params: Any) -> None:
        """Per-train-block refresh: non-blocking in host-player mode (the
        SAC family is off-policy — a block or two of param staleness is the
        standard actor-learner lag; see ``fabric.HostPlayerParams.stream_attr``)."""
        self.stream_attr("params", params)

    def get_actions(self, obs: Array, key: Optional[Array] = None, greedy: bool = False) -> np.ndarray:
        self.poll_stream_attrs()
        if greedy:
            return np.asarray(self._greedy(self.params, obs))
        return np.asarray(self._sample(self.params, obs, put_tree(key, self.device)))


def finite_action_bounds(action_space: gymnasium.spaces.Box) -> Tuple[Tuple[float, ...], Tuple[float, ...]]:
    """Per-dimension (low, high) with non-finite bounds clamped to ±1: an
    unbounded Box means "no rescale", and a literal ``inf`` scale would turn
    the tanh-squashed action (and every loss downstream) into NaN."""
    low = np.asarray(action_space.low, np.float32).ravel()
    high = np.asarray(action_space.high, np.float32).ravel()
    unbounded = ~(np.isfinite(low) & np.isfinite(high))
    low = np.where(unbounded, -1.0, low).astype(np.float32)
    high = np.where(unbounded, 1.0, high).astype(np.float32)
    return tuple(low.tolist()), tuple(high.tolist())


def build_agent(
    fabric: Any,
    cfg: Dict[str, Any],
    obs_space: gymnasium.spaces.Dict,
    action_space: gymnasium.spaces.Box,
    agent_state: Optional[Dict[str, Any]] = None,
    critic_cls: type = SACCritic,
    critic_kwargs: Optional[Dict[str, Any]] = None,
) -> Tuple[SACAgent, SACPlayer]:
    act_dim = int(np.prod(action_space.shape))
    obs_dim = int(sum(np.prod(obs_space[k].shape) for k in cfg["algo"]["mlp_keys"]["encoder"]))
    dtype = fabric.precision.compute_dtype

    action_low, action_high = finite_action_bounds(action_space)
    actor = SACActor(
        action_dim=act_dim,
        hidden_size=int(cfg["algo"]["actor"]["hidden_size"]),
        action_low=action_low,
        action_high=action_high,
        dtype=dtype,
    )
    n_critics = int(cfg["algo"]["critic"]["n"])
    critic = critic_cls(
        hidden_size=int(cfg["algo"]["critic"]["hidden_size"]),
        num_critics=1,
        dtype=dtype,
        **(critic_kwargs or {}),
    )

    key = jax.random.PRNGKey(int(cfg["seed"]))
    k_actor, *k_critics = jax.random.split(key, n_critics + 1)
    dummy_obs = jnp.zeros((1, obs_dim), jnp.float32)
    dummy_act = jnp.zeros((1, act_dim), jnp.float32)

    if agent_state is not None:
        actor_params = jax.tree.map(jnp.asarray, agent_state["actor"])
        critic_params = jax.tree.map(jnp.asarray, agent_state["critics"])
        agent = SACAgent(
            actor,
            critic,
            fabric.replicate(actor_params),
            fabric.replicate(critic_params),
            target_entropy=-act_dim,
            alpha=float(cfg["algo"]["alpha"]["alpha"]),
            tau=float(cfg["algo"]["tau"]),
            num_critics=n_critics,
        )
        agent.target_critic_params = fabric.replicate(jax.tree.map(jnp.asarray, agent_state["target_critics"]))
        agent.log_alpha = fabric.replicate(jnp.asarray(agent_state["log_alpha"]))
    else:
        actor_params = actor.init(k_actor, dummy_obs)
        critic_params = jax.vmap(lambda k: critic.init(k, dummy_obs, dummy_act))(jnp.stack(k_critics))
        agent = SACAgent(
            actor,
            critic,
            fabric.replicate(actor_params),
            fabric.replicate(critic_params),
            target_entropy=-act_dim,
            alpha=float(cfg["algo"]["alpha"]["alpha"]),
            tau=float(cfg["algo"]["tau"]),
            num_critics=n_critics,
        )
        agent.target_critic_params = fabric.replicate(agent.target_critic_params)
    from sheeprl_tpu.parallel.fabric import resolve_player_device

    player = SACPlayer(
        actor,
        agent.actor_params,
        device=resolve_player_device(cfg["algo"].get("player_device", "auto")),
    )
    return agent, player
