"""PPO agent (reference: sheeprl/algos/ppo/agent.py:19-298).

flax re-design: one ``PPOAgent`` module whose params are a single pytree.
The reference's separate DDP-wrapped trainer and single-device player
(agent.py:254-298, weight tying at :292-297) collapse into "the same params
used by two jitted functions" — replication across the mesh *is* the weight
tying. Pixel inputs are NHWC uint8 and are normalized to [-0.5, 0.5] inside
the module, so only bytes cross PCIe.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import flax.linen as nn
import gymnasium
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.models import MLP, NatureCNN
from sheeprl_tpu.ops.distributions import Categorical, Independent, Normal
from sheeprl_tpu.parallel.fabric import HostPlayerParams, put_tree

Array = jax.Array


class CNNEncoder(nn.Module):
    """Concat pixel keys on channels -> NatureCNN (reference agent.py:19-35)."""

    keys: Tuple[str, ...]
    features_dim: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, obs: Dict[str, Array]) -> Array:
        imgs = [obs[k].astype(self.dtype) / 255.0 - 0.5 for k in self.keys]
        x = jnp.concatenate(imgs, axis=-1)
        return NatureCNN(features_dim=self.features_dim, dtype=self.dtype)(x)


class MLPEncoder(nn.Module):
    """Concat vector keys -> MLP (reference agent.py:38-64)."""

    keys: Tuple[str, ...]
    features_dim: Optional[int]
    dense_units: int = 64
    mlp_layers: int = 2
    dense_act: str = "relu"
    layer_norm: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, obs: Dict[str, Array]) -> Array:
        x = jnp.concatenate([obs[k].astype(self.dtype) for k in self.keys], axis=-1)
        return MLP(
            hidden_sizes=(self.dense_units,) * self.mlp_layers,
            output_dim=self.features_dim,
            activation=self.dense_act,
            norm_layer="layer_norm" if self.layer_norm else None,
            dtype=self.dtype,
        )(x)


class PPOAgent(nn.Module):
    """Shared encoder, actor backbone + per-space heads, critic
    (reference agent.py:79-152). ``__call__`` returns raw head outputs; the
    sampling/log-prob math lives in :func:`evaluate_actions` /
    :func:`sample_actions` so the same module serves training and play."""

    actions_dim: Tuple[int, ...]
    is_continuous: bool
    cnn_keys: Tuple[str, ...]
    mlp_keys: Tuple[str, ...]
    cnn_features_dim: int = 512
    mlp_features_dim: Optional[int] = 64
    encoder_units: int = 64
    encoder_layers: int = 2
    actor_units: int = 64
    actor_layers: int = 2
    critic_units: int = 64
    critic_layers: int = 2
    dense_act: str = "tanh"
    layer_norm: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, obs: Dict[str, Array]) -> Tuple[List[Array], Array]:
        feats = []
        if self.cnn_keys:
            feats.append(CNNEncoder(self.cnn_keys, self.cnn_features_dim, dtype=self.dtype)(obs))
        if self.mlp_keys:
            feats.append(
                MLPEncoder(
                    self.mlp_keys,
                    self.mlp_features_dim,
                    self.encoder_units,
                    self.encoder_layers,
                    self.dense_act,
                    self.layer_norm,
                    dtype=self.dtype,
                )(obs)
            )
        feat = feats[0] if len(feats) == 1 else jnp.concatenate(feats, axis=-1)

        critic = MLP(
            hidden_sizes=(self.critic_units,) * self.critic_layers,
            output_dim=1,
            activation=self.dense_act,
            norm_layer="layer_norm" if self.layer_norm else None,
            dtype=self.dtype,
            name="critic",
        )(feat)

        x = MLP(
            hidden_sizes=(self.actor_units,) * self.actor_layers,
            output_dim=None,
            activation=self.dense_act,
            norm_layer="layer_norm" if self.layer_norm else None,
            dtype=self.dtype,
            name="actor_backbone",
        )(feat)
        if self.is_continuous:
            # single head emitting mean ++ log_std (reference agent.py:148-149)
            heads = [nn.Dense(sum(self.actions_dim) * 2, dtype=self.dtype, name="actor_head_0")(x)]
        else:
            heads = [
                nn.Dense(d, dtype=self.dtype, name=f"actor_head_{i}")(x) for i, d in enumerate(self.actions_dim)
            ]
        return heads, critic.astype(jnp.float32)


def _dists(agent: PPOAgent, actor_out: List[Array]):
    if agent.is_continuous:
        mean, log_std = jnp.split(actor_out[0].astype(jnp.float32), 2, axis=-1)
        return [Independent(Normal(mean, jnp.exp(log_std)), 1)]
    return [Categorical(logits=h.astype(jnp.float32)) for h in actor_out]


def sample_actions(
    agent: PPOAgent,
    params: Any,
    obs: Dict[str, Array],
    key: Array,
    greedy: bool = False,
) -> Tuple[Array, Array, Array]:
    """Rollout-time policy (reference PPOPlayer.forward, agent.py:201-224).

    Returns ``(actions, logprobs[B,1], values[B,1])`` where ``actions`` is
    the concatenated one-hot (discrete) or raw (continuous) action vector —
    the buffer layout the reference stores.
    """
    actor_out, values = agent.apply(params, obs)
    dists = _dists(agent, actor_out)
    keys = jax.random.split(key, len(dists))
    if agent.is_continuous:
        d = dists[0]
        act = d.mode if greedy else d.sample(seed=keys[0])
        logprob = d.log_prob(act)[..., None]
        return act, logprob, values
    samples = [
        (d.mode if greedy else d.sample(seed=k)) for d, k in zip(dists, keys)
    ]  # integer class indices per sub-space
    logprob = sum(d.log_prob(s) for d, s in zip(dists, samples))[..., None]
    onehots = [jax.nn.one_hot(s, dim, dtype=jnp.float32) for s, dim in zip(samples, agent.actions_dim)]
    return jnp.concatenate(onehots, axis=-1), logprob, values


def evaluate_actions(
    agent: PPOAgent,
    params: Any,
    obs: Dict[str, Array],
    actions: Array,
) -> Tuple[Array, Array, Array]:
    """Train-time re-evaluation of stored actions (reference
    PPOAgent.forward with actions, agent.py:154-191). Returns
    ``(logprobs[B,1], entropy[B,1], values[B,1])``."""
    actor_out, values = agent.apply(params, obs)
    dists = _dists(agent, actor_out)
    if agent.is_continuous:
        d = dists[0]
        return d.log_prob(actions)[..., None], d.entropy()[..., None], values
    splits = np.cumsum(agent.actions_dim)[:-1]
    onehot_parts = jnp.split(actions, splits, axis=-1)
    idx_parts = [jnp.argmax(p, axis=-1) for p in onehot_parts]
    logprob = sum(d.log_prob(i) for d, i in zip(dists, idx_parts))[..., None]
    entropy = sum(d.entropy() for d in dists)[..., None]
    return logprob, entropy, values


def real_actions_from_onehot(actions_dim: Sequence[int], is_continuous: bool, actions: Array) -> Array:
    """Concatenated one-hot action vector → per-part env indices (identity
    for continuous) — the in-graph twin of the host-side conversion every
    rollout used to pay in numpy."""
    if is_continuous:
        return actions
    splits = np.cumsum(np.asarray(actions_dim))[:-1].tolist()
    parts = jnp.split(actions, splits, axis=-1)
    return jnp.stack([p.argmax(-1) for p in parts], axis=-1)


def rollout_step(agent: PPOAgent, params: Any, obs: Dict[str, Array], key: Array):
    """One fused rollout-time policy call: sample + the one-hot→index
    conversion the env needs, in a single XLA program. On a 1-core host the
    per-step budget is milliseconds, so the separate dispatches the naive
    loop pays (key split, sample, numpy argmax/split per action part) are a
    measurable fraction of the whole rollout — this folds them into one."""
    actions, logprob, values = sample_actions(agent, params, obs, key)
    real_actions = real_actions_from_onehot(agent.actions_dim, agent.is_continuous, actions)
    return actions, real_actions, logprob, values


class PPOPlayer(HostPlayerParams):
    """Host-side convenience handle for rollout/eval: module + params with
    jitted action/value functions (reference PPOPlayer, agent.py:194-251).

    ``device`` optionally pins inference to the host CPU backend so env
    stepping never waits on an accelerator round trip; ``update_params``
    streams learner params across (see ``parallel.fabric.resolve_player_device``)."""

    _placed_attrs = ("params",)

    def __init__(self, agent: PPOAgent, params: Any, device: Optional[Any] = None) -> None:
        self.agent = agent
        self.device = device  # must precede the params assignment
        self.params = params
        self._sample = jax.jit(
            lambda p, o, k, greedy: sample_actions(agent, p, o, k, greedy), static_argnames="greedy"
        )
        self._values = jax.jit(lambda p, o: agent.apply(p, o)[1])
        # fused rollout step: key folding (counter -> fresh stream, no host
        # split dispatch) + sample + real-action conversion in one program
        self._rollout = jax.jit(
            lambda p, o, k, c: rollout_step(agent, p, o, jax.random.fold_in(k, c))
        )

    def update_params(self, params: Any) -> None:
        self.params = params

    def get_actions(self, obs: Dict[str, Array], key: Array, greedy: bool = False):
        return self._sample(self.params, obs, put_tree(key, self.device), greedy)

    def rollout_actions(self, obs: Dict[str, Array], key: Array, counter) -> Any:
        """(actions, real_actions, logprobs, values) for one env step; the
        per-step stream is ``fold_in(key, counter)`` so the base key crosses
        to the player device once per update, not once per step."""
        return self._rollout(self.params, obs, key, counter)

    def get_values(self, obs: Dict[str, Array]) -> Array:
        return self._values(self.params, obs)


def build_agent(
    fabric: Any,
    actions_dim: Sequence[int],
    is_continuous: bool,
    cfg: Dict[str, Any],
    obs_space: gymnasium.spaces.Dict,
    agent_state: Optional[Any] = None,
) -> Tuple[PPOAgent, Any]:
    """Construct the module and init/replicate its params
    (reference build_agent, agent.py:254-298). Returns ``(agent, params)``;
    the caller wraps params in a train state and/or a PPOPlayer — both see
    the same pytree, which is the weight tying of agent.py:292-297."""
    algo = cfg["algo"]
    agent = PPOAgent(
        actions_dim=tuple(int(d) for d in actions_dim),
        is_continuous=bool(is_continuous),
        cnn_keys=tuple(algo["cnn_keys"]["encoder"]),
        mlp_keys=tuple(algo["mlp_keys"]["encoder"]),
        cnn_features_dim=int(algo["encoder"]["cnn_features_dim"]),
        mlp_features_dim=algo["encoder"]["mlp_features_dim"],
        encoder_units=int(algo["encoder"]["dense_units"]),
        encoder_layers=int(algo["encoder"]["mlp_layers"]),
        actor_units=int(algo["actor"]["dense_units"]),
        actor_layers=int(algo["actor"]["mlp_layers"]),
        critic_units=int(algo["critic"]["dense_units"]),
        critic_layers=int(algo["critic"]["mlp_layers"]),
        dense_act=str(algo["dense_act"]),
        layer_norm=bool(algo["layer_norm"]),
        dtype=fabric.precision.compute_dtype,
    )
    if agent_state is not None:
        params = jax.tree.map(jnp.asarray, agent_state)
    else:
        dummy_obs = {}
        for k in agent.cnn_keys:
            shape = obs_space[k].shape  # [S,H,W,C] (stacked) or [H,W,C]
            if len(shape) == 4:
                s, h, w, c = shape
                shape = (h, w, s * c)
            dummy_obs[k] = jnp.zeros((1, *shape), dtype=jnp.uint8)
        for k in agent.mlp_keys:
            dummy_obs[k] = jnp.zeros((1, *obs_space[k].shape), dtype=jnp.float32)
        params = agent.init(jax.random.PRNGKey(int(cfg["seed"])), dummy_obs)
    params = jax.tree.map(lambda x: x.astype(fabric.precision.param_dtype), params)
    return agent, fabric.replicate(params)
