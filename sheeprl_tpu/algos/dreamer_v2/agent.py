"""Dreamer-V2 agent (reference: sheeprl/algos/dreamer_v2/agent.py:40-1104).

flax re-design, TPU-first, sharing the DV3 layout of this repo
(``algos/dreamer_v3/agent.py``): one ``WorldModel`` param tree (the
reference's WorldModel container, agent.py:707-732), an Actor tree and a
critic tree. Differences from the Dreamer-V3 agent that this module encodes:

- ELU activations and *optional* LayerNorm everywhere (reference config
  ``layer_norm: False`` — DV3 always LN+SiLU),
- VALID-padded conv stacks: encoder 4x(k4 s2) from 64x64 -> 2x2, decoder
  1x1 seed -> k5,k5,k6,k6 s2 transposed convs back to 64x64
  (reference agent.py:62-76, 166-186),
- no unimix on the categorical logits,
- scalar Normal(mean, 1) reward head (no two-hot) and an *optional*
  continue model (``use_continues``),
- zero (non-learnable) initial RSSM states, gated by ``is_first``
  (reference RSSM.dynamic, agent.py:380-385),
- trunc_normal continuous actor with exploration-noise support
  (reference Actor, agent.py:417-560).

All sequence loops are ``lax.scan``; images are NHWC uint8 normalized
in-graph.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import flax.linen as nn
import gymnasium
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.models import LayerNormGRUCell
from sheeprl_tpu.models.blocks import LayerNorm, get_activation
from sheeprl_tpu.ops.distributions import (
    Independent,
    Normal,
    OneHotCategoricalStraightThrough,
    TanhNormal,
    TruncatedNormal,
)
from sheeprl_tpu.parallel.fabric import HostPlayerParams, put_tree, resolve_player_device

Array = jax.Array

xavier_init = nn.initializers.xavier_normal()


def _dense(units: int, dtype: Any, name: Optional[str] = None) -> nn.Dense:
    return nn.Dense(units, dtype=dtype, param_dtype=jnp.float32, kernel_init=xavier_init, name=name)


class _MLPBlock(nn.Module):
    """Dense -> (LayerNorm) -> act, repeated — the DV1/DV2 block shape."""

    layers: int
    units: int
    act: str = "elu"
    use_layer_norm: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: Array) -> Array:
        act = get_activation(self.act)
        for _ in range(self.layers):
            x = _dense(self.units, self.dtype)(x)
            if self.use_layer_norm:
                x = LayerNorm()(x)
            x = act(x)
        return x


class CNNEncoderDV2(nn.Module):
    """4-stage VALID k4 s2 conv encoder (reference agent.py:62-76):
    channels ``[1,2,4,8]*multiplier``, for 64x64 inputs the output is
    ``2*2*8*multiplier`` features."""

    keys: Tuple[str, ...]
    channels_multiplier: int
    act: str = "elu"
    use_layer_norm: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, obs: Dict[str, Array]) -> Array:
        act = get_activation(self.act)
        x = jnp.concatenate([obs[k].astype(self.dtype) / 255.0 - 0.5 for k in self.keys], axis=-1)
        for i in range(4):
            x = nn.Conv(
                (2**i) * self.channels_multiplier,
                kernel_size=(4, 4),
                strides=(2, 2),
                padding="VALID",
                dtype=self.dtype,
                param_dtype=jnp.float32,
                kernel_init=xavier_init,
            )(x)
            if self.use_layer_norm:
                x = LayerNorm()(x)
            x = act(x)
        return x.reshape(*x.shape[:-3], -1)


class CNNDecoderDV2(nn.Module):
    """Inverse of :class:`CNNEncoderDV2` (reference agent.py:131-195):
    Dense(latent -> encoder_output_dim), 1x1 seed, then transposed convs
    k5,k5,k6,k6 stride 2 VALID back to 64x64. Returns normalized-pixel
    reconstructions per key."""

    keys: Tuple[str, ...]
    output_channels: Tuple[int, ...]
    channels_multiplier: int
    cnn_encoder_output_dim: int
    image_size: Tuple[int, int]
    act: str = "elu"
    use_layer_norm: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, latent: Array) -> Dict[str, Array]:
        act = get_activation(self.act)
        lead = latent.shape[:-1]
        x = _dense(self.cnn_encoder_output_dim, self.dtype)(latent)
        x = x.reshape(-1, 1, 1, self.cnn_encoder_output_dim)
        channels = [4 * self.channels_multiplier, 2 * self.channels_multiplier, self.channels_multiplier]
        kernels = [5, 5, 6, 6]
        for i, ch in enumerate(channels):
            x = nn.ConvTranspose(
                ch,
                kernel_size=(kernels[i], kernels[i]),
                strides=(2, 2),
                padding="VALID",
                dtype=self.dtype,
                param_dtype=jnp.float32,
                kernel_init=xavier_init,
            )(x)
            if self.use_layer_norm:
                x = LayerNorm()(x)
            x = act(x)
        x = nn.ConvTranspose(
            sum(self.output_channels),
            kernel_size=(kernels[-1], kernels[-1]),
            strides=(2, 2),
            padding="VALID",
            dtype=self.dtype,
            param_dtype=jnp.float32,
            kernel_init=xavier_init,
        )(x)
        x = x.reshape(*lead, *self.image_size, sum(self.output_channels)).astype(jnp.float32)
        splits = np.cumsum(self.output_channels)[:-1]
        return {k: part for k, part in zip(self.keys, jnp.split(x, splits, axis=-1))}


class MLPEncoderDV2(nn.Module):
    """N x (Dense + optional LN + act) over concatenated vector obs
    (reference agent.py:83-129; no symlog in DV2)."""

    keys: Tuple[str, ...]
    mlp_layers: int = 4
    dense_units: int = 400
    act: str = "elu"
    use_layer_norm: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, obs: Dict[str, Array]) -> Array:
        x = jnp.concatenate([obs[k].astype(jnp.float32) for k in self.keys], axis=-1)
        return _MLPBlock(self.mlp_layers, self.dense_units, self.act, self.use_layer_norm, self.dtype)(
            x.astype(self.dtype)
        )


class MLPDecoderDV2(nn.Module):
    """Trunk + per-key linear heads (reference agent.py:198-246)."""

    keys: Tuple[str, ...]
    output_dims: Tuple[int, ...]
    mlp_layers: int = 4
    dense_units: int = 400
    act: str = "elu"
    use_layer_norm: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, latent: Array) -> Dict[str, Array]:
        x = _MLPBlock(self.mlp_layers, self.dense_units, self.act, self.use_layer_norm, self.dtype)(
            latent.astype(self.dtype)
        )
        return {
            k: _dense(d, self.dtype, name=f"head_{k}")(x).astype(jnp.float32)
            for k, d in zip(self.keys, self.output_dims)
        }


class RecurrentModelDV2(nn.Module):
    """Dense(+LN)+act projection then LayerNorm-GRU (reference
    agent.py:249-298). ``gru_layer_norm`` mirrors
    ``world_model.recurrent_model.layer_norm`` (True by default in DV2)."""

    recurrent_state_size: int
    dense_units: int
    act: str = "elu"
    mlp_layer_norm: bool = False
    gru_layer_norm: bool = True
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: Array, h: Array) -> Array:
        feat = _MLPBlock(1, self.dense_units, self.act, self.mlp_layer_norm, self.dtype)(x)
        new_h, _ = LayerNormGRUCell(
            self.recurrent_state_size, bias=True, layer_norm=self.gru_layer_norm, dtype=self.dtype
        )(h.astype(self.dtype), feat)
        return new_h.astype(jnp.float32)


def compute_stochastic_state(logits: Array, key: Optional[Array], sample: bool = True) -> Array:
    """Straight-through sample (or mode) of the ``[..., S, D]`` categorical,
    flattened to ``[..., S*D]`` (reference dreamer_v2/utils.py:44-60 — no
    unimix in DV2)."""
    dist = Independent(OneHotCategoricalStraightThrough(logits=logits), 1)
    state = dist.rsample(seed=key) if sample else dist.mode
    return state.reshape(*state.shape[:-2], -1)


class WorldModelDV2(nn.Module):
    """Encoder + RSSM + decoders + reward (+ optional continue) in one param
    tree (reference WorldModel container agent.py:707-732 and RSSM
    agent.py:300-415). Methods are ``apply(..., method=...)`` entry points."""

    cnn_keys: Tuple[str, ...]
    mlp_keys: Tuple[str, ...]
    cnn_output_channels: Tuple[int, ...]
    mlp_output_dims: Tuple[int, ...]
    image_size: Tuple[int, int]
    actions_dim: Tuple[int, ...]
    stochastic_size: int = 32
    discrete_size: int = 32
    recurrent_state_size: int = 600
    recurrent_dense_units: int = 400
    gru_layer_norm: bool = True
    encoder_cnn_multiplier: int = 48
    encoder_mlp_layers: int = 4
    encoder_dense_units: int = 400
    decoder_cnn_multiplier: int = 48
    decoder_mlp_layers: int = 4
    decoder_dense_units: int = 400
    representation_hidden_size: int = 600
    transition_hidden_size: int = 600
    reward_layers: int = 4
    reward_dense_units: int = 400
    use_continues: bool = False
    continue_layers: int = 4
    continue_dense_units: int = 400
    dense_act: str = "elu"
    cnn_act: str = "elu"
    layer_norm: bool = False
    dtype: Any = jnp.float32

    @property
    def stoch_state_size(self) -> int:
        return self.stochastic_size * self.discrete_size

    @property
    def latent_state_size(self) -> int:
        return self.stoch_state_size + self.recurrent_state_size

    @property
    def cnn_encoder_output_dim(self) -> int:
        # 4 VALID k4 s2 stages: 64 -> 31 -> 14 -> 6 -> 2
        hw = self.image_size[0]
        for _ in range(4):
            hw = (hw - 4) // 2 + 1
        return hw * hw * 8 * self.encoder_cnn_multiplier

    def setup(self) -> None:
        if self.cnn_keys:
            self.cnn_encoder = CNNEncoderDV2(
                self.cnn_keys, self.encoder_cnn_multiplier, self.cnn_act, self.layer_norm, self.dtype
            )
            self.cnn_decoder = CNNDecoderDV2(
                self.cnn_keys,
                self.cnn_output_channels,
                self.decoder_cnn_multiplier,
                self.cnn_encoder_output_dim,
                self.image_size,
                self.cnn_act,
                self.layer_norm,
                self.dtype,
            )
        if self.mlp_keys:
            self.mlp_encoder = MLPEncoderDV2(
                self.mlp_keys,
                self.encoder_mlp_layers,
                self.encoder_dense_units,
                self.dense_act,
                self.layer_norm,
                self.dtype,
            )
            self.mlp_decoder = MLPDecoderDV2(
                self.mlp_keys,
                self.mlp_output_dims,
                self.decoder_mlp_layers,
                self.decoder_dense_units,
                self.dense_act,
                self.layer_norm,
                self.dtype,
            )
        self.recurrent_model = RecurrentModelDV2(
            self.recurrent_state_size,
            self.recurrent_dense_units,
            self.dense_act,
            False,
            self.gru_layer_norm,
            self.dtype,
        )
        self.representation_model = nn.Sequential(
            [
                _MLPBlock(1, self.representation_hidden_size, self.dense_act, self.layer_norm, self.dtype),
                _dense(self.stoch_state_size, jnp.float32),
            ]
        )
        self.transition_model = nn.Sequential(
            [
                _MLPBlock(1, self.transition_hidden_size, self.dense_act, self.layer_norm, self.dtype),
                _dense(self.stoch_state_size, jnp.float32),
            ]
        )
        self.reward_model = nn.Sequential(
            [
                _MLPBlock(self.reward_layers, self.reward_dense_units, self.dense_act, self.layer_norm, self.dtype),
                _dense(1, jnp.float32),
            ]
        )
        if self.use_continues:
            self.continue_model = nn.Sequential(
                [
                    _MLPBlock(
                        self.continue_layers, self.continue_dense_units, self.dense_act, self.layer_norm, self.dtype
                    ),
                    _dense(1, jnp.float32),
                ]
            )

    # ------------------------------------------------------------------ #
    # entry points
    # ------------------------------------------------------------------ #
    def encode(self, obs: Dict[str, Array]) -> Array:
        feats = []
        if self.cnn_keys:
            feats.append(self.cnn_encoder(obs))
        if self.mlp_keys:
            feats.append(self.mlp_encoder(obs))
        out = feats[0] if len(feats) == 1 else jnp.concatenate(feats, axis=-1)
        return out.astype(jnp.float32)

    def decode(self, latent: Array) -> Dict[str, Array]:
        out: Dict[str, Array] = {}
        if self.cnn_keys:
            out.update(self.cnn_decoder(latent.astype(self.dtype)))
        if self.mlp_keys:
            out.update(self.mlp_decoder(latent.astype(self.dtype)))
        return out

    def reward_mean(self, latent: Array) -> Array:
        return self.reward_model(latent.astype(self.dtype))

    def continue_logits(self, latent: Array) -> Array:
        return self.continue_model(latent.astype(self.dtype))

    def _stoch_logits(self, logits: Array) -> Array:
        return logits.reshape(*logits.shape[:-1], self.stochastic_size, self.discrete_size)

    def dynamic(
        self,
        z: Array,
        h: Array,
        action: Array,
        embedded: Array,
        is_first: Array,
        key: Array,
    ) -> Tuple[Array, Array, Array, Array]:
        """One posterior step (reference RSSM.dynamic, agent.py:334-385):
        zero initial states gated by ``is_first``; returns
        ``(h', z'_flat, posterior_logits, prior_logits)`` with logits
        ``[B, S, D]``."""
        action = (1 - is_first) * action
        z = (1 - is_first) * z
        h = (1 - is_first) * h
        h = self.recurrent_model(jnp.concatenate([z, action], axis=-1).astype(self.dtype), h)
        prior_logits = self._stoch_logits(self.transition_model(h.astype(self.dtype)))
        post_in = jnp.concatenate([h, embedded], axis=-1)
        post_logits = self._stoch_logits(self.representation_model(post_in.astype(self.dtype)))
        z = compute_stochastic_state(post_logits, key)
        return h, z, post_logits, prior_logits

    def imagination(self, z: Array, h: Array, action: Array, key: Array) -> Tuple[Array, Array]:
        """One prior step in latent space (reference RSSM.imagination,
        agent.py:397-414)."""
        h = self.recurrent_model(jnp.concatenate([z, action], axis=-1).astype(self.dtype), h)
        prior_logits = self._stoch_logits(self.transition_model(h.astype(self.dtype)))
        z = compute_stochastic_state(prior_logits, key)
        return z, h

    def observe_step(self, z, h, action, obs, key):
        """Policy-time posterior update (reference PlayerDV2.get_actions,
        agent.py:823-852)."""
        embedded = self.encode(obs)
        h = self.recurrent_model(jnp.concatenate([z, action], axis=-1).astype(self.dtype), h)
        post_in = jnp.concatenate([h, embedded], axis=-1)
        post_logits = self._stoch_logits(self.representation_model(post_in.astype(self.dtype)))
        z = compute_stochastic_state(post_logits, key)
        return z, h


def rssm_scan(
    wm: WorldModelDV2,
    params: Any,
    embedded: Array,  # [T, B, E]
    actions: Array,  # [T, B, A] (already shifted)
    is_first: Array,  # [T, B, 1]
    key: Array,
) -> Tuple[Array, Array, Array, Array]:
    """The DV2 RSSM sequence as one ``lax.scan`` (replaces the reference's
    Python loop, dreamer_v2.py:148-158). Returns time-major
    ``(recurrent_states, posteriors, posterior_logits, prior_logits)``."""
    B = embedded.shape[1]
    h = jnp.zeros((B, wm.recurrent_state_size), jnp.float32)
    z = jnp.zeros((B, wm.stoch_state_size), jnp.float32)

    def step(carry, xs):
        h, z, key = carry
        emb_t, act_t, first_t = xs
        key, sub = jax.random.split(key)
        h, z, post_logits, prior_logits = wm.apply(
            params, z, h, act_t, emb_t, first_t, sub, method=WorldModelDV2.dynamic
        )
        return (h, z, key), (h, z, post_logits, prior_logits)

    (_, _, _), (hs, zs, post_logits, prior_logits) = jax.lax.scan(step, (h, z, key), (embedded, actions, is_first))
    return hs, zs, post_logits, prior_logits


class ActorDV2(nn.Module):
    """Dreamer-V2 actor (reference agent.py:417-560): MLP trunk + heads.
    ``__call__`` returns raw head outputs; distribution math lives in
    :func:`actor_dists`. Default continuous distribution is trunc_normal."""

    latent_state_size: int
    actions_dim: Tuple[int, ...]
    is_continuous: bool
    distribution: str = "auto"
    init_std: float = 0.0
    min_std: float = 0.1
    dense_units: int = 400
    mlp_layers: int = 4
    act: str = "elu"
    use_layer_norm: bool = False
    expl_amount: float = 0.0
    expl_decay: float = 0.0
    expl_min: float = 0.0
    dtype: Any = jnp.float32

    def resolved_distribution(self) -> str:
        dist = self.distribution.lower()
        if dist not in ("auto", "normal", "tanh_normal", "discrete", "trunc_normal"):
            raise ValueError(f"unknown actor distribution: {dist}")
        if dist == "discrete" and self.is_continuous:
            raise ValueError("discrete distribution with continuous action space")
        if dist == "auto":
            dist = "trunc_normal" if self.is_continuous else "discrete"
        return dist

    @nn.compact
    def __call__(self, state: Array) -> List[Array]:
        x = _MLPBlock(self.mlp_layers, self.dense_units, self.act, self.use_layer_norm, self.dtype)(
            state.astype(self.dtype)
        )
        if self.is_continuous:
            return [_dense(sum(self.actions_dim) * 2, jnp.float32, name="head_0")(x)]
        return [_dense(d, jnp.float32, name=f"head_{i}")(x) for i, d in enumerate(self.actions_dim)]

    def get_expl_amount(self, step: int) -> float:
        amount = self.expl_amount
        if self.expl_decay:
            amount *= 0.5 ** (float(step) / self.expl_decay)
        return max(amount, self.expl_min)


def actor_dists(actor: ActorDV2, pre_dist: List[Array]):
    """Build action distributions from raw head outputs (reference
    Actor.forward, agent.py:506-549)."""
    dist_type = actor.resolved_distribution()
    if actor.is_continuous:
        mean, std = jnp.split(pre_dist[0], 2, axis=-1)
        if dist_type == "tanh_normal":
            mean = 5 * jnp.tanh(mean / 5)
            std = jax.nn.softplus(std + actor.init_std) + actor.min_std
            return [TanhNormal(mean, std)]
        if dist_type == "normal":
            return [Independent(Normal(mean, std), 1)]
        # trunc_normal (DV1/DV2 default)
        std = 2 * jax.nn.sigmoid((std + actor.init_std) / 2) + actor.min_std
        mean = jnp.tanh(mean)
        return [
            Independent(
                TruncatedNormal(mean, std, -jnp.ones_like(mean), jnp.ones_like(mean)), 1
            )
        ]
    return [OneHotCategoricalStraightThrough(logits=logits) for logits in pre_dist]


def sample_actor_actions(
    actor: ActorDV2, params: Any, state: Array, key: Array, greedy: bool = False
) -> Array:
    """Sample (or mode-of-100-candidates) actions; returns the concatenated
    action vector (reference Actor.forward sampling, agent.py:538-549)."""
    dists = actor_dists(actor, actor.apply(params, state))
    if actor.is_continuous:
        d = dists[0]
        if greedy:
            cand = d.sample(seed=key, sample_shape=(100,))
            logp = jax.vmap(d.log_prob)(cand)
            idx = jnp.argmax(logp, axis=0)
            return jnp.take_along_axis(cand, idx[None, ..., None], axis=0)[0]
        return d.rsample(seed=key)
    keys = jax.random.split(key, len(dists))
    parts = [(d.mode if greedy else d.rsample(seed=k)) for d, k in zip(dists, keys)]
    return jnp.concatenate(parts, axis=-1)


def actor_logprob_entropy(
    actor: ActorDV2, params: Any, states: Array, actions: Array
) -> Tuple[Array, Array]:
    """log pi(a|s) and entropy for stored (imagined) actions."""
    dists = actor_dists(actor, actor.apply(params, states))
    if actor.is_continuous:
        d = dists[0]
        try:
            ent = d.entropy()
        except NotImplementedError:
            ent = jnp.zeros(states.shape[:-1])
        return d.log_prob(actions), ent
    splits = np.cumsum(actor.actions_dim)[:-1]
    parts = jnp.split(actions, splits, axis=-1)
    logp = sum(d.log_prob(p) for d, p in zip(dists, parts))
    ent = sum(d.entropy() for d in dists)
    return logp, ent


def add_exploration_noise(
    actor: ActorDV2,
    actions: np.ndarray,
    actions_dim: Sequence[int],
    step: int,
    rng: np.random.Generator,
) -> np.ndarray:
    """Epsilon-style exploration noise on host actions (reference
    Actor.add_exploration_noise, agent.py:551-575): Gaussian jitter for
    continuous actions, uniform-resample for discrete one-hots."""
    expl_amount = actor.get_expl_amount(step)
    if expl_amount <= 0.0:
        return actions
    if actor.is_continuous:
        return np.clip(rng.normal(actions, expl_amount), -1, 1).astype(np.float32)
    out = []
    splits = np.cumsum(actions_dim)[:-1]
    for part in np.split(actions, splits, axis=-1):
        d = part.shape[-1]
        sample = np.eye(d, dtype=part.dtype)[rng.integers(0, d, part.shape[:-1])]
        mask = (rng.random(part.shape[:-1]) < expl_amount)[..., None]
        out.append(np.where(mask, sample, part))
    return np.concatenate(out, axis=-1)


class CriticDV2(nn.Module):
    """MLP critic with scalar Normal(mean, 1) head (reference build_agent,
    agent.py:1032-1055)."""

    mlp_layers: int = 4
    dense_units: int = 400
    act: str = "elu"
    use_layer_norm: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: Array) -> Array:
        x = _MLPBlock(self.mlp_layers, self.dense_units, self.act, self.use_layer_norm, self.dtype)(
            x.astype(self.dtype)
        )
        return _dense(1, jnp.float32)(x)


class PlayerDV2(HostPlayerParams):
    """Stateful env-interaction handle (reference PlayerDV2,
    agent.py:735-860): per-env (h, z, prev_action) advanced by one jitted
    observe+act step; zero initial states.

    ``device`` optionally pins the observe+act step to the host CPU backend
    (learner-on-chip/actor-on-host; see ``parallel.fabric.resolve_player_device``)."""

    _placed_attrs = ("wm_params", "actor_params")

    def __init__(
        self,
        wm: WorldModelDV2,
        wm_params: Any,
        actor: ActorDV2,
        actor_params: Any,
        actions_dim: Sequence[int],
        num_envs: int,
        seed: int = 0,
        device: Optional[Any] = None,
    ) -> None:
        self.wm = wm
        self.actor = actor
        self.device = device  # must precede the param assignments below
        self.wm_params = wm_params
        self.actor_params = actor_params
        self.actions_dim = tuple(actions_dim)
        self.num_envs = num_envs
        self.expl_rng = np.random.default_rng(seed)
        # recurrent state lives on device between steps (one less host round
        # trip per env step); exploration noise is
        # host-side, so the action still crosses to host every step
        self.h: Optional[Any] = None
        self.z: Optional[Any] = None
        self.actions: Optional[Any] = None

        def _step(wm_params, actor_params, obs, h, z, prev_action, key, greedy):
            k1, k2 = jax.random.split(key)
            # method-by-name so the same player drives any world model with an
            # ``observe_step`` entry point (DV1 reuses this class, mirroring
            # the reference's Actor aliasing in dreamer_v1/agent.py:28-29)
            z, h = wm.apply(wm_params, z, h, prev_action, obs, k1, method="observe_step")
            latent = jnp.concatenate([z, h], axis=-1)
            action = sample_actor_actions(actor, actor_params, latent, k2, greedy)
            return action, h, z

        self._step = jax.jit(_step, static_argnames="greedy")

    def init_states(self, reset_envs: Optional[Sequence[int]] = None) -> None:
        if reset_envs is None or len(reset_envs) == 0:
            # host-side zeros: uncommitted, so the jitted step pulls them
            # onto whichever backend the params live on
            self.h = np.zeros((self.num_envs, self.wm.recurrent_state_size), np.float32)
            self.z = np.zeros((self.num_envs, self.wm.stoch_state_size), np.float32)
            self.actions = np.zeros((self.num_envs, int(np.sum(self.actions_dim))), np.float32)
        else:
            mask = np.zeros((self.num_envs, 1), np.float32)
            mask[list(reset_envs)] = 1.0
            m = jnp.asarray(mask)
            self.h = jnp.where(m, 0.0, self.h)
            self.z = jnp.where(m, 0.0, self.z)
            self.actions = np.asarray(self.actions).copy()
            self.actions[list(reset_envs)] = 0.0

    def get_actions(
        self,
        obs: Dict[str, Array],
        key: Array,
        greedy: bool = False,
        expl_step: int = 0,
        with_exploration: bool = False,
    ) -> Array:
        self.poll_stream_attrs()
        action, h, z = self._step(
            self.wm_params, self.actor_params, obs, self.h, self.z, self.actions, put_tree(key, self.device), greedy
        )
        self.h, self.z = h, z
        actions = np.asarray(jax.device_get(action))
        if with_exploration:
            actions = add_exploration_noise(self.actor, actions, self.actions_dim, expl_step, self.expl_rng)
        self.actions = actions
        return self.actions


def build_agent(
    fabric: Any,
    actions_dim: Sequence[int],
    is_continuous: bool,
    cfg: Dict[str, Any],
    obs_space: gymnasium.spaces.Dict,
    world_model_state: Optional[Any] = None,
    actor_state: Optional[Any] = None,
    critic_state: Optional[Any] = None,
    target_critic_state: Optional[Any] = None,
) -> Tuple[WorldModelDV2, Any, ActorDV2, Any, Any, Any, Any, PlayerDV2]:
    """Construct modules + init/replicate params (reference build_agent,
    agent.py:863-1104). Returns the same tuple shape as the DV3 builder."""
    wm_cfg = cfg["algo"]["world_model"]
    actor_cfg = cfg["algo"]["actor"]
    cnn_keys = tuple(cfg["algo"]["cnn_keys"]["encoder"])
    mlp_keys = tuple(cfg["algo"]["mlp_keys"]["encoder"])
    compute_dtype = fabric.precision.compute_dtype
    screen = int(cfg["env"]["screen_size"])

    def _channels(k):
        shape = obs_space[k].shape
        return int(np.prod(shape[:-3]) * shape[-1]) if len(shape) >= 3 else 1

    wm = WorldModelDV2(
        cnn_keys=cnn_keys,
        mlp_keys=mlp_keys,
        cnn_output_channels=tuple(_channels(k) for k in cfg["algo"]["cnn_keys"]["decoder"]),
        mlp_output_dims=tuple(int(obs_space[k].shape[0]) for k in cfg["algo"]["mlp_keys"]["decoder"]),
        image_size=(screen, screen),
        actions_dim=tuple(actions_dim),
        stochastic_size=int(wm_cfg["stochastic_size"]),
        discrete_size=int(wm_cfg["discrete_size"]),
        recurrent_state_size=int(wm_cfg["recurrent_model"]["recurrent_state_size"]),
        recurrent_dense_units=int(wm_cfg["recurrent_model"]["dense_units"]),
        gru_layer_norm=bool(wm_cfg["recurrent_model"]["layer_norm"]),
        encoder_cnn_multiplier=int(wm_cfg["encoder"]["cnn_channels_multiplier"]),
        encoder_mlp_layers=int(wm_cfg["encoder"]["mlp_layers"]),
        encoder_dense_units=int(wm_cfg["encoder"]["dense_units"]),
        decoder_cnn_multiplier=int(wm_cfg["observation_model"]["cnn_channels_multiplier"]),
        decoder_mlp_layers=int(wm_cfg["observation_model"]["mlp_layers"]),
        decoder_dense_units=int(wm_cfg["observation_model"]["dense_units"]),
        representation_hidden_size=int(wm_cfg["representation_model"]["hidden_size"]),
        transition_hidden_size=int(wm_cfg["transition_model"]["hidden_size"]),
        reward_layers=int(wm_cfg["reward_model"]["mlp_layers"]),
        reward_dense_units=int(wm_cfg["reward_model"]["dense_units"]),
        use_continues=bool(wm_cfg["use_continues"]),
        continue_layers=int(wm_cfg["discount_model"]["mlp_layers"]),
        continue_dense_units=int(wm_cfg["discount_model"]["dense_units"]),
        dense_act=str(cfg["algo"]["dense_act"]),
        cnn_act=str(cfg["algo"]["cnn_act"]),
        layer_norm=bool(cfg["algo"]["layer_norm"]),
        dtype=compute_dtype,
    )

    actor = ActorDV2(
        latent_state_size=wm.latent_state_size,
        actions_dim=tuple(actions_dim),
        is_continuous=bool(is_continuous),
        distribution=str(cfg.get("distribution", {}).get("type", "auto")),
        init_std=float(actor_cfg["init_std"]),
        min_std=float(actor_cfg["min_std"]),
        dense_units=int(actor_cfg["dense_units"]),
        mlp_layers=int(actor_cfg["mlp_layers"]),
        act=str(actor_cfg["dense_act"]),
        use_layer_norm=bool(actor_cfg["layer_norm"]),
        expl_amount=float(actor_cfg.get("expl_amount", 0.0) or 0.0),
        expl_decay=float(actor_cfg.get("expl_decay", 0.0) or 0.0),
        expl_min=float(actor_cfg.get("expl_min", 0.0) or 0.0),
        dtype=compute_dtype,
    )
    critic_cfg = cfg["algo"]["critic"]
    critic = CriticDV2(
        mlp_layers=int(critic_cfg["mlp_layers"]),
        dense_units=int(critic_cfg["dense_units"]),
        act=str(critic_cfg["dense_act"]),
        use_layer_norm=bool(critic_cfg["layer_norm"]),
        dtype=compute_dtype,
    )

    key = jax.random.PRNGKey(int(cfg["seed"]))
    k_wm, k_actor, k_critic, k_dyn = jax.random.split(key, 4)

    B = 1
    dummy_obs = {}
    for k in cnn_keys:
        shape = obs_space[k].shape
        if len(shape) == 4:
            s, hh, ww, c = shape
            shape = (hh, ww, s * c)
        dummy_obs[k] = jnp.zeros((B, *shape), jnp.uint8)
    for k in mlp_keys:
        dummy_obs[k] = jnp.zeros((B, *obs_space[k].shape), jnp.float32)

    if world_model_state is not None:
        wm_params = jax.tree.map(jnp.asarray, world_model_state)
    else:

        def wm_init(mod: WorldModelDV2):
            emb = mod.encode(dummy_obs)
            h = jnp.zeros((B, wm.recurrent_state_size), jnp.float32)
            z = jnp.zeros((B, wm.stoch_state_size), jnp.float32)
            a = jnp.zeros((B, int(np.sum(actions_dim))), jnp.float32)
            first = jnp.ones((B, 1), jnp.float32)
            h, z, _, _ = mod.dynamic(z, h, a, emb, first, k_dyn)
            latent = jnp.concatenate([z, h], axis=-1)
            mod.decode(latent)
            mod.reward_mean(latent)
            if mod.use_continues:
                mod.continue_logits(latent)
            return ()

        wm_params = nn.init(wm_init, wm)(k_wm)

    latent = jnp.zeros((B, wm.latent_state_size), jnp.float32)
    actor_params = (
        jax.tree.map(jnp.asarray, actor_state) if actor_state is not None else actor.init(k_actor, latent)
    )
    critic_params = (
        jax.tree.map(jnp.asarray, critic_state) if critic_state is not None else critic.init(k_critic, latent)
    )
    target_critic_params = (
        jax.tree.map(jnp.asarray, target_critic_state)
        if target_critic_state is not None
        else jax.tree.map(jnp.copy, critic_params)
    )

    wm_params = fabric.replicate(wm_params)
    actor_params = fabric.replicate(actor_params)
    critic_params = fabric.replicate(critic_params)
    target_critic_params = fabric.replicate(target_critic_params)

    player = PlayerDV2(
        wm,
        wm_params,
        actor,
        actor_params,
        actions_dim,
        int(cfg["env"]["num_envs"]),
        int(cfg["seed"]),
        device=resolve_player_device(cfg["algo"].get("player_device", "auto")),
    )
    return wm, wm_params, actor, actor_params, critic, critic_params, target_critic_params, player
