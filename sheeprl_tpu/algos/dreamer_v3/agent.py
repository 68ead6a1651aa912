"""Dreamer-V3 agent (reference: sheeprl/algos/dreamer_v3/agent.py:42-1236).

flax re-design, TPU-first:

- **Three param trees** — world model, actor, critic — matching the three
  optimizers; the reference's per-submodule DDP wrapping
  (agent.py:1205-1214) and player weight tying (:1229-1235) are replaced by
  replicated pytrees shared between the jitted train step and the jitted
  policy step.
- **The RSSM time loop is a ``lax.scan``** (``rssm_scan``): the reference's
  Python loop over ``rssm.dynamic`` (dreamer_v3.py:134-145) — the #1
  compilation win on TPU (SURVEY.md §7 hard parts).
- Images are NHWC uint8 and normalized in-graph; encoder convs run bf16 on
  the MXU under the ``bf16-mixed`` policy while logits/losses stay fp32.
- Hafner init (agent.py:1170-1180) is expressed as flax initializers:
  ``variance_scaling(1.0, "fan_avg", "truncated_normal")`` for the trunk and
  ``variance_scaling(scale, "fan_avg", "uniform")`` for the special heads.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import flax.linen as nn
import gymnasium
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.models import MLP, LayerNormGRUCell
from sheeprl_tpu.models.blocks import LayerNorm
from sheeprl_tpu.ops.distributions import (
    Independent,
    Normal,
    OneHotCategoricalStraightThrough,
    TanhNormal,
)
from sheeprl_tpu.ops.hoisted_scan import hoist_plan, hoisted_scan
from sheeprl_tpu.ops.math import symlog
from sheeprl_tpu.ops.pallas_gru import fused_recurrent_step, resolve_backend
from sheeprl_tpu.parallel.fabric import HostPlayerParams, put_tree

Array = jax.Array

hafner_init = nn.initializers.variance_scaling(1.0, "fan_avg", "truncated_normal")


def uniform_init(scale: float):
    """uniform_init_weights (reference dreamer_v3/utils.py:170-182); scale 0
    degenerates to zeros (used by reward/critic heads so early returns are 0)."""
    if scale == 0.0:
        return nn.initializers.zeros_init()
    return nn.initializers.variance_scaling(scale, "fan_avg", "uniform")


def _dense(units: int, dtype: Any, name: Optional[str] = None, kernel_init=hafner_init) -> nn.Dense:
    return nn.Dense(units, dtype=dtype, param_dtype=jnp.float32, kernel_init=kernel_init, name=name)


class _LNMLP(nn.Module):
    """Dense -> LayerNorm(eps) -> act, repeated (the Dreamer-V3 block shape:
    reference MLPEncoder/agent.py:100-151 and every head trunk)."""

    layers: int
    units: int
    dtype: Any = jnp.float32
    eps: float = 1e-3
    use_layer_norm: bool = True

    @nn.compact
    def __call__(self, x: Array) -> Array:
        for _ in range(self.layers):
            x = _dense(self.units, self.dtype)(x)
            if self.use_layer_norm:
                x = LayerNorm(eps=self.eps)(x)
            x = nn.silu(x)
        return x


class CNNEncoder(nn.Module):
    """4-stage stride-2 conv encoder (reference agent.py:42-97): kernel 4,
    channels ``[1,2,4,8]*multiplier``, LayerNorm + SiLU, NHWC."""

    keys: Tuple[str, ...]
    channels_multiplier: int
    stages: int = 4
    dtype: Any = jnp.float32
    eps: float = 1e-3

    @nn.compact
    def __call__(self, obs: Dict[str, Array]) -> Array:
        x = jnp.concatenate([obs[k].astype(self.dtype) / 255.0 - 0.5 for k in self.keys], axis=-1)
        for i in range(self.stages):
            x = nn.Conv(
                (2**i) * self.channels_multiplier,
                kernel_size=(4, 4),
                strides=(2, 2),
                padding=[(1, 1), (1, 1)],
                use_bias=False,
                dtype=self.dtype,
                param_dtype=jnp.float32,
                kernel_init=hafner_init,
            )(x)
            x = LayerNorm(eps=self.eps)(x)
            x = nn.silu(x)
        return x.reshape(*x.shape[:-3], -1)


class MLPEncoder(nn.Module):
    """symlog -> N x (Dense+LN+SiLU) (reference agent.py:100-151)."""

    keys: Tuple[str, ...]
    mlp_layers: int = 4
    dense_units: int = 512
    symlog_inputs: bool = True
    dtype: Any = jnp.float32
    eps: float = 1e-3

    @nn.compact
    def __call__(self, obs: Dict[str, Array]) -> Array:
        parts = [obs[k].astype(jnp.float32) for k in self.keys]
        x = jnp.concatenate([symlog(p) if self.symlog_inputs else p for p in parts], axis=-1)
        return _LNMLP(self.mlp_layers, self.dense_units, self.dtype, self.eps)(x.astype(self.dtype))


class CNNDecoder(nn.Module):
    """Inverse of CNNEncoder (reference agent.py:154-226): Dense to a
    ``4x4x(8*mult)`` seed, 3 upsampling stages with LN+SiLU, plain final
    ConvTranspose. Returns a dict of NHWC reconstructions."""

    keys: Tuple[str, ...]
    output_channels: Tuple[int, ...]
    channels_multiplier: int
    image_size: Tuple[int, int]
    stages: int = 4
    dtype: Any = jnp.float32
    eps: float = 1e-3

    @nn.compact
    def __call__(self, latent: Array) -> Dict[str, Array]:
        lead = latent.shape[:-1]
        seed_hw = self.image_size[0] // (2**self.stages)
        seed_ch = (2 ** (self.stages - 1)) * self.channels_multiplier
        x = _dense(seed_hw * seed_hw * seed_ch, self.dtype)(latent)
        x = x.reshape(-1, seed_hw, seed_hw, seed_ch)
        for i in range(self.stages - 1):
            x = nn.ConvTranspose(
                (2 ** (self.stages - 2 - i)) * self.channels_multiplier,
                kernel_size=(4, 4),
                strides=(2, 2),
                padding=[(2, 2), (2, 2)],
                use_bias=False,
                dtype=self.dtype,
                param_dtype=jnp.float32,
                kernel_init=hafner_init,
            )(x)
            x = LayerNorm(eps=self.eps)(x)
            x = nn.silu(x)
        x = nn.ConvTranspose(
            sum(self.output_channels),
            kernel_size=(4, 4),
            strides=(2, 2),
            padding=[(2, 2), (2, 2)],
            dtype=self.dtype,
            param_dtype=jnp.float32,
            kernel_init=uniform_init(1.0),
        )(x)
        x = x.reshape(*lead, *self.image_size, sum(self.output_channels)).astype(jnp.float32)
        splits = np.cumsum(self.output_channels)[:-1]
        return {k: part for k, part in zip(self.keys, jnp.split(x, splits, axis=-1))}


class MLPDecoder(nn.Module):
    """Trunk + per-key linear heads (reference agent.py:229-278)."""

    keys: Tuple[str, ...]
    output_dims: Tuple[int, ...]
    mlp_layers: int = 4
    dense_units: int = 512
    dtype: Any = jnp.float32
    eps: float = 1e-3

    @nn.compact
    def __call__(self, latent: Array) -> Dict[str, Array]:
        x = _LNMLP(self.mlp_layers, self.dense_units, self.dtype, self.eps)(latent.astype(self.dtype))
        return {
            k: _dense(d, self.dtype, kernel_init=uniform_init(1.0), name=f"head_{k}")(x).astype(jnp.float32)
            for k, d in zip(self.keys, self.output_dims)
        }


class RecurrentModel(nn.Module):
    """Dense+LN+SiLU projection then LayerNorm-GRU (reference agent.py:281-341)
    — the RSSM hot kernel."""

    recurrent_state_size: int
    dense_units: int
    dtype: Any = jnp.float32
    eps: float = 1e-3

    @nn.compact
    def __call__(self, x: Array, h: Array) -> Array:
        feat = _dense(self.dense_units, self.dtype)(x)
        feat = LayerNorm(eps=self.eps)(feat)
        feat = nn.silu(feat)
        new_h, _ = LayerNormGRUCell(
            self.recurrent_state_size, bias=False, dtype=self.dtype
        )(h.astype(self.dtype), feat)
        return new_h.astype(jnp.float32)


class _DenseParams(nn.Module):
    """Parameter-only shadow of ``nn.Dense`` — declares the identical
    ``kernel``/``bias`` params (same names, shapes, inits) without running the
    matmul, so a fused kernel can consume them directly."""

    features: int
    in_dim: int
    use_bias: bool = True
    kernel_init: Any = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self) -> Tuple[Array, Optional[Array]]:
        kernel = self.param("kernel", self.kernel_init, (self.in_dim, self.features), jnp.float32)
        bias = (
            self.param("bias", nn.initializers.zeros_init(), (self.features,), jnp.float32)
            if self.use_bias
            else None
        )
        return kernel, bias


class _LayerNormParams(nn.Module):
    """Parameter-only shadow of the repo's LayerNorm wrapper: the wrapper
    nests an ``nn.LayerNorm`` child, so the tree is LayerNorm_0/{scale,bias}
    one level down — reproduced here for checkpoint interchange."""

    features: int

    @nn.compact
    def __call__(self) -> Tuple[Array, Array]:
        class _Inner(nn.Module):
            features: int

            @nn.compact
            def __call__(self) -> Tuple[Array, Array]:
                scale = self.param("scale", nn.initializers.ones_init(), (self.features,), jnp.float32)
                bias = self.param("bias", nn.initializers.zeros_init(), (self.features,), jnp.float32)
                return scale, bias

        return _Inner(self.features, name="LayerNorm_0")()


class FusedRecurrentModel(nn.Module):
    """Drop-in for :class:`RecurrentModel` whose whole step — input Dense →
    LN → SiLU → LayerNorm-GRU — runs as ONE Pallas TPU kernel
    (:func:`sheeprl_tpu.ops.pallas_gru.fused_recurrent_step`): both matmuls
    on the MXU from VMEM-resident weights, LayerNorm statistics and gate
    math on the VPU with no HBM round-trips between ops.

    The parameter tree exactly mirrors :class:`RecurrentModel`'s
    (Dense_0, LayerNorm_0/LayerNorm_0, LayerNormGRUCell_0/{Dense_0,
    LayerNorm_0/LayerNorm_0}), so checkpoints interchange freely between the
    fused and flax backends — ``fused=auto`` may resolve differently on the
    training and eval/resume hosts without breaking restore."""

    recurrent_state_size: int
    dense_units: int
    # accepted for signature parity with RecurrentModel but NOT used: the
    # Pallas kernel always computes in fp32 (LayerNorm statistics dominate
    # and the weights are VMEM-resident, so bf16 would save no bandwidth —
    # only cost precision in the gate math)
    dtype: Any = jnp.float32
    eps: float = 1e-3
    interpret: bool = False

    @nn.compact
    def __call__(self, x: Array, h: Array) -> Array:
        in_dim = x.shape[-1]
        d, hid = self.dense_units, self.recurrent_state_size
        w1, b1 = _DenseParams(d, in_dim, kernel_init=hafner_init, name="Dense_0")()
        g1, be1 = _LayerNormParams(d, name="LayerNorm_0")()

        class _GRUParams(nn.Module):
            hidden: int
            in_features: int

            @nn.compact
            def __call__(self) -> Tuple[Array, Array, Array]:
                kernel, _ = _DenseParams(
                    3 * self.hidden, self.in_features, use_bias=False, name="Dense_0"
                )()
                scale, bias = _LayerNormParams(3 * self.hidden, name="LayerNorm_0")()
                return kernel, scale, bias

        w2, g2, be2 = _GRUParams(hid, hid + d, name="LayerNormGRUCell_0")()
        batch_shape = x.shape[:-1]
        x2 = x.reshape(-1, in_dim)
        h2 = h.astype(jnp.float32).reshape(-1, hid)
        out = fused_recurrent_step(
            x2, h2, w1, b1, g1, be1, w2, g2, be2, eps1=self.eps, interpret=self.interpret
        )
        return out.reshape(*batch_shape, hid)


def _uniform_mix(logits: Array, discrete: int, unimix: float) -> Array:
    """1% uniform mixing of the categorical (reference agent.py:437-449)."""
    logits = logits.reshape(*logits.shape[:-1], -1, discrete)
    if unimix > 0.0:
        probs = jax.nn.softmax(logits, axis=-1)
        probs = (1 - unimix) * probs + unimix / discrete
        logits = jnp.log(probs)
    return logits  # [..., stoch, discrete]


def compute_stochastic_state(logits: Array, key: Optional[Array], sample: bool = True) -> Array:
    """Straight-through sample (or mode) of the [..., S, D] categorical,
    flattened to [..., S*D] (reference dreamer_v2/utils.py:44-60)."""
    dist = Independent(OneHotCategoricalStraightThrough(logits=logits), 1)
    state = dist.rsample(seed=key) if sample else dist.mode
    return state.reshape(*state.shape[:-2], -1)


class WorldModel(nn.Module):
    """Encoder + RSSM + decoders + reward + continue in ONE param tree
    (reference WorldModel container, dreamer_v2/agent.py:707-732, plus the
    RSSM of dreamer_v3/agent.py:344-498). Methods are entry points for
    ``apply(..., method=...)``."""

    cnn_keys: Tuple[str, ...]
    mlp_keys: Tuple[str, ...]
    cnn_output_channels: Tuple[int, ...]
    mlp_output_dims: Tuple[int, ...]
    image_size: Tuple[int, int]
    actions_dim: Tuple[int, ...]
    stochastic_size: int = 32
    discrete_size: int = 32
    unimix: float = 0.01
    recurrent_state_size: int = 4096
    recurrent_dense_units: int = 1024
    encoder_cnn_multiplier: int = 96
    encoder_mlp_layers: int = 5
    encoder_dense_units: int = 1024
    decoder_cnn_multiplier: int = 96
    decoder_mlp_layers: int = 5
    decoder_dense_units: int = 1024
    representation_hidden_size: int = 1024
    transition_hidden_size: int = 1024
    reward_bins: int = 255
    reward_layers: int = 5
    reward_dense_units: int = 1024
    continue_layers: int = 5
    continue_dense_units: int = 1024
    cnn_stages: int = 4
    learnable_initial_recurrent_state: bool = True
    fused_recurrent: Any = "auto"  # "auto" | True/"pallas" | False/"flax"
    dtype: Any = jnp.float32

    @property
    def stoch_state_size(self) -> int:
        return self.stochastic_size * self.discrete_size

    @property
    def latent_state_size(self) -> int:
        return self.stoch_state_size + self.recurrent_state_size

    def setup(self) -> None:
        if self.cnn_keys:
            self.cnn_encoder = CNNEncoder(
                self.cnn_keys, self.encoder_cnn_multiplier, self.cnn_stages, dtype=self.dtype
            )
            self.cnn_decoder = CNNDecoder(
                self.cnn_keys,
                self.cnn_output_channels,
                self.decoder_cnn_multiplier,
                self.image_size,
                self.cnn_stages,
                dtype=self.dtype,
            )
        if self.mlp_keys:
            self.mlp_encoder = MLPEncoder(
                self.mlp_keys, self.encoder_mlp_layers, self.encoder_dense_units, dtype=self.dtype
            )
            self.mlp_decoder = MLPDecoder(
                self.mlp_keys,
                self.mlp_output_dims,
                self.decoder_mlp_layers,
                self.decoder_dense_units,
                dtype=self.dtype,
            )
        gru_in_dim = self.stoch_state_size + int(sum(self.actions_dim))
        # the RSSM params are stored fp32 under every precision policy
        # (_DenseParams), which is the dtype resolve_backend sizes by default
        if resolve_backend(
            self.fused_recurrent, gru_in_dim, self.recurrent_dense_units, self.recurrent_state_size
        ):
            self.recurrent_model = FusedRecurrentModel(
                self.recurrent_state_size, self.recurrent_dense_units, dtype=self.dtype
            )
        else:
            self.recurrent_model = RecurrentModel(
                self.recurrent_state_size, self.recurrent_dense_units, dtype=self.dtype
            )
        self.representation_model = nn.Sequential(
            [
                _LNMLP(1, self.representation_hidden_size, self.dtype),
                _dense(self.stoch_state_size, jnp.float32, kernel_init=uniform_init(1.0)),
            ]
        )
        self.transition_model = nn.Sequential(
            [
                _LNMLP(1, self.transition_hidden_size, self.dtype),
                _dense(self.stoch_state_size, jnp.float32, kernel_init=uniform_init(1.0)),
            ]
        )
        self.reward_model = nn.Sequential(
            [
                _LNMLP(self.reward_layers, self.reward_dense_units, self.dtype),
                _dense(self.reward_bins, jnp.float32, kernel_init=uniform_init(0.0)),
            ]
        )
        self.continue_model = nn.Sequential(
            [
                _LNMLP(self.continue_layers, self.continue_dense_units, self.dtype),
                _dense(1, jnp.float32, kernel_init=uniform_init(1.0)),
            ]
        )
        if self.learnable_initial_recurrent_state:
            self.initial_recurrent_state = self.param(
                "initial_recurrent_state", nn.initializers.zeros_init(), (self.recurrent_state_size,), jnp.float32
            )

    # ------------------------------------------------------------------ #
    # entry points (used via apply(..., method="..."))
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

    def reward_logits(self, latent: Array) -> Array:
        return self.reward_model(latent.astype(self.dtype))

    def continue_logits(self, latent: Array) -> Array:
        return self.continue_model(latent.astype(self.dtype))

    def initial_state(self, batch_shape: Tuple[int, ...]) -> Tuple[Array, Array]:
        """(h0, z0-flat) (reference get_initial_states, agent.py:391-394)."""
        if self.learnable_initial_recurrent_state:
            h0 = jnp.tanh(self.initial_recurrent_state)
        else:
            h0 = jnp.zeros((self.recurrent_state_size,), jnp.float32)
        h0 = jnp.broadcast_to(h0, (*batch_shape, self.recurrent_state_size))
        logits = _uniform_mix(self.transition_model(h0.astype(self.dtype)), self.discrete_size, self.unimix)
        z0 = compute_stochastic_state(logits, key=None, sample=False)
        return h0, z0

    def dynamic(
        self,
        z: Array,
        h: Array,
        action: Array,
        embedded: Array,
        is_first: Array,
        key: Array,
    ) -> Tuple[Array, Array, Array, Array]:
        """One posterior step (reference RSSM.dynamic, agent.py:396-435).
        ``z`` is the flattened [B, S*D] posterior; returns
        ``(h', z', posterior_logits, prior_logits)`` with logits [B, S, D]."""
        action = (1 - is_first) * action
        h0, z0 = self.initial_state(h.shape[:-1])
        h = (1 - is_first) * h + is_first * h0
        z = (1 - is_first) * z + is_first * z0
        h = self.recurrent_model(jnp.concatenate([z, action], axis=-1).astype(self.dtype), h)
        prior_logits = _uniform_mix(self.transition_model(h.astype(self.dtype)), self.discrete_size, self.unimix)
        post_in = jnp.concatenate([h, embedded], axis=-1)
        post_logits = _uniform_mix(
            self.representation_model(post_in.astype(self.dtype)), self.discrete_size, self.unimix
        )
        z = compute_stochastic_state(post_logits, key)
        return h, z, post_logits, prior_logits

    def imagination(self, z: Array, h: Array, action: Array, key: Array) -> Tuple[Array, Array]:
        """One prior step in latent space (reference RSSM.imagination,
        agent.py:482-498)."""
        h = self.recurrent_model(jnp.concatenate([z, action], axis=-1).astype(self.dtype), h)
        prior_logits = _uniform_mix(self.transition_model(h.astype(self.dtype)), self.discrete_size, self.unimix)
        z = compute_stochastic_state(prior_logits, key)
        return z, h

    def observe_step(self, z, h, action, obs, key):
        """Policy-time posterior update: encode a single obs and run one
        dynamic-like step WITHOUT is_first gating (the player resets its own
        states — reference PlayerDV3.get_actions, agent.py:661-691)."""
        with jax.named_scope("dv3/player/encode"):
            embedded = self.encode(obs)
        with jax.named_scope("dv3/player/rssm"):
            h = self.recurrent_model(jnp.concatenate([z, action], axis=-1).astype(self.dtype), h)
            post_in = jnp.concatenate([h, embedded], axis=-1)
            post_logits = _uniform_mix(
                self.representation_model(post_in.astype(self.dtype)), self.discrete_size, self.unimix
            )
            z = compute_stochastic_state(post_logits, key)
        return z, h


def _rssm_step(wm: WorldModel):
    """One step of :func:`rssm_scan` in :func:`hoisted_scan`'s form."""

    def step(params, carry, emb_t, frozen):
        h, z, key = carry
        act_t, first_t = frozen
        key, sub = jax.random.split(key)
        h, z, post_logits, prior_logits = wm.apply(params, z, h, act_t, emb_t, first_t, sub, method=WorldModel.dynamic)
        return (h, z, key), (h, z, post_logits, prior_logits)

    return step


def _rssm_init(wm: WorldModel, batch: int, key: Array) -> Tuple[Array, Array, Array]:
    h = jnp.zeros((batch, wm.recurrent_state_size), jnp.float32)
    z = jnp.zeros((batch, wm.stoch_state_size), jnp.float32)
    return h, z, key


def rssm_scan(
    wm: WorldModel,
    params: Any,
    embedded: Array,  # [T, B, E]
    actions: Array,  # [T, B, A] (already shifted)
    is_first: Array,  # [T, B, 1]
    key: Array,
) -> Tuple[Array, Array, Array, Array]:
    """The RSSM sequence as one ``lax.scan`` (replaces the reference's Python
    loop, dreamer_v3.py:134-145). Returns time-major
    ``(recurrent_states, posteriors, posterior_logits, prior_logits)``.

    Gradients flow to ``params`` and ``embedded``. The backward loop hands out
    each dense layer's pre-activation gradient per step and every such
    kernel's gradient is one contraction over ``T x B`` after it
    (:mod:`sheeprl_tpu.ops.hoisted_scan`); the Pallas recurrent step's two
    kernels keep their per-step accumulation."""
    init = _rssm_init(wm, embedded.shape[1], key)
    _, (hs, zs, post_logits, prior_logits) = hoisted_scan(_rssm_step(wm), params, init, embedded, (actions, is_first))
    return hs, zs, post_logits, prior_logits


def rssm_scan_kernels(wm: WorldModel, params: Any, embedded: Array, actions: Array, is_first: Array, key: Array) -> Dict[str, int]:
    """What :func:`rssm_scan`'s backward does with the step's kernels on these
    arguments, from their shapes alone: the fields of the ``dv3/rssm_scan``
    counters event (howto/telemetry.md)."""
    init = _rssm_init(wm, embedded.shape[1], key)
    return hoist_plan(_rssm_step(wm), params, init, embedded, (actions, is_first)).counters(params)


class Actor(nn.Module):
    """Dreamer-V3 actor (reference agent.py:694-845). ``__call__`` returns
    raw head outputs; distribution math lives in :func:`actor_dists`."""

    latent_state_size: int
    actions_dim: Tuple[int, ...]
    is_continuous: bool
    distribution: str = "auto"
    init_std: float = 2.0
    min_std: float = 0.1
    max_std: float = 1.0
    dense_units: int = 1024
    mlp_layers: int = 5
    unimix: float = 0.01
    action_clip: float = 1.0
    dtype: Any = jnp.float32

    def resolved_distribution(self) -> str:
        dist = self.distribution.lower()
        if dist not in ("auto", "normal", "tanh_normal", "discrete", "scaled_normal"):
            raise ValueError(f"unknown actor distribution: {dist}")
        if dist == "discrete" and self.is_continuous:
            raise ValueError("discrete distribution with continuous action space")
        if dist == "auto":
            dist = "scaled_normal" if self.is_continuous else "discrete"
        return dist

    @nn.compact
    def __call__(self, state: Array) -> List[Array]:
        x = _LNMLP(self.mlp_layers, self.dense_units, self.dtype)(state.astype(self.dtype))
        if self.is_continuous:
            return [
                _dense(sum(self.actions_dim) * 2, jnp.float32, kernel_init=uniform_init(1.0), name="head_0")(x)
            ]
        return [
            _dense(d, jnp.float32, kernel_init=uniform_init(1.0), name=f"head_{i}")(x)
            for i, d in enumerate(self.actions_dim)
        ]


def actor_dists(actor: Actor, pre_dist: List[Array]):
    """Build the action distributions from raw head outputs
    (reference Actor.forward, agent.py:783-845)."""
    dist_type = actor.resolved_distribution()
    if actor.is_continuous:
        mean, std = jnp.split(pre_dist[0], 2, axis=-1)
        if dist_type == "tanh_normal":
            mean = 5 * jnp.tanh(mean / 5)
            std = jax.nn.softplus(std + actor.init_std) + actor.min_std
            return [TanhNormal(mean, std)]
        if dist_type == "normal":
            return [Independent(Normal(mean, std), 1)]
        # scaled_normal (DV3 default)
        std = (actor.max_std - actor.min_std) * jax.nn.sigmoid(std + actor.init_std) + actor.min_std
        return [Independent(Normal(jnp.tanh(mean), std), 1)]
    return [
        OneHotCategoricalStraightThrough(logits=_actor_unimix(logits, actor.unimix)) for logits in pre_dist
    ]


def _actor_unimix(logits: Array, unimix: float) -> Array:
    if unimix > 0.0:
        probs = jax.nn.softmax(logits, axis=-1)
        probs = (1 - unimix) * probs + unimix / probs.shape[-1]
        logits = jnp.log(probs)
    return logits


class MinedojoActor(Actor):
    """Actor whose discrete heads honor MineDojo's action masks at play time
    (reference MinedojoActor, agent.py:848-932): the action-type head is
    masked directly; the craft head only when the sampled action type is
    CRAFT (15); the item head by the equip/place mask for action types 16-17
    and the destroy mask for 18. Selected via ``algo.actor.cls``."""


def sample_minedojo_actions(
    actor: Actor,
    params: Any,
    state: Array,
    key: Array,
    mask: Optional[Dict[str, Array]],
    greedy: bool = False,
) -> Array:
    """Masked sequential sampling of the three MineDojo heads — the
    reference's per-(t, b) Python loops (agent.py:903-929) become vectorized
    ``jnp.where`` masking."""
    heads = actor.apply(params, state)
    neg_inf = jnp.asarray(-jnp.inf, jnp.float32)
    keys = jax.random.split(key, len(heads))

    logits0 = _actor_unimix(heads[0], actor.unimix)
    if mask is not None:
        logits0 = jnp.where(mask["mask_action_type"].astype(bool), logits0, neg_inf)
    d0 = OneHotCategoricalStraightThrough(logits=logits0)
    a0 = d0.mode if greedy else d0.rsample(seed=keys[0])
    func = jnp.argmax(a0, axis=-1)  # composite action type

    logits1 = _actor_unimix(heads[1], actor.unimix)
    if mask is not None:
        is_craft = (func == 15)[..., None]
        logits1 = jnp.where(jnp.logical_and(is_craft, ~mask["mask_craft_smelt"].astype(bool)), neg_inf, logits1)
    d1 = OneHotCategoricalStraightThrough(logits=logits1)
    a1 = d1.mode if greedy else d1.rsample(seed=keys[1])

    logits2 = _actor_unimix(heads[2], actor.unimix)
    if mask is not None:
        is_equip_place = jnp.logical_or(func == 16, func == 17)[..., None]
        is_destroy = (func == 18)[..., None]
        logits2 = jnp.where(
            jnp.logical_and(is_equip_place, ~mask["mask_equip_place"].astype(bool)), neg_inf, logits2
        )
        logits2 = jnp.where(
            jnp.logical_and(is_destroy, ~mask["mask_destroy"].astype(bool)), neg_inf, logits2
        )
    d2 = OneHotCategoricalStraightThrough(logits=logits2)
    a2 = d2.mode if greedy else d2.rsample(seed=keys[2])
    return jnp.concatenate([a0, a1, a2], axis=-1)


def sample_actor_actions(
    actor: Actor, params: Any, state: Array, key: Array, greedy: bool = False
) -> Array:
    """Sample (or mode) actions; returns the concatenated action vector."""
    dists = actor_dists(actor, actor.apply(params, state))
    if actor.is_continuous:
        d = dists[0]
        if greedy:
            # sample 100 candidates, keep the most likely (reference :820-822)
            cand = d.sample(seed=key, sample_shape=(100,))
            logp = jax.vmap(d.log_prob)(cand)
            idx = jnp.argmax(logp, axis=0)
            actions = jnp.take_along_axis(cand, idx[None, ..., None], axis=0)[0]
        else:
            actions = d.rsample(seed=key)
        if actor.action_clip > 0.0:
            clip = jnp.full_like(actions, actor.action_clip)
            actions = actions * jax.lax.stop_gradient(clip / jnp.maximum(clip, jnp.abs(actions)))
        return actions
    keys = jax.random.split(key, len(dists))
    parts = [(d.mode if greedy else d.rsample(seed=k)) for d, k in zip(dists, keys)]
    return jnp.concatenate(parts, axis=-1)


def actor_logprob_entropy(
    actor: Actor, params: Any, states: Array, actions: Array
) -> Tuple[Array, Array]:
    """log pi(a|s) and entropy for stored (imagined) actions; discrete
    actions are the concatenated one-hots."""
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


def make_critic(cfg_critic: Dict[str, Any], dtype: Any) -> MLP:
    """Two-hot critic trunk+head as one MLP-like module."""

    class Critic(nn.Module):
        bins: int
        layers: int
        units: int
        dtype: Any

        @nn.compact
        def __call__(self, x: Array) -> Array:
            x = _LNMLP(self.layers, self.units, self.dtype)(x.astype(self.dtype))
            return _dense(self.bins, jnp.float32, kernel_init=uniform_init(0.0))(x)

    return Critic(
        bins=int(cfg_critic["bins"]),
        layers=int(cfg_critic["mlp_layers"]),
        units=int(cfg_critic["dense_units"]),
        dtype=dtype,
    )


class PlayerDV3(HostPlayerParams):
    """Stateful env-interaction handle (reference PlayerDV3,
    agent.py:596-691): keeps (h, z, prev_action) per env and advances them
    with one jitted observe+act step.

    The recurrent state lives ON DEVICE between steps — pulling (h, z) to
    host every step would double the per-step round trips; only the action
    is downloaded. Per-env resets are a jitted masked blend instead of
    host-side indexing.

    ``device`` (see ``parallel.fabric.resolve_player_device``) optionally
    pins the player to the host CPU backend: the observe+act step then runs
    host-side with no accelerator round trip per env step, and
    ``update_params`` streams fresh learner params accelerator→host once per
    train block — the learner-on-accelerator/actor-on-host split taken when
    the dispatch round trip measures above 5 ms."""

    _placed_attrs = ("wm_params", "actor_params")

    def __init__(
        self,
        wm: WorldModel,
        wm_params: Any,
        actor: Actor,
        actor_params: Any,
        actions_dim: Sequence[int],
        num_envs: int,
        device: Optional[Any] = None,
    ) -> None:
        self.wm = wm
        self.actor = actor
        self.device = device  # must precede the param assignments below
        self.wm_params = wm_params
        self.actor_params = actor_params
        self.actions_dim = tuple(actions_dim)
        self.num_envs = num_envs
        self.h: Optional[Any] = None  # device [E, H]
        self.z: Optional[Any] = None  # device [E, S]
        self.actions: Optional[Any] = None  # device [E, A]

        # the jitted programs carry stable names of their own (the XLA module
        # is ``jit_<name>``; howto/telemetry.md lists them), and the step's
        # three parts a ``jax.named_scope`` each: ``dv3/player/encode`` and
        # ``dv3/player/rssm`` (in ``WorldModel.observe_step``), ``dv3/player/actor``
        def dv3_player_step(wm_params, actor_params, obs, h, z, prev_action, key, greedy):
            k1, k2 = jax.random.split(key)
            z, h = wm.apply(wm_params, z, h, prev_action, obs, k1, method=WorldModel.observe_step)
            with jax.named_scope("dv3/player/actor"):
                latent = jnp.concatenate([z, h], axis=-1)
                action = sample_actor_actions(actor, actor_params, latent, k2, greedy)
            return action, h, z

        def dv3_player_step_masked(wm_params, actor_params, obs, h, z, prev_action, key, mask, greedy):
            k1, k2 = jax.random.split(key)
            z, h = wm.apply(wm_params, z, h, prev_action, obs, k1, method=WorldModel.observe_step)
            with jax.named_scope("dv3/player/actor"):
                latent = jnp.concatenate([z, h], axis=-1)
                action = sample_minedojo_actions(actor, actor_params, latent, k2, mask, greedy)
            return action, h, z

        def dv3_player_reset(wm_params, h, z, actions, mask):
            # mask [E, 1]: 1 where the env restarts
            h0, z0 = wm.apply(wm_params, (h.shape[0],), method=WorldModel.initial_state)
            return (
                jnp.where(mask, h0, h),
                jnp.where(mask, z0, z),
                jnp.where(mask, 0.0, actions),
            )

        def dv3_player_initial(wm_params, n):
            return wm.apply(wm_params, (n,), method=WorldModel.initial_state)

        self._step = jax.jit(dv3_player_step, static_argnames="greedy")
        self._step_masked = jax.jit(dv3_player_step_masked, static_argnames="greedy")
        self._initial = jax.jit(dv3_player_initial, static_argnums=1)
        self._masked_reset = jax.jit(dv3_player_reset)

    def update_params(self, wm_params: Any, actor_params: Any) -> None:
        """Refresh the player's weights from the learner's. In host-player
        mode the trees stream through the non-blocking pipe
        (``fabric.HostPlayerParams.stream_attr``): the call returns
        immediately and the player flips to the new params a train block or
        two later, once the async device→host copy lands — the env loop
        never stalls on the transfer."""
        self.stream_attr("wm_params", wm_params)
        self.stream_attr("actor_params", actor_params)

    def init_states(self, reset_envs: Optional[Sequence[int]] = None) -> None:
        if reset_envs is None or len(reset_envs) == 0:
            h0, z0 = self._initial(self.wm_params, self.num_envs)
            self.h, self.z = h0, z0
            # host-side zeros: uncommitted, so the next jitted step pulls
            # them onto whichever backend the params live on
            self.actions = np.zeros((self.num_envs, int(np.sum(self.actions_dim))), np.float32)
        else:
            mask = np.zeros((self.num_envs, 1), np.float32)
            mask[list(reset_envs)] = 1.0
            self.h, self.z, self.actions = self._masked_reset(
                self.wm_params, self.h, self.z, self.actions, mask
            )

    def get_actions(
        self,
        obs: Dict[str, Array],
        key: Array,
        greedy: bool = False,
        mask: Optional[Dict[str, Array]] = None,
        fetch: bool = True,
    ) -> Array:
        """One observe+act step. ``fetch=False`` is for a caller that queues
        the forward ahead of the turn that needs it: the step is dispatched,
        the action's copy to the host started, and the device array returned
        for the caller to read (``np.asarray``) when that turn comes."""
        self.poll_stream_attrs()
        # keys minted on another backend would clash with host-pinned params
        # (committed-device mismatch) — re-place; identity when aligned
        key = put_tree(key, self.device)
        # only the MinedojoActor honors masks — the base Actor ignores them,
        # matching the reference's forward signatures (agent.py:783, :882)
        if mask and isinstance(self.actor, MinedojoActor):
            action, h, z = self._step_masked(
                self.wm_params, self.actor_params, obs, self.h, self.z, self.actions, key, mask, greedy
            )
        else:
            action, h, z = self._step(
                self.wm_params, self.actor_params, obs, self.h, self.z, self.actions, key, greedy
            )
        # recurrent state stays on device; only the action crosses PCIe
        self.actions, self.h, self.z = action, h, z
        if not fetch:
            action.copy_to_host_async()
            return action
        return np.asarray(jax.device_get(action))


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
) -> Tuple[WorldModel, Any, Actor, Any, Any, Any, Any, PlayerDV3]:
    """Construct modules + init/replicate params (reference build_agent,
    agent.py:935-1236). Returns
    ``(wm, wm_params, actor, actor_params, critic, critic_params,
    target_critic_params, player)``."""
    wm_cfg = cfg["algo"]["world_model"]
    cnn_keys = tuple(cfg["algo"]["cnn_keys"]["encoder"])
    mlp_keys = tuple(cfg["algo"]["mlp_keys"]["encoder"])
    compute_dtype = fabric.precision.compute_dtype
    screen = int(cfg["env"]["screen_size"])
    cnn_stages = int(np.log2(screen) - np.log2(4))

    def _channels(k):
        shape = obs_space[k].shape
        return int(np.prod(shape[:-3]) * shape[-1]) if len(shape) >= 3 else 1

    wm = WorldModel(
        cnn_keys=cnn_keys,
        mlp_keys=mlp_keys,
        cnn_output_channels=tuple(_channels(k) for k in cfg["algo"]["cnn_keys"]["decoder"]),
        mlp_output_dims=tuple(int(obs_space[k].shape[0]) for k in cfg["algo"]["mlp_keys"]["decoder"]),
        image_size=(screen, screen),
        actions_dim=tuple(actions_dim),
        stochastic_size=int(wm_cfg["stochastic_size"]),
        discrete_size=int(wm_cfg["discrete_size"]),
        unimix=float(cfg["algo"]["unimix"]),
        recurrent_state_size=int(wm_cfg["recurrent_model"]["recurrent_state_size"]),
        recurrent_dense_units=int(wm_cfg["recurrent_model"]["dense_units"]),
        fused_recurrent=wm_cfg["recurrent_model"].get("fused", "auto"),
        encoder_cnn_multiplier=int(wm_cfg["encoder"]["cnn_channels_multiplier"]),
        encoder_mlp_layers=int(wm_cfg["encoder"]["mlp_layers"]),
        encoder_dense_units=int(wm_cfg["encoder"]["dense_units"]),
        decoder_cnn_multiplier=int(wm_cfg["observation_model"]["cnn_channels_multiplier"]),
        decoder_mlp_layers=int(wm_cfg["observation_model"]["mlp_layers"]),
        decoder_dense_units=int(wm_cfg["observation_model"]["dense_units"]),
        representation_hidden_size=int(wm_cfg["representation_model"]["hidden_size"]),
        transition_hidden_size=int(wm_cfg["transition_model"]["hidden_size"]),
        reward_bins=int(wm_cfg["reward_model"]["bins"]),
        reward_layers=int(wm_cfg["reward_model"]["mlp_layers"]),
        reward_dense_units=int(wm_cfg["reward_model"]["dense_units"]),
        continue_layers=int(wm_cfg["discount_model"]["mlp_layers"]),
        continue_dense_units=int(wm_cfg["discount_model"]["dense_units"]),
        cnn_stages=cnn_stages,
        learnable_initial_recurrent_state=bool(wm_cfg["learnable_initial_recurrent_state"]),
        dtype=compute_dtype,
    )

    actor_cls = (
        MinedojoActor if "minedojo" in str(cfg["algo"]["actor"].get("cls", "")).lower() else Actor
    )
    actor = actor_cls(
        latent_state_size=wm.latent_state_size,
        actions_dim=tuple(actions_dim),
        is_continuous=bool(is_continuous),
        distribution=str(cfg.get("distribution", {}).get("type", "auto")),
        init_std=float(cfg["algo"]["actor"]["init_std"]),
        min_std=float(cfg["algo"]["actor"]["min_std"]),
        max_std=float(cfg["algo"]["actor"].get("max_std", 1.0)),
        dense_units=int(cfg["algo"]["actor"]["dense_units"]),
        mlp_layers=int(cfg["algo"]["actor"]["mlp_layers"]),
        unimix=float(cfg["algo"]["unimix"]),
        action_clip=float(cfg["algo"]["actor"]["action_clip"]),
        dtype=compute_dtype,
    )
    critic = make_critic(dict(cfg["algo"]["critic"]), compute_dtype)

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
        # initialize every submodule: encode + one dynamic step + decode/reward/continue
        def wm_init(mod: WorldModel):
            emb = mod.encode(dummy_obs)
            h = jnp.zeros((B, wm.recurrent_state_size), jnp.float32)
            z = jnp.zeros((B, wm.stoch_state_size), jnp.float32)
            a = jnp.zeros((B, int(np.sum(actions_dim))), jnp.float32)
            first = jnp.ones((B, 1), jnp.float32)
            h, z, _, _ = mod.dynamic(z, h, a, emb, first, k_dyn)
            latent = jnp.concatenate([z, h], axis=-1)
            mod.decode(latent)
            mod.reward_logits(latent)
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

    # model-axis meshes shard the large kernels over `model` (fabric
    # param_spec rule); pure-DP meshes replicate — same call either way
    wm_params = fabric.shard_params(wm_params)
    actor_params = fabric.shard_params(actor_params)
    critic_params = fabric.shard_params(critic_params)
    target_critic_params = fabric.shard_params(target_critic_params)

    from sheeprl_tpu.parallel.fabric import resolve_player_device

    player_device = resolve_player_device(cfg["algo"].get("player_device", "auto"))
    # a host-pinned player runs on the CPU backend, where the Pallas TPU
    # kernel cannot execute — swap in the flax GRU cell (identical param
    # tree, pallas_gru docstring) for the player's module only
    player_wm = wm.clone(fused_recurrent="flax") if player_device is not None else wm
    player = PlayerDV3(
        player_wm,
        wm_params,
        actor,
        actor_params,
        actions_dim,
        int(cfg["env"]["num_envs"]),
        device=player_device,
    )
    return wm, wm_params, actor, actor_params, critic, critic_params, target_critic_params, player
