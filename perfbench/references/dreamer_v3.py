"""Dreamer-V3 in plain ``jax.numpy``: weights from a seed, one gradient step
of the world model, the actor and the critic, and the player's forward.

Written from the paper (Hafner et al. 2023, arXiv:2301.04104) and the recipe's
stated constants; it imports nothing of ``sheeprl_tpu`` and takes no weight,
scale or table from it. What it shares with the program is the
configuration's file, the batch that the replay fed, and the random key of
each step with the order in which the algorithm splits it (scan key first,
imagination key second; one split per RSSM step; three per imagined step):
with random weights a categorical sample changes on rounding, so the two
sides can only be compared on the same draws.

``policy`` is the precision of the step. The configurations state
``bf16-mixed``, which is two precisions: float32 for the weights and the
optimizer's state, bfloat16 for products and activations. Each has its
control, the step below it that would tempt a later PR:

- ``float32``: operands and sums in float32 at ``highest`` — the reference.
- ``bfloat16``: what ``bf16-mixed`` states: float32 weights, bfloat16
  operands and activations in the trunks, float32 logits, losses and
  LayerNorm statistics.
- ``bfloat16_weights``: the control below float32: as ``bfloat16``, with the
  weights and Adam's moments kept in bfloat16 too (``bf16-true``). An update of
  1e-4 to a weight of 1 is then lost, and one to a weight of 0.03 lands on a
  grid of 1.2e-4.
- ``float8``: the control below bfloat16: an 8-bit float (4 exponent bits, 3
  of mantissa) wherever the bfloat16 policy has bfloat16. Every
  operand of a product and every activation that a trunk keeps (a product's
  result, a LayerNorm's output) is rounded to it under a per-tensor scale, the
  rounding passed straight through on the way back, and the gradient arriving
  at each product is rounded to 5 exponent bits and 2 of mantissa under a per-tensor scale.
  (Rounding the operands alone, with results kept in bfloat16, reads no
  different from bfloat16 itself: over 512 terms or more the products'
  errors average out below bfloat16's own rounding of the result. PERF.md,
  section 2.)
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

Params = Dict[str, Any]
F32 = jnp.float32


# --------------------------------------------------------------------------- #
# shapes and weights
# --------------------------------------------------------------------------- #


def sizes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Derived sizes of the configuration's ``model`` block."""
    m = cfg["model"]
    stages = int(math.log2(m["image_size"]) - 2)
    stoch = m["stochastic_size"] * m["discrete_size"]
    cnn_out = (m["image_size"] // 2**stages) ** 2 * (2 ** (stages - 1)) * m["cnn_multiplier"]
    mlp_in = sum(m.get("mlp_inputs", {}).values())
    embed = cnn_out + (m["dense_units"] if mlp_in else 0)
    action = cfg["env"]["action"]
    act_dim = int(action["dim"])
    return {
        "stages": stages,
        "stoch": stoch,
        "cnn_out": cnn_out,
        "mlp_in": mlp_in,
        "embed": embed,
        "act_dim": act_dim,
        "continuous": action["type"] == "continuous",
        "latent": stoch + m["recurrent_state_size"],
    }


def _fans(shape: Sequence[int]) -> Tuple[float, float]:
    receptive = float(np.prod(shape[:-2])) if len(shape) > 2 else 1.0
    return shape[-2] * receptive, shape[-1] * receptive


def _trunc_normal(key, shape, fan: float):
    std = math.sqrt(1.0 / fan) / 0.87962566103423978
    return jax.random.truncated_normal(key, -2.0, 2.0, shape, F32) * std


def _hafner(key, shape):
    fan_in, fan_out = _fans(shape)
    return _trunc_normal(key, shape, (fan_in + fan_out) / 2.0)


def _uniform(scale: float):
    def init(key, shape):
        if scale == 0.0:
            return jnp.zeros(shape, F32)
        fan_in, fan_out = _fans(shape)
        limit = math.sqrt(3.0 * scale / ((fan_in + fan_out) / 2.0))
        return jax.random.uniform(key, shape, F32, -limit, limit)

    return init


class _Keys:
    def __init__(self, key) -> None:
        self.key = key

    def __call__(self):
        self.key, sub = jax.random.split(self.key)
        return sub


def _block(keys: _Keys, fan_in: int, units: int) -> Params:
    return {
        "w": _hafner(keys(), (fan_in, units)),
        "b": jnp.zeros((units,), F32),
        "ln_s": jnp.ones((units,), F32),
        "ln_b": jnp.zeros((units,), F32),
    }


def _trunk(keys: _Keys, fan_in: int, units: int, layers: int) -> List[Params]:
    return [_block(keys, fan_in if i == 0 else units, units) for i in range(layers)]


def _head(keys: _Keys, fan_in: int, units: int, layers: int, out: int, out_scale: float) -> Params:
    return {
        "trunk": _trunk(keys, fan_in, units, layers),
        "out": {"w": _uniform(out_scale)(keys(), (units, out)), "b": jnp.zeros((out,), F32)},
    }


def seeded_params(cfg: Dict[str, Any], seed: int) -> Tuple[Params, Params, Params]:
    """:func:`init_params` on the device in one jitted call. The seed goes in
    as an argument, so one compiled program (and one entry of the persistent
    compile cache) serves every seed."""

    def seeded_params(seed_u32):
        return init_params(cfg, seed_u32)

    return jax.jit(seeded_params)(np.uint32(seed % (2**32)))


def init_params(cfg: Dict[str, Any], seed) -> Tuple[Params, Params, Params]:
    """``(world_model, actor, critic)`` from ``seed`` (a ``uint32``, traced or
    not): truncated-normal fan-average trunks, uniform fan-average output
    layers, zero reward and critic outputs, as the paper's appendix has them."""
    m, s = cfg["model"], sizes(cfg)
    keys = _Keys(jax.random.PRNGKey(seed))
    units, layers, mult = m["dense_units"], m["mlp_layers"], m["cnn_multiplier"]
    rec, hidden = m["recurrent_state_size"], m["hidden_size"]
    wm: Params = {"enc_cnn": [], "dec": {"convs": []}}
    cin = m["image_channels"]
    for i in range(s["stages"]):
        cout = 2**i * mult
        wm["enc_cnn"].append(
            {"w": _hafner(keys(), (4, 4, cin, cout)), "ln_s": jnp.ones((cout,), F32), "ln_b": jnp.zeros((cout,), F32)}
        )
        cin = cout
    if s["mlp_in"]:
        wm["enc_mlp"] = _trunk(keys, s["mlp_in"], units, layers)
        # the recipe builds the vector decoder's trunk even where it decodes
        # no key; nothing reads it and its gradient is exactly zero
        wm["dec_mlp_trunk"] = _trunk(keys, s["latent"], units, layers)
    seed_ch = 2 ** (s["stages"] - 1) * mult
    seed_hw = m["image_size"] // 2 ** s["stages"]
    wm["dec"]["fc"] = {
        "w": _hafner(keys(), (s["latent"], seed_hw * seed_hw * seed_ch)),
        "b": jnp.zeros((seed_hw * seed_hw * seed_ch,), F32),
    }
    cin = seed_ch
    for i in range(s["stages"] - 1):
        cout = 2 ** (s["stages"] - 2 - i) * mult
        wm["dec"]["convs"].append(
            {"w": _hafner(keys(), (4, 4, cin, cout)), "ln_s": jnp.ones((cout,), F32), "ln_b": jnp.zeros((cout,), F32)}
        )
        cin = cout
    wm["dec"]["out"] = {
        "w": _uniform(1.0)(keys(), (4, 4, cin, m["image_channels"])),
        "b": jnp.zeros((m["image_channels"],), F32),
    }
    wm["rec"] = {
        "fc": _block(keys, s["stoch"] + s["act_dim"], units),
        "gru": {
            "w": _trunc_normal(keys(), (rec + units, 3 * rec), float(rec + units)),
            "ln_s": jnp.ones((3 * rec,), F32),
            "ln_b": jnp.zeros((3 * rec,), F32),
        },
    }
    wm["trans"] = _head(keys, rec, hidden, 1, s["stoch"], 1.0)
    wm["repr"] = _head(keys, rec + s["embed"], hidden, 1, s["stoch"], 1.0)
    wm["reward"] = _head(keys, s["latent"], units, layers, m["bins"], 0.0)
    wm["cont"] = _head(keys, s["latent"], units, layers, 1, 1.0)
    wm["h0"] = jnp.zeros((rec,), F32)
    actor_out = 2 * s["act_dim"] if s["continuous"] else s["act_dim"]
    actor = _head(keys, s["latent"], units, layers, actor_out, 1.0)
    critic = _head(keys, s["latent"], units, layers, m["bins"], 0.0)
    return wm, actor, critic


# --------------------------------------------------------------------------- #
# layers
# --------------------------------------------------------------------------- #


#: exponent and mantissa bits of the two 8-bit floats, and the largest value
#: that ``lax.reduce_precision`` keeps finite at those widths
E4M3 = (4, 3, 240.0)
E5M2 = (5, 2, 57344.0)


def _round_fp8(x, fmt):
    """``x`` rounded to an 8-bit float under a per-tensor scale, and back.
    By ``lax.reduce_precision``, which the compiler has to keep: a conversion
    to ``float8`` and back it removes on the TPU as excess precision, and the
    control then reads as bfloat16 does."""
    exponent_bits, mantissa_bits, top = fmt
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)).astype(F32), 1e-30)
    return (lax.reduce_precision(x.astype(F32) * scale, exponent_bits, mantissa_bits) / scale).astype(x.dtype)


@jax.custom_vjp
def _fp8_gradient(y):
    """The identity, whose gradient is rounded to ``float8_e5m2``."""
    return y


_fp8_gradient.defvjp(lambda y: (y, None), lambda _, g: (_round_fp8(g, E5M2),))


class Precision:
    def __init__(self, policy: str) -> None:
        if policy not in ("float32", "bfloat16", "bfloat16_weights", "float8"):
            raise ValueError(f"unknown precision policy {policy!r}")
        self.policy = policy
        self.act = F32 if policy == "float32" else jnp.bfloat16
        #: what the weights and the optimizer's state are kept in
        self.weights = jnp.bfloat16 if policy == "bfloat16_weights" else F32
        self.precision = lax.Precision.HIGHEST if policy == "float32" else None

    def keep(self, x):
        """``x`` as a trunk keeps it and as a product reads it: in the
        activation type, under ``float8`` rounded to it (straight through)."""
        x = x.astype(self.act)
        if self.policy == "float8":
            return x + lax.stop_gradient(_round_fp8(x, E4M3) - x)
        return x

    def result(self, y):
        return _fp8_gradient(y) if self.policy == "float8" else y

    def matmul(self, x, w, out_dtype=None):
        y = jnp.matmul(self.keep(x), self.keep(w), precision=self.precision, preferred_element_type=F32)
        y = self.result(y)
        return y.astype(out_dtype) if out_dtype else self.keep(y)

    def conv(self, x, w, stride: int, padding: int, lhs_dilation: int = 1):
        y = lax.conv_general_dilated(
            self.keep(x),
            self.keep(w),
            window_strides=(stride, stride),
            padding=[(padding, padding), (padding, padding)],
            lhs_dilation=(lhs_dilation, lhs_dilation),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=self.precision,
        )
        return self.keep(self.result(y))


def layer_norm(x, scale, bias, eps: float):
    dtype = x.dtype
    x = x.astype(F32)
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return ((x - mean) * lax.rsqrt(var + eps) * scale + bias).astype(dtype)


def silu(x):
    return x * jax.nn.sigmoid(x)


def trunk(pr: Precision, layers: List[Params], x, eps: float = 1e-3):
    x = x.astype(pr.act)
    for p in layers:
        x = pr.matmul(x, p["w"]) + p["b"].astype(pr.act)
        x = pr.keep(silu(layer_norm(x, p["ln_s"], p["ln_b"], eps)))
    return x


def head(pr: Precision, p: Params, x):
    """Trunk in the activation type, output layer and logits in float32."""
    x = trunk(pr, p["trunk"], x)
    return pr.matmul(x.astype(F32), p["out"]["w"], F32) + p["out"]["b"]


def symlog(x):
    return jnp.sign(x) * jnp.log1p(jnp.abs(x))


def symexp(x):
    return jnp.sign(x) * jnp.expm1(jnp.abs(x))


def unimix(logits, classes: int, mix: float):
    logits = logits.reshape(*logits.shape[:-1], -1, classes)
    probs = (1.0 - mix) * jax.nn.softmax(logits, -1) + mix / classes
    return jnp.log(probs)


def one_hot_sample(key, logits):
    """Straight-through one-hot sample over the last axis."""
    idx = jax.random.categorical(key, logits, axis=-1, shape=logits.shape[:-1])
    probs = jax.nn.softmax(logits, -1)
    return jax.nn.one_hot(idx, logits.shape[-1], dtype=logits.dtype) + probs - lax.stop_gradient(probs)


def one_hot_mode(logits):
    return jax.nn.one_hot(jnp.argmax(logits, -1), logits.shape[-1], dtype=logits.dtype)


def twohot_bins(n: int):
    return jnp.linspace(-20.0, 20.0, n, dtype=F32)


def twohot_mean(logits):
    bins = twohot_bins(logits.shape[-1])
    return symexp((jax.nn.softmax(logits, -1) * bins).sum(-1, keepdims=True))


def twohot_log_prob(logits, x):
    """Cross-entropy of ``logits`` against the two-hot code of ``symlog(x)``
    (``x`` is ``[..., 1]``) on 255 bins over [-20, 20]."""
    bins = twohot_bins(logits.shape[-1])
    n = bins.shape[0]
    x = symlog(x)
    below = (bins <= x).astype(jnp.int32).sum(-1) - 1
    above = jnp.minimum(below + 1, n - 1)
    below = jnp.maximum(below, 0)
    same = below == above
    to_below = jnp.where(same, 1.0, jnp.abs(bins[below] - x[..., 0]))
    to_above = jnp.where(same, 1.0, jnp.abs(bins[above] - x[..., 0]))
    total = to_below + to_above
    target = (
        jax.nn.one_hot(below, n, dtype=F32) * (to_above / total)[..., None]
        + jax.nn.one_hot(above, n, dtype=F32) * (to_below / total)[..., None]
    )
    return (target * jax.nn.log_softmax(logits, -1)).sum(-1)


# --------------------------------------------------------------------------- #
# the world model
# --------------------------------------------------------------------------- #


class Model:
    """The networks as functions of their weights; ``cfg`` is the
    configuration's file."""

    def __init__(self, cfg: Dict[str, Any], policy: str) -> None:
        self.cfg, self.m, self.a, self.s = cfg, cfg["model"], cfg["algo"], sizes(cfg)
        self.pr = Precision(policy)

    # -- encoder / decoder ----------------------------------------------------

    def encode(self, wm: Params, obs: Dict[str, Any]):
        pr = self.pr
        x = obs["rgb"].astype(pr.act) / 255.0 - 0.5
        lead = x.shape[:-3]
        x = x.reshape(-1, *x.shape[-3:])
        for p in wm["enc_cnn"]:
            x = pr.conv(x, p["w"], stride=2, padding=1)
            x = pr.keep(silu(layer_norm(x, p["ln_s"], p["ln_b"], 1e-3)))
        out = x.reshape(*lead, -1)
        if self.s["mlp_in"]:
            vec = jnp.concatenate([symlog(obs[k].astype(F32)) for k in self.m["mlp_inputs"]], -1)
            out = jnp.concatenate([out, trunk(pr, wm["enc_mlp"], vec)], -1)
        return out.astype(F32)

    def decode(self, wm: Params, latent):
        pr, d = self.pr, wm["dec"]
        lead = latent.shape[:-1]
        hw = self.m["image_size"] // 2 ** self.s["stages"]
        x = pr.matmul(latent.astype(pr.act), d["fc"]["w"]) + d["fc"]["b"].astype(pr.act)
        x = x.reshape(-1, hw, hw, x.shape[-1] // (hw * hw))
        for p in d["convs"]:
            x = pr.conv(x, p["w"], stride=1, padding=2, lhs_dilation=2)
            x = pr.keep(silu(layer_norm(x, p["ln_s"], p["ln_b"], 1e-3)))
        x = pr.conv(x, d["out"]["w"], stride=1, padding=2, lhs_dilation=2) + d["out"]["b"].astype(pr.act)
        return x.reshape(*lead, *x.shape[1:]).astype(F32)

    # -- RSSM -----------------------------------------------------------------

    def recurrent(self, wm: Params, x, h):
        """Dense, LayerNorm, SiLU, then the LayerNorm-GRU of the paper: one
        joint projection of [h, x], reset/candidate/update, update bias -1."""
        pr, p = self.pr, wm["rec"]
        feat = trunk(pr, [p["fc"]], x)
        h = h.astype(pr.act)
        proj = pr.matmul(jnp.concatenate([h, feat], -1), p["gru"]["w"])
        proj = pr.keep(layer_norm(proj, p["gru"]["ln_s"], p["gru"]["ln_b"], 1e-5))
        reset, cand, update = jnp.split(proj, 3, -1)
        cand = jnp.tanh(jax.nn.sigmoid(reset) * cand)
        update = jax.nn.sigmoid(update - 1)
        return (update * cand + (1 - update) * h).astype(F32)

    def logits(self, p: Params, x):
        return unimix(head(self.pr, p, x), self.m["discrete_size"], self.a["unimix"])

    def initial(self, wm: Params, batch: int):
        h0 = jnp.broadcast_to(jnp.tanh(wm["h0"]), (batch, wm["h0"].shape[0]))
        z0 = one_hot_mode(self.logits(wm["trans"], h0)).reshape(batch, -1)
        return h0, z0

    def observe(self, wm: Params, embedded, actions, is_first, key):
        """Posterior over a ``[T, B]`` sequence; ``actions`` already shifted."""
        B = embedded.shape[1]
        rec = wm["h0"].shape[0]

        def step(carry, xs):
            h, z, key = carry
            emb, act, first = xs
            key, sub = jax.random.split(key)
            h0, z0 = self.initial(wm, B)
            act = (1 - first) * act
            h = (1 - first) * h + first * h0
            z = (1 - first) * z + first * z0
            h = self.recurrent(wm, jnp.concatenate([z, act], -1), h)
            prior = self.logits(wm["trans"], h)
            post = self.logits(wm["repr"], jnp.concatenate([h, emb], -1))
            z = one_hot_sample(sub, post).reshape(B, -1)
            return (h, z, key), (h, z, post, prior)

        init = (jnp.zeros((B, rec), F32), jnp.zeros((B, self.s["stoch"]), F32), key)
        _, out = lax.scan(step, init, (embedded, actions, is_first))
        return out

    def imagine_step(self, wm: Params, z, h, action, key):
        h = self.recurrent(wm, jnp.concatenate([z, action], -1), h)
        z = one_hot_sample(key, self.logits(wm["trans"], h))
        return z.reshape(z.shape[0], -1), h

    # -- actor ----------------------------------------------------------------

    def actor_out(self, actor: Params, latent):
        out = head(self.pr, actor, latent)
        if self.s["continuous"]:
            mean, std = jnp.split(out, 2, -1)
            a = self.a
            std = (a["actor_max_std"] - a["actor_min_std"]) * jax.nn.sigmoid(std + a["actor_init_std"]) + a[
                "actor_min_std"
            ]
            return jnp.tanh(mean), std
        probs = (1.0 - self.a["unimix"]) * jax.nn.softmax(out, -1) + self.a["unimix"] / out.shape[-1]
        return (jnp.log(probs),)

    def act(self, actor: Params, latent, key):
        dist = self.actor_out(actor, latent)
        if self.s["continuous"]:
            mean, std = dist
            action = mean + jax.random.normal(key, mean.shape, mean.dtype) * std
            clip = self.a["action_clip"]
            return action * lax.stop_gradient(clip / jnp.maximum(clip, jnp.abs(action)))
        return one_hot_sample(jax.random.split(key, 1)[0], dist[0])

    def logp_entropy(self, actor: Params, latent, action):
        dist = self.actor_out(actor, latent)
        if self.s["continuous"]:
            mean, std = dist
            z = (action - mean) / std
            logp = (-0.5 * math.log(2 * math.pi) - jnp.log(std) - 0.5 * jnp.square(z)).sum(-1)
            entropy = (0.5 * math.log(2 * math.pi * math.e) + jnp.log(std)).sum(-1)
            return logp, entropy
        logp = jax.nn.log_softmax(dist[0], -1)
        return (action * logp).sum(-1), -(jnp.exp(logp) * logp).sum(-1)


# --------------------------------------------------------------------------- #
# one gradient step
# --------------------------------------------------------------------------- #


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree)))


def adam_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"mu": zeros, "nu": jax.tree.map(jnp.zeros_like, params), "count": jnp.zeros((), jnp.int32)}


def adam_step(params, grads, state, lr: float, eps: float, clip: float, b1: float = 0.9, b2: float = 0.999):
    """Global-norm clipping, then Adam with bias correction. Returns the new
    weights, the new state and the gradient as the optimizer got it."""
    norm = global_norm(grads)
    grads = jax.tree.map(lambda g: jnp.where(norm < clip, g, g / norm * clip), grads)
    count = state["count"] + 1
    mu = jax.tree.map(lambda m, g: b1 * m.astype(F32) + (1 - b1) * g.astype(F32), state["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v.astype(F32) + (1 - b2) * jnp.square(g.astype(F32)), state["nu"], grads)
    c1 = 1 - b1 ** count.astype(F32)
    c2 = 1 - b2 ** count.astype(F32)
    # sums in float32, kept in the type the state came in (bfloat16 under ``bfloat16_weights``)
    mu = jax.tree.map(lambda m, new: new.astype(m.dtype), state["mu"], mu)
    nu = jax.tree.map(lambda v, new: new.astype(v.dtype), state["nu"], nu)
    params = jax.tree.map(
        lambda p, m, v: (p - lr * (m.astype(F32) / c1) / (jnp.sqrt(v.astype(F32) / c2) + eps)).astype(p.dtype), params, mu, nu
    )
    return params, {"mu": mu, "nu": nu, "count": count}, grads


def lambda_returns(rewards, values, continues, lmbda: float):
    """``R_t = r_t + c_t ((1 - lambda) v_t + lambda R_{t+1})``, ``R_T = v_T``."""
    interm = rewards + continues * values * (1 - lmbda)

    def step(carry, xs):
        ret = xs[0] + xs[1] * lmbda * carry
        return ret, ret

    return lax.scan(step, values[-1], (interm, continues), reverse=True)[1]


def train_step(model: Model, state: Dict[str, Any], batch: Dict[str, Any], key) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """World model, actor and critic updated once on one ``[T, B]`` batch.
    ``state``: ``wm, actor, critic, target`` weights, their three Adam states
    and the return normaliser's ``low``/``high``. Returns the new state and
    the losses with the three gradients as the optimizers got them."""
    a, m, s = model.a, model.m, model.s
    sg = lax.stop_gradient
    k_scan, k_img = jax.random.split(key)
    T, B = batch["rewards"].shape[:2]
    is_first = batch["is_first"].at[0].set(1.0)
    prev_actions = jnp.concatenate([jnp.zeros_like(batch["actions"][:1]), batch["actions"][:-1]], 0)
    target_rgb = batch["rgb"].astype(F32) / 255.0 - 0.5
    continue_target = 1.0 - batch["terminated"]

    def world_loss(wm):
        embedded = model.encode(wm, batch)
        hs, zs, post, prior = model.observe(wm, embedded, prev_actions, is_first, k_scan)
        latent = jnp.concatenate([zs, hs], -1)
        observation = jnp.square(model.decode(wm, latent) - target_rgb).sum((-3, -2, -1))
        reward = -twohot_log_prob(head(model.pr, wm["reward"], latent), batch["rewards"])
        logit = head(model.pr, wm["cont"], latent)
        cont = a["continue_scale"] * -(
            -jnp.maximum(logit, 0) + logit * continue_target - jnp.log1p(jnp.exp(-jnp.abs(logit)))
        ).sum(-1)

        def kl(p_logits, q_logits):
            p, q = jax.nn.log_softmax(p_logits, -1), jax.nn.log_softmax(q_logits, -1)
            return (jnp.exp(p) * (p - q)).sum((-2, -1))

        dyn = kl(sg(post), prior)
        rep = kl(post, sg(prior))
        kl_loss = a["kl_dynamic"] * jnp.maximum(dyn, a["kl_free_nats"]) + a["kl_representation"] * jnp.maximum(
            rep, a["kl_free_nats"]
        )
        loss = (a["kl_regularizer"] * kl_loss + observation + reward + cont).mean()
        return loss, (hs, zs)

    (wm_loss, (hs, zs)), wm_grads = jax.value_and_grad(world_loss, has_aux=True)(state["wm"])
    wm, wm_opt, wm_grads = adam_step(state["wm"], wm_grads, state["wm_opt"], a["wm_lr"], a["wm_eps"], a["wm_clip"])

    start_z = sg(zs).reshape(T * B, -1)
    start_h = sg(hs).reshape(T * B, -1)
    true_continue = continue_target.reshape(T * B, 1)
    horizon, gamma = a["horizon"], a["gamma"]

    def actor_loss(actor):
        def step(carry, _):
            z, h, latent, key = carry
            key, k_act, k_state = jax.random.split(key, 3)
            action = model.act(actor, sg(latent), k_act)
            z, h = model.imagine_step(wm, z, h, action, k_state)
            return (z, h, jnp.concatenate([z, h], -1), key), (latent, action)

        first = jnp.concatenate([start_z, start_h], -1)
        _, (latents, actions) = lax.scan(step, (start_z, start_h, first, k_img), None, length=horizon + 1)
        values = twohot_mean(head(model.pr, state["critic"], latents))
        rewards = twohot_mean(head(model.pr, wm["reward"], latents))
        continues = (jax.nn.sigmoid(head(model.pr, wm["cont"], latents)) > 0.5).astype(F32)
        continues = jnp.concatenate([true_continue[None], continues[1:]], 0)
        returns = lambda_returns(rewards[1:], values[1:], continues[1:] * gamma, a["lmbda"])
        discount = sg(jnp.cumprod(continues * gamma, 0) / gamma)
        flat = sg(returns).astype(F32)
        low = a["moments_decay"] * state["low"] + (1 - a["moments_decay"]) * jnp.quantile(flat, a["moments_low"])
        high = a["moments_decay"] * state["high"] + (1 - a["moments_decay"]) * jnp.quantile(flat, a["moments_high"])
        scale = jnp.maximum(1.0 / a["moments_max"], high - low)
        advantage = (returns - low) / scale - (values[:-1] - low) / scale
        logp, entropy = model.logp_entropy(actor, sg(latents), sg(actions))
        objective = advantage if s["continuous"] else logp[..., None][:-1] * sg(advantage)
        loss = -jnp.mean(discount[:-1] * (objective + a["ent_coef"] * entropy[..., None][:-1]))
        return loss, (latents, returns, discount, low, high)

    (policy_loss, (latents, returns, discount, low, high)), actor_grads = jax.value_and_grad(actor_loss, has_aux=True)(
        state["actor"]
    )
    actor, actor_opt, actor_grads = adam_step(
        state["actor"], actor_grads, state["actor_opt"], a["actor_lr"], a["actor_eps"], a["actor_clip"]
    )

    traj = sg(latents[:-1])
    target_values = twohot_mean(head(model.pr, state["target"], traj))

    def critic_loss(critic):
        logits = head(model.pr, critic, traj)
        loss = -twohot_log_prob(logits, sg(returns)) - twohot_log_prob(logits, sg(target_values))
        return jnp.mean(loss * discount[:-1][..., 0])

    value_loss, critic_grads = jax.value_and_grad(critic_loss)(state["critic"])
    critic, critic_opt, critic_grads = adam_step(
        state["critic"], critic_grads, state["critic_opt"], a["critic_lr"], a["critic_eps"], a["critic_clip"]
    )
    new_state = {
        "wm": wm, "actor": actor, "critic": critic, "target": state["target"],
        "wm_opt": wm_opt, "actor_opt": actor_opt, "critic_opt": critic_opt, "low": low, "high": high,
    }  # fmt: skip
    out = {
        "losses": {"world_model": wm_loss, "policy": policy_loss, "value": value_loss},
        "grads": {"wm": wm_grads, "actor": actor_grads, "critic": critic_grads},
    }
    return new_state, out


def initial_state(params: Tuple[Params, Params, Params], dtype=F32) -> Dict[str, Any]:
    """A state of its own arrays, the weights in ``dtype``: ``train_step`` may
    be jitted with the state donated, and the caller keeps ``params`` to
    measure the change against."""
    wm, actor, critic = jax.tree.map(lambda x: jnp.array(x, dtype), params)
    return {
        "wm": wm, "actor": actor, "critic": critic, "target": jax.tree.map(jnp.copy, critic),
        "wm_opt": adam_init(wm), "actor_opt": adam_init(actor), "critic_opt": adam_init(critic),
        "low": jnp.zeros((), F32), "high": jnp.zeros((), F32),
    }  # fmt: skip


def refresh_target(state: Dict[str, Any], step: int, tau: float) -> Dict[str, Any]:
    """Before every gradient step the slow critic follows the critic: a copy
    before the first step, ``tau`` of the way after."""
    mix = 1.0 if step == 0 else tau
    target = jax.tree.map(lambda c, t: mix * c + (1 - mix) * t, state["critic"], state["target"])
    return {**state, "target": target}


def player_latent(model: Model, wm: Params, obs, h, z, prev_action, key):
    """What the agent does at every env step, up to its latent: encode, one
    posterior step without the first-step gate. ``key`` is the step's key.
    Returns the recurrent state, the sampled latent and the posterior's
    perturbed logits, ``[batch, stochastic, classes]``, whose argmax it is."""
    k1, _ = jax.random.split(key)
    embedded = model.encode(wm, obs)
    h = model.recurrent(wm, jnp.concatenate([z, prev_action], -1), h)
    post = model.logits(wm["repr"], jnp.concatenate([h, embedded], -1))
    noisy = post + jax.random.gumbel(k1, post.shape, post.dtype)
    return h, one_hot_sample(k1, post).reshape(z.shape[0], -1), noisy


def player_action(model: Model, actor: Params, h, z, key):
    """The action sampled at a latent with the step's key, and for discrete
    actions the perturbed logits whose argmax it is."""
    k2 = jax.random.split(key)[1]
    latent = jnp.concatenate([z, h], -1)
    action = model.act(actor, latent, k2)
    if model.s["continuous"]:
        return action, None
    logits = model.actor_out(actor, latent)[0]
    return action, logits + jax.random.gumbel(jax.random.split(k2, 1)[0], logits.shape, logits.dtype)
