"""The plain reference of the token policy and its PPO update: one chip's
share of a latent-attention, routed-expert decoder (the held experts of each
layer, the held rows of the vocabulary, the depth kept, the multi-token-
prediction module) in straightforward ``jax.numpy``.

Float32 under ``jax.default_matmul_precision("highest")``. No cache, no
kernels, no grouping, no batching: the full forward over one episode's tokens
from its first, every held expert on every token under a mask, PPO's clipped
loss with GAE, the value, entropy and multi-token-prediction terms, the
gradient and one AdamW step. It imports nothing of ``sheeprl_tpu``; the tree
of weights (``init_weights``) is the one thing the two sides agree on.

Departures and conventions the published configuration does not settle are
listed under ``assumed`` in the configuration's file.
"""

from __future__ import annotations

import zlib
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The model's sizes as the configuration's file states them (``model``)."""
    return dict(config["model"])


# --------------------------------------------------------------------------- #
# weights from a seed
# --------------------------------------------------------------------------- #


def _shapes(m: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    d, heads, held = m["hidden_size"], m["num_attention_heads"], len(m["held_experts"])
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]

    def layer(prefix: str, dense: bool) -> Dict[str, Tuple[int, ...]]:
        out = {
            f"{prefix}/attn_norm/scale": (d,),
            f"{prefix}/ffn_norm/scale": (d,),
            f"{prefix}/attn/q_a/kernel": (d, m["q_lora_rank"]),
            f"{prefix}/attn/q_norm/scale": (m["q_lora_rank"],),
            f"{prefix}/attn/q_b/kernel": (m["q_lora_rank"], heads * qk),
            f"{prefix}/attn/kv_a/kernel": (d, m["kv_lora_rank"] + m["qk_rope_head_dim"]),
            f"{prefix}/attn/kv_norm/scale": (m["kv_lora_rank"],),
            f"{prefix}/attn/kv_b/kernel": (m["kv_lora_rank"], heads * (m["qk_nope_head_dim"] + m["v_head_dim"])),
            f"{prefix}/attn/o/kernel": (heads * m["v_head_dim"], d),
        }
        if dense:
            inner = m["intermediate_size"]
            out.update({f"{prefix}/mlp/gate/kernel": (d, inner), f"{prefix}/mlp/up/kernel": (d, inner), f"{prefix}/mlp/down/kernel": (inner, d)})
            return out
        inner = m["moe_intermediate_size"]
        shared = inner * m["n_shared_experts"]
        out.update({
            f"{prefix}/moe/router/kernel": (d, m["n_routed_experts"]),
            f"{prefix}/moe/router/bias": (m["n_routed_experts"],),
            f"{prefix}/moe/experts/gate/kernel": (held, d, inner),
            f"{prefix}/moe/experts/up/kernel": (held, d, inner),
            f"{prefix}/moe/experts/down/kernel": (held, inner, d),
            f"{prefix}/moe/shared/gate/kernel": (d, shared),
            f"{prefix}/moe/shared/up/kernel": (d, shared),
            f"{prefix}/moe/shared/down/kernel": (shared, d),
        })  # fmt: skip
        return out

    shapes = {"embed/embedding": (m["vocab_rows"], d), "final_norm/scale": (d,), "head/kernel": (d, m["vocab_rows"]), "value_head/kernel": (d, 1)}
    for i in range(m["num_hidden_layers"]):
        shapes.update(layer(f"layers/{i}", dense=i < m["first_k_dense_replace"]))
    if m["num_nextn_predict_layers"]:
        shapes.update({"mtp/enorm/scale": (d,), "mtp/hnorm/scale": (d,), "mtp/eh_proj/kernel": (2 * d, d), "mtp/final_norm/scale": (d,)})
        shapes.update(layer("mtp/block", dense=False))
    return shapes


def init_weights(config: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The nested tree of float32 weights, each leaf from ``seed`` and its own
    path: kernels of variance ``1 / fan_in`` (so that activations, the router's
    scores and the logits spread as a trained model's do and not as a flat
    one's), norm scales near 1, the router's correction bias small and not 0.
    Made by one program, on the device."""
    shapes = sorted(_shapes(sizes(config)).items())

    def make(base):
        tree: Dict[str, Any] = {}
        for path, shape in shapes:
            noise = jax.random.normal(jax.random.fold_in(base, zlib.crc32(path.encode()) & 0x7FFFFFFF), shape, jnp.float32)
            if path.endswith("scale"):
                leaf = 1.0 + 0.1 * noise
            elif path.endswith("bias"):
                leaf = 0.05 * noise
            elif path.endswith("embedding"):
                leaf = noise
            else:
                leaf = noise * (shape[-2] ** -0.5)
            node = tree
            *parents, name = path.split("/")
            for part in parents:
                node = node.setdefault(part, {})
            node[name] = leaf
        return tree

    return jax.jit(make)(jax.random.PRNGKey(int(seed) % (2**31 - 1)))


def cast(weights: Any, precision: str) -> Any:
    """The controls' weights: the tree kept in ``bfloat16`` (and back, so the
    arithmetic that follows is the reference's own), or every leaf rounded to
    4 exponent and 3 mantissa bits."""
    if precision == "float32":
        return weights
    if precision == "bfloat16_weights":
        return jax.tree.map(lambda w: w.astype(jnp.bfloat16).astype(jnp.float32), weights)
    if precision == "float8":
        return jax.tree.map(lambda w: jax.lax.reduce_precision(w, 4, 3), weights)
    raise ValueError(precision)


# --------------------------------------------------------------------------- #
# the forward pass over one episode
# --------------------------------------------------------------------------- #


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, positions, theta):
    half = x.shape[-1] // 2
    angles = positions[:, None].astype(jnp.float32) * theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    while angles.ndim < x.ndim:
        angles = angles[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angles) - b * jnp.sin(angles), b * jnp.cos(angles) + a * jnp.sin(angles)], -1)


def _swiglu(w, x):
    return (jax.nn.silu(x @ w["gate"]["kernel"]) * (x @ w["up"]["kernel"])) @ w["down"]["kernel"]


def attention(w, m, x, positions, keys=None):
    """Multi-head latent attention over ``x [S, D]`` at ``positions [S]``,
    causal; ``keys [S]`` (optional) marks the slots that may be attended to."""
    S, H = x.shape[0], m["num_attention_heads"]
    nope, rope_d, vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    q = (_rms(x @ w["q_a"]["kernel"], w["q_norm"]["scale"], m["rms_norm_eps"]) @ w["q_b"]["kernel"]).reshape(S, H, nope + rope_d)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], positions, m["rope_theta"])
    kv = x @ w["kv_a"]["kernel"]
    c_kv = _rms(kv[:, : m["kv_lora_rank"]], w["kv_norm"]["scale"], m["rms_norm_eps"])
    k_rope = _rope(kv[:, m["kv_lora_rank"] :], positions, m["rope_theta"])  # one for all heads
    expanded = (c_kv @ w["kv_b"]["kernel"]).reshape(S, H, nope + vd)
    k_nope, v = expanded[..., :nope], expanded[..., nope:]
    score = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope) + jnp.einsum("qhr,kr->hqk", q_rope, k_rope)) / np.sqrt(nope + rope_d)
    seen = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    if keys is not None:
        seen = seen & keys[None, :]
    prob = jax.nn.softmax(jnp.where(seen[None], score, -1e30), -1)
    return jnp.einsum("hqk,khd->qhd", prob, v).reshape(S, H * vd) @ w["o"]["kernel"]


def expert_layer(w, m, x, without: Tuple[int, ...] = ()):
    """The shared expert plus the held experts' part of the routed sum, each
    held expert on every token under the mask of the tokens that chose it.
    ``without`` leaves held experts out (a planted fault)."""
    score = jax.nn.sigmoid(x @ w["router"]["kernel"])
    _, chosen = jax.lax.top_k(score + w["router"]["bias"], m["num_experts_per_tok"])
    weight = jnp.take_along_axis(score, chosen, -1)
    if m["norm_topk_prob"]:
        weight = weight / weight.sum(-1, keepdims=True)
    weight = weight * m["routed_scaling_factor"]
    y = _swiglu(w["shared"], x)
    for slot, expert in enumerate(m["held_experts"]):
        if expert in without:
            continue
        mine = jnp.where(chosen == expert, weight, 0.0).sum(-1, keepdims=True)
        one = {k: {"kernel": w["experts"][k]["kernel"][slot]} for k in ("gate", "up", "down")}
        y = y + mine * _swiglu(one, x)
    return y


def block(w, m, x, positions, without=(), keys=None):
    x = x + attention(w["attn"], m, _rms(x, w["attn_norm"]["scale"], m["rms_norm_eps"]), positions, keys)
    z = _rms(x, w["ffn_norm"]["scale"], m["rms_norm_eps"])
    return x + (_swiglu(w["mlp"], z) if "mlp" in w else expert_layer(w["moe"], m, z, without))


def trunk(weights, m, tokens, without=()):
    """The hidden state before the final norm, ``[S, D]``, of an episode's tokens from its first."""
    x = weights["embed"]["embedding"][tokens]
    positions = jnp.arange(tokens.shape[0])
    for i in range(m["num_hidden_layers"]):
        x = block(weights["layers"][str(i)], m, x, positions, without)
    return x


def logits_and_values(weights, m, h):
    z = _rms(h, weights["final_norm"]["scale"], m["rms_norm_eps"])
    return z @ weights["head"]["kernel"], (z @ weights["value_head"]["kernel"])[:, 0]


def forward(weights, m, tokens, without=()):
    """``(logits [S, V], values [S])`` of the full forward over ``tokens [S]``."""
    with jax.default_matmul_precision(HIGHEST):
        return logits_and_values(weights, m, trunk(weights, m, tokens, without))


def mtp_logits(weights, m, h, next_tokens, positions, without=(), keys=None):
    """The multi-token-prediction module on the slots ``h [S, D]``: logits for
    the token after ``next_tokens``. Its block attends over the slots ``keys``
    marks (a sequence's own steps) and no others."""
    w = weights["mtp"]
    joined = jnp.concatenate([_rms(h, w["hnorm"]["scale"], m["rms_norm_eps"]),
                              _rms(weights["embed"]["embedding"][next_tokens], w["enorm"]["scale"], m["rms_norm_eps"])], -1)  # fmt: skip
    x = block(w["block"], m, joined @ w["eh_proj"]["kernel"], positions, without, keys)
    return _rms(x, w["final_norm"]["scale"], m["rms_norm_eps"]) @ weights["head"]["kernel"]


# --------------------------------------------------------------------------- #
# PPO on recorded sequences
# --------------------------------------------------------------------------- #


def gae(rewards: np.ndarray, values: np.ndarray, dones: np.ndarray, next_value: np.ndarray, gamma: float, lam: float):
    """``(returns, advantages)`` over a ``[T, E]`` rollout; ``dones[t]`` ends the episode at step ``t``."""
    T = rewards.shape[0]
    adv = np.zeros_like(values, dtype=np.float64)
    last = np.zeros(values.shape[1:], np.float64)
    for t in reversed(range(T)):
        nxt = next_value if t == T - 1 else values[t + 1]
        alive = 1.0 - dones[t]
        delta = rewards[t] + gamma * nxt * alive - values[t]
        last = delta + gamma * lam * alive * last
        adv[t] = last
    return (adv + values).astype(np.float32), adv.astype(np.float32)


def sequence_terms(weights, m, a, seq, without=()):
    """The summed loss terms of one recorded sequence, every array aligned to
    the episode's positions and padded to one length: ``tokens [S]`` (the
    inputs from the episode's first: the prompt, then the tokens taken),
    ``steps [S]`` (1 at the positions whose output was a step of this
    sequence) and, at those positions, ``actions``, ``logprobs``,
    ``advantages``, ``returns``, ``values``. Returns sums of the policy,
    value, entropy and MTP terms, the MTP term's count, and the sum of the
    policy terms' magnitudes (``policy_abs``: the scale a gap of the policy
    loss, whose terms cancel, is held against)."""
    steps = seq["steps"]
    h = trunk(weights, m, seq["tokens"], without)
    logits, values = logits_and_values(weights, m, h)
    logp_all = jax.nn.log_softmax(logits, -1)
    logp = jnp.take_along_axis(logp_all, seq["actions"][:, None], -1)[:, 0]
    ratio = jnp.exp(logp - seq["logprobs"])
    adv = seq["advantages"]
    pg = -jnp.minimum(adv * ratio, adv * jnp.clip(ratio, 1 - a["clip_coef"], 1 + a["clip_coef"]))
    v = jnp.square(values - seq["returns"])
    ent = (jnp.exp(logp_all) * logp_all).sum(-1)  # minus the entropy
    out = {"policy": (pg * steps).sum(), "policy_abs": (jnp.abs(pg) * steps).sum(), "value": (v * steps).sum(), "entropy": (ent * steps).sum(),
           "mtp": jnp.zeros(()), "mtp_n": jnp.zeros(())}  # fmt: skip
    if m["num_nextn_predict_layers"]:
        # at step t the module sees the trunk's state and the token taken at t, and predicts the token taken at t + 1
        extra = mtp_logits(weights, m, h, seq["actions"], jnp.arange(steps.shape[0]), without, keys=steps > 0)
        target = jnp.concatenate([seq["actions"][1:], seq["actions"][:1]])
        both = steps * jnp.concatenate([steps[1:], jnp.zeros((1,), steps.dtype)])
        ce = -jnp.take_along_axis(jax.nn.log_softmax(extra, -1), target[:, None], -1)[:, 0]
        out.update({"mtp": (ce * both).sum(), "mtp_n": both.sum()})
    return out


def loss_and_grad(weights, m, a, sequences: List[Dict[str, Any]], without=()):
    """The minibatch's loss terms (means over its steps) and the gradient of
    ``policy + vf_coef value + ent_coef entropy + mtp_loss_coef mtp``, one
    sequence at a time (sequences of one padded length share a compilation)."""
    steps = float(sum(s["steps"].sum() for s in sequences))
    mtp_steps = float(sum((s["steps"][:-1] * s["steps"][1:]).sum() for s in sequences)) if m["num_nextn_predict_layers"] else 0.0

    def total(w, seq):
        t = sequence_terms(w, m, a, seq, without)
        loss = (t["policy"] + a["vf_coef"] * t["value"] + a["ent_coef"] * t["entropy"]) / steps
        if mtp_steps:
            loss = loss + a["mtp_loss_coef"] * t["mtp"] / mtp_steps
        return loss, t

    grad_fn = jax.jit(jax.value_and_grad(total, has_aux=True))
    sums = {"policy": 0.0, "policy_abs": 0.0, "value": 0.0, "entropy": 0.0, "mtp": 0.0}
    grads = None
    with jax.default_matmul_precision(HIGHEST):
        for seq in sequences:
            (_, terms), g = grad_fn(weights, {k: jnp.asarray(v) for k, v in seq.items()})
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
            for k in sums:
                sums[k] += float(terms[k])
    losses = {"policy_loss": sums["policy"] / steps, "value_loss": sums["value"] / steps, "entropy_loss": sums["entropy"] / steps,
              "mtp_loss": sums["mtp"] / mtp_steps if mtp_steps else 0.0, "policy_scale": sums["policy_abs"] / steps}  # fmt: skip
    return losses, grads


def losses_only(weights, m, a, sequences: List[Dict[str, Any]], without=()):
    """The minibatch's loss terms at ``weights``, no gradient."""
    terms_fn = jax.jit(lambda w, seq: sequence_terms(w, m, a, seq, without))
    sums = {"policy": 0.0, "policy_abs": 0.0, "value": 0.0, "entropy": 0.0, "mtp": 0.0, "mtp_n": 0.0}
    with jax.default_matmul_precision(HIGHEST):
        for seq in sequences:
            terms = terms_fn(weights, {k: jnp.asarray(v) for k, v in seq.items()})
            for k in sums:
                sums[k] += float(terms[k])
    steps = float(sum(s["steps"].sum() for s in sequences))
    return {"policy_loss": sums["policy"] / steps, "value_loss": sums["value"] / steps, "entropy_loss": sums["entropy"] / steps,
            "mtp_loss": sums["mtp"] / sums["mtp_n"] if sums["mtp_n"] else 0.0, "policy_scale": sums["policy_abs"] / steps}  # fmt: skip


def clip_by_global_norm(grads, max_norm: float):
    if not max_norm or max_norm <= 0:
        return grads
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    return jax.tree.map(lambda g: g * (max_norm / jnp.maximum(norm, max_norm)), grads)


def adamw_first_step(weights, grads, a):
    """The weights after AdamW's first step from a zero state (decoupled weight
    decay on every leaf, as ``optax.adamw`` without a mask): with the moments'
    bias corrected, the step is ``g / (|g| + eps)``."""
    lr, eps, wd = a["lr"], a["eps"], a["weight_decay"]
    return jax.tree.map(jax.jit(lambda w, g: w - lr * (g / (jnp.abs(g) + eps) + wd * w)), weights, grads)
