"""The plain reference of the hybrid token policy (``lfm2_moe``: gated short
convolutions beside grouped-query attention, over a dense SwiGLU or a routed
expert layer) and its PPO update: one chip's share of the model (the held
experts of each layer, the held rows of the vocabulary, the depth kept) in
straightforward ``jax.numpy``.

Float32 under ``jax.default_matmul_precision("highest")``. No cache, no state,
no kernels, no grouping, no batching: the full forward over one episode's
tokens from its first, every held expert on every token under a mask, PPO's
clipped loss with GAE, the value and entropy terms, the gradient and one AdamW
step. It imports nothing of ``sheeprl_tpu``; the tree of weights
(``init_weights``) is the one thing the two sides agree on. What is no model's
(GAE, the clip by global norm, AdamW's first step, the controls' rounding) is
``references/token_ppo.py``'s.

The layer equations, with ``D`` the hidden size and ``n(x) = x /
sqrt(mean(x^2) + eps) * g``:

- Block ``l``: ``h = x + op_l(n_op(x))``; ``y = h + ffn_l(n_ffn(h))``. ``op_l``
  is the convolution or the attention by ``layer_types[l]``; ``ffn_l`` is the
  dense SwiGLU for ``l < first_k_dense_replace`` (``num_dense_layers``), else
  the expert layer. After the last block one more norm, then the head (the
  embedding's rows, transposed: tied) and the value head.
- Gated short convolution: ``[B, C, X] = split3(W_in u)`` (``W_in``: D -> 3D,
  no bias); ``z_t = B_t * X_t``; ``c_t = sum_{j=0..L-1} w[j] * z_{t-(L-1)+j}``
  per channel (depthwise, causal, ``L = conv_L_cache``, ``z`` before the
  episode's first token zero); ``out_t = W_out (C_t * c_t)``. The kernel is
  kept as ``w [L, D]`` (the published tensor is ``[D, 1, L]``).
- Attention: ``q = W_q u`` as H heads of d, ``k = W_k u``, ``v = W_v u`` as G
  heads of d; ``q`` and ``k`` RMS-normed over the d dims with a learned scale,
  then rotary (rotate-half pairing, all d dims); causal softmax of ``q k^T /
  sqrt(d)`` with query head ``i`` reading key-value head ``i // (H / G)``;
  ``W_o`` on the concatenation.
- Expert layer: ``s = sigmoid(W_r u)`` over all experts; the choice is the top
  k of ``s + bias``; the weights are ``s`` at the chosen, divided by their sum
  plus ``router_eps``, times ``routed_scaling_factor``; expert ``e`` is
  ``W2_e(silu(W1_e u) * W3_e u)``. No shared expert. Only the held experts'
  part of the sum is computed.

Departures and conventions the published configuration does not settle are
listed under ``assumed`` in the configuration's file.
"""

from __future__ import annotations

import zlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.references.token_ppo import adamw_first_step, cast, clip_by_global_norm, gae  # noqa: F401  (no model's)

HIGHEST = "highest"
CONV, ATTENTION = "conv", "full_attention"


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The model's sizes as the configuration's file states them (``model``)."""
    return dict(config["model"])


# --------------------------------------------------------------------------- #
# weights from a seed
# --------------------------------------------------------------------------- #


def _shapes(m: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    d, held = m["hidden_size"], len(m["held_experts"])
    H, G, hd, L = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"], m["conv_L_cache"]
    shapes = {"embed/embedding": (m["vocab_rows"], d), "final_norm/scale": (d,), "value_head/kernel": (d, 1)}
    if not m["tie_word_embeddings"]:
        shapes["head/kernel"] = (d, m["vocab_rows"])
    for i, kind in enumerate(m["layer_types"]):
        prefix = f"layers/{i}"
        shapes.update({f"{prefix}/attn_norm/scale": (d,), f"{prefix}/ffn_norm/scale": (d,)})  # ``attn_norm``: the norm before the operator
        if kind == CONV:
            shapes.update({f"{prefix}/conv/in_proj/kernel": (d, 3 * d), f"{prefix}/conv/conv/kernel": (L, d), f"{prefix}/conv/out_proj/kernel": (d, d)})
        else:
            shapes.update({f"{prefix}/attn/q/kernel": (d, H * hd), f"{prefix}/attn/q_norm/scale": (hd,), f"{prefix}/attn/k/kernel": (d, G * hd),
                           f"{prefix}/attn/k_norm/scale": (hd,), f"{prefix}/attn/v/kernel": (d, G * hd), f"{prefix}/attn/o/kernel": (H * hd, d)})  # fmt: skip
        if i < m["first_k_dense_replace"]:
            inner = m["intermediate_size"]
            shapes.update({f"{prefix}/mlp/gate/kernel": (d, inner), f"{prefix}/mlp/up/kernel": (d, inner), f"{prefix}/mlp/down/kernel": (inner, d)})
        else:
            inner = m["moe_intermediate_size"]
            shapes.update({f"{prefix}/moe/router/kernel": (d, m["n_routed_experts"]), f"{prefix}/moe/router/bias": (m["n_routed_experts"],),
                           f"{prefix}/moe/experts/gate/kernel": (held, d, inner), f"{prefix}/moe/experts/up/kernel": (held, d, inner),
                           f"{prefix}/moe/experts/down/kernel": (held, inner, d)})  # fmt: skip
    return shapes


def init_weights(config: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The nested tree of float32 weights, each leaf from ``seed`` and its own
    path: kernels of variance ``1 / fan_in`` (so that activations, the router's
    scores and the logits spread as a trained model's do and not as a flat
    one's), norm scales near 1, the router's correction bias small and not 0.
    The embedding, whose rows are the head's too (tied), has the head's
    variance ``1 / D``: every block norms its input, so the scale of the
    residual stream's first term decides nothing. Made by one program, on the
    device."""
    m = sizes(config)
    shapes = sorted(_shapes(m).items())

    def make(base):
        tree: Dict[str, Any] = {}
        for path, shape in shapes:
            noise = jax.random.normal(jax.random.fold_in(base, zlib.crc32(path.encode()) & 0x7FFFFFFF), shape, jnp.float32)
            if path.endswith("scale"):
                leaf = 1.0 + 0.1 * noise
            elif path.endswith("bias"):
                leaf = 0.05 * noise
            elif path.endswith("embedding"):
                leaf = noise * (shape[-1] ** -0.5 if m["tie_word_embeddings"] else 1.0)
            else:
                leaf = noise * (shape[-2] ** -0.5)
            node = tree
            *parents, name = path.split("/")
            for part in parents:
                node = node.setdefault(part, {})
            node[name] = leaf
        return tree

    return jax.jit(make)(jax.random.PRNGKey(int(seed) % (2**31 - 1)))


# --------------------------------------------------------------------------- #
# the forward pass over one episode
# --------------------------------------------------------------------------- #


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, positions, theta):
    """``x [S, heads, d]`` rotated by ``positions [S]``: pairs ``(i, i + d/2)``, all ``d`` dims."""
    half = x.shape[-1] // 2
    angles = positions[:, None, None].astype(jnp.float32) * theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angles) - b * jnp.sin(angles), b * jnp.cos(angles) + a * jnp.sin(angles)], -1)


def _swiglu(w, x):
    return (jax.nn.silu(x @ w["gate"]["kernel"]) * (x @ w["up"]["kernel"])) @ w["down"]["kernel"]


def conv(w, m, x, kept: Optional[Tuple[Any, Any]] = None):
    """The gated short convolution over ``x [S, D]``; returns the output and
    the gated inputs ``z [S, D]``. ``kept = (p, held [L - 1, D])`` plants a
    fault: from position ``p`` on (a player's first decode of the episode)
    every gated input from before ``p`` reads as what a state ``held`` that no
    reset cleared and no prefill replaced (its latest entry last)."""
    S, L = x.shape[0], m["conv_L_cache"]
    b, c, xx = jnp.split(x @ w["in_proj"]["kernel"], 3, axis=-1)
    z = b * xx
    padded = jnp.concatenate([jnp.zeros((L - 1, z.shape[1]), z.dtype), z])
    window = jnp.stack([padded[j : j + S] for j in range(L)], axis=1)  # [S, L, D]: entry j of row t is z_{t - (L-1) + j}
    if kept is not None:
        p, held = kept
        t = jnp.arange(S)[:, None]
        source = t - (L - 1) + jnp.arange(L)[None, :]  # the position each window entry comes from
        stale = (t >= p) & (source < p)  # what the state would have held of the episode before
        from_held = jnp.clip(source - p + (L - 1), 0, L - 2)  # the state moved on ``t - p + 1`` entries since
        window = jnp.where(stale[..., None], held[from_held], window)
    mixed = (window * w["conv"]["kernel"][None]).sum(1)
    return (c * mixed) @ w["out_proj"]["kernel"], z


def attention(w, m, x, positions):
    """Grouped-query attention over ``x [S, D]`` at ``positions [S]``, causal."""
    S, H, G, hd = x.shape[0], m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    q = _rope(_rms((x @ w["q"]["kernel"]).reshape(S, H, hd), w["q_norm"]["scale"], m["rms_norm_eps"]), positions, m["rope_theta"])
    k = _rope(_rms((x @ w["k"]["kernel"]).reshape(S, G, hd), w["k_norm"]["scale"], m["rms_norm_eps"]), positions, m["rope_theta"])
    v = (x @ w["v"]["kernel"]).reshape(S, G, hd)
    k, v = jnp.repeat(k, H // G, axis=1), jnp.repeat(v, H // G, axis=1)  # query head i reads key-value head i // (H / G)
    score = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
    seen = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    prob = jax.nn.softmax(jnp.where(seen[None], score, -1e30), -1)
    return jnp.einsum("hqk,khd->qhd", prob, v).reshape(S, H * hd) @ w["o"]["kernel"]


def expert_layer(w, m, x, without: Tuple[int, ...] = ()):
    """The held experts' part of the routed sum (and the shared expert, had
    the layer one), each held expert on every token under the mask of the
    tokens that chose it. ``without`` leaves held experts out (a planted fault)."""
    score = jax.nn.sigmoid(x @ w["router"]["kernel"])
    _, chosen = jax.lax.top_k(score + w["router"]["bias"], m["num_experts_per_tok"])
    weight = jnp.take_along_axis(score, chosen, -1)
    if m["norm_topk_prob"]:
        weight = weight / (weight.sum(-1, keepdims=True) + m["router_eps"])
    weight = weight * m["routed_scaling_factor"]
    y = _swiglu(w["shared"], x) if "shared" in w else jnp.zeros_like(x)
    for slot, expert in enumerate(m["held_experts"]):
        if expert in without:
            continue
        mine = jnp.where(chosen == expert, weight, 0.0).sum(-1, keepdims=True)
        one = {k: {"kernel": w["experts"][k]["kernel"][slot]} for k in ("gate", "up", "down")}
        y = y + mine * _swiglu(one, x)
    return y


def trunk(weights, m, tokens, without=(), kept=None):
    """The hidden state before the final norm, ``[S, D]``, of an episode's
    tokens from its first, and each convolution layer's gated inputs. ``kept =
    (p, [held of each convolution layer])`` plants :func:`conv`'s fault."""
    x = weights["embed"]["embedding"][tokens]
    positions = jnp.arange(tokens.shape[0])
    gated = []
    for i, kind in enumerate(m["layer_types"]):
        w = weights["layers"][str(i)]
        u = _rms(x, w["attn_norm"]["scale"], m["rms_norm_eps"])
        if kind == CONV:
            out, z = conv(w["conv"], m, u, None if kept is None else (kept[0], kept[1][len(gated)]))
            gated.append(z)
        else:
            out = attention(w["attn"], m, u, positions)
        x = x + out
        z = _rms(x, w["ffn_norm"]["scale"], m["rms_norm_eps"])
        x = x + (_swiglu(w["mlp"], z) if "mlp" in w else expert_layer(w["moe"], m, z, without))
    return x, gated


def logits_and_values(weights, m, h):
    z = _rms(h, weights["final_norm"]["scale"], m["rms_norm_eps"])
    head = weights["embed"]["embedding"].T if m["tie_word_embeddings"] else weights["head"]["kernel"]
    return z @ head, (z @ weights["value_head"]["kernel"])[:, 0]


def forward(weights, m, tokens, without=(), kept=None):
    """``(logits [S, V], values [S], gated inputs of each convolution layer)``
    of the full forward over ``tokens [S]``."""
    with jax.default_matmul_precision(HIGHEST):
        h, gated = trunk(weights, m, tokens, without, kept)
        return (*logits_and_values(weights, m, h), gated)


# --------------------------------------------------------------------------- #
# PPO on recorded sequences
# --------------------------------------------------------------------------- #


def sequence_terms(weights, m, a, seq, without=()):
    """The summed loss terms of one recorded sequence, every array aligned to
    the episode's positions and padded to one length: ``tokens [S]`` (the
    inputs from the episode's first: the prompt, then the tokens taken),
    ``steps [S]`` (1 at the positions whose output was a step of this
    sequence) and, at those positions, ``actions``, ``logprobs``,
    ``advantages``, ``returns``, ``values``. Returns sums of the policy, value
    and entropy terms and of the policy terms' magnitudes (``policy_abs``: the
    scale a gap of the policy loss, whose terms cancel, is held against)."""
    steps = seq["steps"]
    logits, values = logits_and_values(weights, m, trunk(weights, m, seq["tokens"], without)[0])
    logp_all = jax.nn.log_softmax(logits, -1)
    logp = jnp.take_along_axis(logp_all, seq["actions"][:, None], -1)[:, 0]
    ratio = jnp.exp(logp - seq["logprobs"])
    adv = seq["advantages"]
    pg = -jnp.minimum(adv * ratio, adv * jnp.clip(ratio, 1 - a["clip_coef"], 1 + a["clip_coef"]))
    v = jnp.square(values - seq["returns"])
    ent = (jnp.exp(logp_all) * logp_all).sum(-1)  # minus the entropy
    return {"policy": (pg * steps).sum(), "policy_abs": (jnp.abs(pg) * steps).sum(), "value": (v * steps).sum(), "entropy": (ent * steps).sum()}


def _means(sums: Dict[str, float], steps: float) -> Dict[str, float]:
    return {"policy_loss": sums["policy"] / steps, "value_loss": sums["value"] / steps, "entropy_loss": sums["entropy"] / steps,
            "policy_scale": sums["policy_abs"] / steps}  # fmt: skip


def loss_and_grad(weights, m, a, sequences: List[Dict[str, Any]], without=()):
    """The minibatch's loss terms (means over its steps) and the gradient of
    ``policy + vf_coef value + ent_coef entropy``, one sequence at a time
    (sequences of one padded length share a compilation)."""
    steps = float(sum(s["steps"].sum() for s in sequences))

    def total(w, seq):
        t = sequence_terms(w, m, a, seq, without)
        return (t["policy"] + a["vf_coef"] * t["value"] + a["ent_coef"] * t["entropy"]) / steps, t

    grad_fn = jax.jit(jax.value_and_grad(total, has_aux=True))
    sums = {"policy": 0.0, "policy_abs": 0.0, "value": 0.0, "entropy": 0.0}
    grads = None
    with jax.default_matmul_precision(HIGHEST):
        for seq in sequences:
            (_, terms), g = grad_fn(weights, {k: jnp.asarray(v) for k, v in seq.items()})
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
            for k in sums:
                sums[k] += float(terms[k])
    return _means(sums, steps), grads


def losses_only(weights, m, a, sequences: Sequence[Dict[str, Any]], without=()):
    """The minibatch's loss terms at ``weights``, no gradient."""
    terms_fn = jax.jit(lambda w, seq: sequence_terms(w, m, a, seq, without))
    sums = {"policy": 0.0, "policy_abs": 0.0, "value": 0.0, "entropy": 0.0}
    with jax.default_matmul_precision(HIGHEST):
        for seq in sequences:
            terms = terms_fn(weights, {k: jnp.asarray(v) for k, v in seq.items()})
            for k in sums:
                sums[k] += float(terms[k])
    return _means(sums, float(sum(s["steps"].sum() for s in sequences)))
