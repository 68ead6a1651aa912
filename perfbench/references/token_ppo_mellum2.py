"""The plain reference of the window-and-full-attention token policy
(``mellum``: three sliding-window attention layers beside one full-attention
layer under YaRN, each over a routed expert layer with a softmax router) and
its PPO update: one chip's share of the model (the held experts of each layer,
the held rows of the vocabulary, the depth kept) in straightforward
``jax.numpy``.

Float32 under ``jax.default_matmul_precision("highest")``. No cache, no ring,
no kernels, no grouping, no batching: the full forward over one episode's
tokens from its first with the window as a band in the mask over positions,
every held expert on every token under a mask, PPO's clipped loss with GAE,
the value and entropy terms, the gradient and one AdamW step. It imports
nothing of ``sheeprl_tpu``; the tree of weights (``init_weights``) is the one
thing the two sides agree on. What is no model's (GAE, the clip by global
norm, AdamW's first step, the controls' rounding) is ``references/token_ppo.py``'s,
and PPO's terms over a recorded sequence are ``references/token_ppo_lfm2.py``'s
with this model's forward put in.

The layer equations, with ``D`` the hidden size 2,304, ``H`` 32 query heads,
``G`` 4 key-value heads, ``d`` 128, ``W`` the window 1,024 and ``n(x) = x /
sqrt(mean(x^2) + 1e-6) * g``:

- Block ``l``: ``h = x + attn_l(n_1(x))``; ``y = h + moe(n_2(h))``. After the
  last block one more norm, then the head (``W_head``: D -> vocabulary rows,
  its own matrix) and the value head.
- Attention, both kinds: ``q = W_q u`` as H heads of d, ``k = W_k u``, ``v =
  W_v u`` as G heads of d, no bias; ``q`` and ``k`` RMS-normed over the d dims
  with a learned scale, then rotary on all d dims (rotate-half pairing ``(i, i
  + d/2)``); scores ``q k^T / sqrt(d)``, query head ``i`` reading key-value
  head ``i // (H / G)``; softmax in float32; ``W_o``: H d -> D.
- ``sliding_attention``: a query at position ``q`` sees the keys at ``q - W <
  p <= q`` (W keys with its own). Rotary: ``inv_freq_i = theta^(-i / (d/2))``.
- ``full_attention``: every ``p <= q``. Rotary is YaRN's, fixed whatever the
  length: with ``f_i = theta^(-i / (d/2))``, ``c(r) = d ln(original context /
  (2 pi r)) / (2 ln theta)``, ``low = max(floor(c(beta_fast)), 0)``, ``high =
  min(ceil(c(beta_slow)), d - 1)``, ``ramp_i = clip((i - low) / (high - low),
  0, 1)``: ``inv_freq_i = (f_i / factor) ramp_i + f_i (1 - ramp_i)``, and
  cosine and sine are both multiplied by ``attention_factor`` (a score carries
  its square). At the published values ``low`` is 18 and ``high`` 35.
- Expert layer: ``s = softmax(W_r u)`` over all experts; the choice is the top
  k of ``s``, no bias; the weights are ``s`` at the chosen over their sum;
  expert ``e`` is ``W2_e(silu(W1_e u) * W3_e u)``. No shared expert, no
  scaling factor. Only the held experts' part of the sum is computed.

Departures and conventions the published configuration does not settle are
listed under ``assumed`` in the configuration's file.
"""

from __future__ import annotations

import math
import zlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.references.token_ppo import adamw_first_step, cast, clip_by_global_norm, gae  # noqa: F401  (no model's)

HIGHEST = "highest"
SLIDING, FULL = "sliding_attention", "full_attention"
#: the planted faults of the forward: the window layers attend to the whole episode; the full layer is rotated by the default table
WINDOW_IGNORED, YARN_LEFT_OUT = "window_ignored", "yarn_left_out"


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The model's sizes as the configuration's file states them (``model``)."""
    return dict(config["model"])


# --------------------------------------------------------------------------- #
# weights from a seed
# --------------------------------------------------------------------------- #


def _shapes(m: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    d, held, inner = m["hidden_size"], len(m["held_experts"]), m["moe_intermediate_size"]
    H, G, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    shapes = {"embed/embedding": (m["vocab_rows"], d), "head/kernel": (d, m["vocab_rows"]), "final_norm/scale": (d,), "value_head/kernel": (d, 1)}
    for i in range(len(m["layer_types"])):
        prefix = f"layers/{i}"
        shapes.update({f"{prefix}/attn_norm/scale": (d,), f"{prefix}/ffn_norm/scale": (d,),
                       f"{prefix}/attn/q/kernel": (d, H * hd), f"{prefix}/attn/q_norm/scale": (hd,), f"{prefix}/attn/k/kernel": (d, G * hd),
                       f"{prefix}/attn/k_norm/scale": (hd,), f"{prefix}/attn/v/kernel": (d, G * hd), f"{prefix}/attn/o/kernel": (H * hd, d),
                       f"{prefix}/moe/router/kernel": (d, m["n_routed_experts"]),
                       f"{prefix}/moe/experts/gate/kernel": (held, d, inner), f"{prefix}/moe/experts/up/kernel": (held, d, inner),
                       f"{prefix}/moe/experts/down/kernel": (held, inner, d)})  # fmt: skip
    return shapes


def init_weights(config: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The nested tree of float32 weights, each leaf from ``seed`` and its own
    path: kernels of variance ``1 / fan_in`` (so that activations, the
    router's scores and the logits spread as a trained model's do and not as a
    flat one's: the head's ``1 / D`` gives logits of unit spread), norm scales
    near 1, the embedding of unit variance (every block norms its input, so the
    scale of the residual stream's first term decides nothing). Made by one
    program, on the device."""
    shapes = sorted(_shapes(sizes(config)).items())

    def make(base):
        tree: Dict[str, Any] = {}
        for path, shape in shapes:
            noise = jax.random.normal(jax.random.fold_in(base, zlib.crc32(path.encode()) & 0x7FFFFFFF), shape, jnp.float32)
            if path.endswith("scale"):
                leaf = 1.0 + 0.1 * noise
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


# --------------------------------------------------------------------------- #
# the forward pass over one episode
# --------------------------------------------------------------------------- #


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rotary_table(table: Dict[str, Any], dim: int) -> Tuple[np.ndarray, float]:
    """``(inv_freq [dim / 2], what cosine and sine are multiplied by)`` of one
    entry of ``rope_parameters``: the default table, or YaRN's (module docstring)."""
    i = np.arange(dim // 2, dtype=np.float64)
    f = float(table["rope_theta"]) ** (-i / (dim // 2))
    if table["rope_type"] == "default":
        return f.astype(np.float32), 1.0

    def c(r):
        return dim * math.log(table["original_max_position_embeddings"] / (2 * math.pi * r)) / (2 * math.log(table["rope_theta"]))

    low, high = max(math.floor(c(table["beta_fast"])), 0), min(math.ceil(c(table["beta_slow"])), dim - 1)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return (f / table["factor"] * ramp + f * (1.0 - ramp)).astype(np.float32), float(table["attention_factor"])


def _rope(x, positions, table):
    """``x [S, heads, d]`` rotated by ``positions [S]`` under one table: pairs ``(i, i + d/2)``, all ``d`` dims."""
    half = x.shape[-1] // 2
    inv_freq, factor = rotary_table(table, x.shape[-1])
    angles = positions[:, None, None].astype(jnp.float32) * jnp.asarray(inv_freq)
    cos, sin = factor * jnp.cos(angles), factor * jnp.sin(angles)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _swiglu(w, x):
    return (jax.nn.silu(x @ w["gate"]["kernel"]) * (x @ w["up"]["kernel"])) @ w["down"]["kernel"]


def attention(w, m, x, positions, kind, faults: Tuple[str, ...] = (), kept: Optional[Tuple[Any, int, Any, Any]] = None):
    """Grouped-query attention over ``x [S, D]`` at ``positions [S]``, causal,
    inside the window for a layer of ``kind`` :data:`SLIDING`. Returns the
    output and the layer's keys and values ``[S, G, d]``.

    ``kept = (p, n, ring_k [W, G, d], ring_v [W, G, d])`` plants the fault of a
    ring that is never reset nor prefilled, for the ``n`` queries from
    position ``p`` on (a player's decodes of this episode; the outputs before
    ``p`` are a prefill's and nobody reads them): such a query sees every
    entry of a ring that began as ``ring_k, ring_v`` (what the episodes before
    left, entry ``e`` the key of a position ``= e mod W``) and into which the
    decodes from ``p`` on, and no prompt, were written."""
    S, H, G, hd, W = x.shape[0], m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"], m["sliding_window"]
    table = m["rope_parameters"][SLIDING if YARN_LEFT_OUT in faults else kind]
    q = _rope(_rms((x @ w["q"]["kernel"]).reshape(S, H, hd), w["q_norm"]["scale"], m["rms_norm_eps"]), positions, table)
    k = _rope(_rms((x @ w["k"]["kernel"]).reshape(S, G, hd), w["k_norm"]["scale"], m["rms_norm_eps"]), positions, table)
    v = (x @ w["v"]["kernel"]).reshape(S, G, hd)
    wide = lambda a: jnp.repeat(a, H // G, axis=1)  # noqa: E731  (query head i reads key-value head i // (H / G))
    seen = positions[:, None] >= positions[None, :]
    if kind == SLIDING and WINDOW_IGNORED not in faults:
        seen = seen & (positions[:, None] - positions[None, :] < W)
    score = jnp.einsum("qhd,khd->hqk", q, wide(k)) / np.sqrt(hd)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(jnp.where(seen[None], score, -1e30), -1), wide(v)).reshape(S, H * hd)
    if kept is not None and kind == SLIDING:
        p, n, ring_k, ring_v = kept
        at = p + jnp.arange(n)  # the faulty queries' positions
        qf = jax.lax.dynamic_slice_in_dim(q, p, n)
        # its own episode's keys: those a decode wrote (from ``p`` on), while no later one has taken their entry
        own_seen = (positions[None, :] >= p) & (positions[None, :] <= at[:, None]) & (at[:, None] - positions[None, :] < W)
        # the ring's entry ``e`` is first overwritten by the decode at ``p + ((e - p) mod W)``
        stale_seen = p + jnp.mod(jnp.arange(W)[None, :] - p, W) > at[:, None]
        score = jnp.concatenate([jnp.einsum("qhd,khd->hqk", qf, wide(k)), jnp.einsum("qhd,khd->hqk", qf, wide(ring_k))], -1) / np.sqrt(hd)
        prob = jax.nn.softmax(jnp.where(jnp.concatenate([own_seen, stale_seen], -1)[None], score, -1e30), -1)
        faulty = jnp.einsum("hqk,khd->qhd", prob, jnp.concatenate([wide(v), wide(ring_v)])).reshape(n, H * hd)
        out = jax.lax.dynamic_update_slice_in_dim(out, faulty, p, 0)
    return out @ w["o"]["kernel"], k, v


def expert_layer(w, m, x, without: Tuple[int, ...] = ()):
    """The held experts' part of the routed sum, each held expert on every
    token under the mask of the tokens that chose it. ``without`` leaves held
    experts out (a planted fault)."""
    score = jax.nn.softmax(x @ w["router"]["kernel"], -1)
    _, chosen = jax.lax.top_k(score, m["num_experts_per_tok"])
    weight = jnp.take_along_axis(score, chosen, -1)
    weight = weight / weight.sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    for slot, expert in enumerate(m["held_experts"]):
        if expert in without:
            continue
        mine = jnp.where(chosen == expert, weight, 0.0).sum(-1, keepdims=True)
        one = {k: {"kernel": w["experts"][k]["kernel"][slot]} for k in ("gate", "up", "down")}
        y = y + mine * _swiglu(one, x)
    return y


def trunk(weights, m, tokens, without=(), faults: Tuple[str, ...] = (), kept=None):
    """The hidden state before the final norm, ``[S, D]``, of an episode's
    tokens from its first, and each layer's keys and values. ``kept = (p, n,
    [(ring_k, ring_v) of each window layer])`` plants :func:`attention`'s fault."""
    x = weights["embed"]["embedding"][tokens]
    positions = jnp.arange(tokens.shape[0])
    entries = []
    for i, kind in enumerate(m["layer_types"]):
        w = weights["layers"][str(i)]
        ring = None if kept is None or kind != SLIDING else (kept[0], kept[1], *kept[2][sum(t == SLIDING for t in m["layer_types"][:i])])
        out, k, v = attention(w["attn"], m, _rms(x, w["attn_norm"]["scale"], m["rms_norm_eps"]), positions, kind, faults, ring)
        entries.append((k, v))
        x = x + out
        x = x + expert_layer(w["moe"], m, _rms(x, w["ffn_norm"]["scale"], m["rms_norm_eps"]), without)
    return x, entries


def logits_and_values(weights, m, h):
    z = _rms(h, weights["final_norm"]["scale"], m["rms_norm_eps"])
    return z @ weights["head"]["kernel"], (z @ weights["value_head"]["kernel"])[:, 0]


def forward(weights, m, tokens, without=(), faults: Tuple[str, ...] = (), kept=None):
    """``(logits [S, V], values [S], (keys, values) of each layer)`` of the full forward over ``tokens [S]``."""
    with jax.default_matmul_precision(HIGHEST):
        h, entries = trunk(weights, m, tokens, without, faults, kept)
        return (*logits_and_values(weights, m, h), entries)


# --------------------------------------------------------------------------- #
# PPO on recorded sequences
# --------------------------------------------------------------------------- #


def sequence_terms(weights, m, a, seq, without=(), faults: Tuple[str, ...] = ()):
    """The summed loss terms of one recorded sequence, every array aligned to
    the episode's positions and padded to one length: ``tokens [S]`` (the
    inputs from the episode's first: the prompt, then the tokens taken),
    ``steps [S]`` (1 at the positions whose output was a step of this
    sequence) and, at those positions, ``actions``, ``logprobs``,
    ``advantages``, ``returns``, ``values``. Returns sums of the policy, value
    and entropy terms and of the policy terms' magnitudes (``policy_abs``: the
    scale a gap of the policy loss, whose terms cancel, is held against)."""
    steps = seq["steps"]
    logits, values = logits_and_values(weights, m, trunk(weights, m, seq["tokens"], without, faults)[0])
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


def loss_and_grad(weights, m, a, sequences: List[Dict[str, Any]], without=(), faults: Tuple[str, ...] = ()):
    """The minibatch's loss terms (means over its steps) and the gradient of
    ``policy + vf_coef value + ent_coef entropy``, one sequence at a time
    (sequences of one padded length share a compilation)."""
    steps = float(sum(s["steps"].sum() for s in sequences))

    def total(w, seq):
        t = sequence_terms(w, m, a, seq, without, faults)
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


def losses_only(weights, m, a, sequences: Sequence[Dict[str, Any]], without=(), faults: Tuple[str, ...] = ()):
    """The minibatch's loss terms at ``weights``, no gradient."""
    terms_fn = jax.jit(lambda w, seq: sequence_terms(w, m, a, seq, without, faults))
    sums = {"policy": 0.0, "policy_abs": 0.0, "value": 0.0, "entropy": 0.0}
    with jax.default_matmul_precision(HIGHEST):
        for seq in sequences:
            terms = terms_fn(weights, {k: jnp.asarray(v) for k, v in seq.items()})
            for k in sums:
                sums[k] += float(terms[k])
    return _means(sums, float(sum(s["steps"].sum() for s in sequences)))
