"""A decoder-only token policy: RMSNorm, rotary embedding (one table of
frequencies a layer type, YaRN's among them), a layer operator chosen layer by
layer (multi-head latent attention, grouped-query attention over the whole
context or inside a sliding window, or a gated short convolution, each in a
whole-sequence and a one-token form), SwiGLU, a routed expert layer that is
told which experts it holds, the decoder block, and the
multi-token-prediction module.

The equations are the published forms: DeepSeek-V2/V3's latent attention and
expert layer (``glm4_moe_lite`` follows them), ``lfm2_moe``'s gated short
convolution beside grouped-query attention with normed queries and keys, and
``mellum``'s sliding-window layers beside full-attention layers under YaRN over
a softmax router. Every size comes from :class:`SeqPolConfig`, nothing is
fixed here.

Unlike ``blocks.py`` this module is plain functions over one parameter tree
(nested dicts whose leaves are named ``kernel``, ``embedding``, ``scale`` and
``bias``, as flax names them, so ``parallel/fabric.py``'s partition rules
resolve every leaf): the two forms of the attention read the same weights in
two arrangements, which a ``linen`` module would have to share by hand.

- :func:`forward_sequence` — the whole-sequence form, for the update and the
  prefill: ``[B, S]`` tokens under a causal mask, each row optionally continuing
  from the state a player carried as it stood before the row's first position.
- :func:`decode_step` — one token per row through that state, written in place.

**A layer's state is what its operator declares** (:data:`OPERATORS`,
:func:`state_shapes`): a tuple of arrays with the rows on the leading axis, in
the compute dtype. Latent attention: ``c_kv`` after its norm and ``k_rope``
after its rotation, ``[E, context, kv_lora_rank]`` and ``[E, context,
qk_rope_head_dim]`` (the whole-sequence form expands them through ``W_kvb``;
the one-token form absorbs ``W_kvb``'s key part into the query and its value
part into the output, so a row attends over its 576-wide entries as they lie).
Grouped-query attention: keys after norm and rotation, and values, ``[E,
context, key-value heads x head_dim]`` each. The gated short convolution: the
last ``conv_L_cache`` gated inputs, ``[E, conv_L_cache, D]``; the entries that
lie before a row's first position read as zero, which is the reset.
Sliding-window attention: keys and values again, but **a ring** of
``sliding_window`` entries a row, ``[E, W, key-value heads x head_dim]``:
position ``p`` lies at entry ``p mod W``, so the row's last ``W`` positions are
all it keeps, and entry ``j`` of a row whose newest position is ``q`` holds
position ``q - ((q - j) mod W)`` (:func:`ring_positions`), or nothing of the
row's episode where that is negative: what the episode before left there is
thereby unseen, which is this state's reset. A cache of ``context`` positions
is the ring that never wraps.

The expert layer routes over all ``n_routed_experts`` at the published width
and computes only the ``held`` experts' part for the tokens routed to them,
plus the shared expert; what absent experts would have added is left out.
No token is dropped and there is no capacity factor: every routed pair on a
held expert is computed, under whatever imbalance.
"""

from __future__ import annotations

import contextlib
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array
Params = Dict[str, Any]

#: a layer's operator, as ``layer_types`` names it (latent attention where a configuration names none)
LATENT, ATTENTION, SLIDING, CONV = "latent_attention", "full_attention", "sliding_attention", "conv"


@dataclass(frozen=True)
class RopeTable:
    """One layer type's rotary table, under the keys a published
    ``rope_parameters`` entry has: the default table (``theta^(-i / half)``) or
    YaRN's (the frequencies under ``low`` rotations of the original context
    kept, those over ``high`` divided by ``factor``, a ramp between; cosine
    and sine times ``attention_factor``, so a score carries its square)."""

    rope_theta: float
    rope_type: str = "default"
    factor: float = 1.0
    original_max_position_embeddings: Optional[int] = None
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None


@dataclass(frozen=True)
class SeqPolConfig:
    hidden_size: int
    num_attention_heads: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int  # the router's width: all experts of the layer, held here or not
    held_experts: Tuple[int, ...]  # which of them this share computes
    num_experts_per_tok: int
    n_shared_experts: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    first_k_dense_replace: int
    num_hidden_layers: int
    num_nextn_predict_layers: int
    vocab_rows: int  # rows of the vocabulary held here: ids, logits, sampling and losses are over them
    context: int  # positions a row's cache holds
    # latent attention's sizes: of a configuration that has such layers
    q_lora_rank: Optional[int] = None
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    #: each layer's operator (:data:`LATENT`, :data:`ATTENTION`, :data:`SLIDING`, :data:`CONV`); latent attention everywhere if not given
    layer_types: Optional[Tuple[str, ...]] = None
    # grouped-query attention's sizes
    num_key_value_heads: Optional[int] = None
    head_dim: Optional[int] = None
    #: positions a sliding-window layer attends to, the query's own among them: the entries of its ring state
    sliding_window: Optional[int] = None
    #: gated inputs the short convolution spans, the current one among them
    conv_L_cache: int = 3
    #: the head reads the embedding's rows and the tree has no ``head``
    tie_word_embeddings: bool = False
    #: added to the sum the chosen experts' scores are divided by
    router_eps: float = 1e-20
    #: the router's scores: ``sigmoid`` (each expert's own, the choice by score plus a correction bias) or
    #: ``softmax`` (over all experts in float32, no bias)
    router_scoring: str = "sigmoid"
    #: the rotary table of every layer type that ``rope_parameters`` does not name: the table with one entry
    rope_theta: float = 1e6
    #: ``(layer type, its table)``: a model whose layer types rotate by different tables
    rope_parameters: Optional[Tuple[Tuple[str, RopeTable], ...]] = None
    rms_norm_eps: float = 1e-5
    #: routed pairs up to which the expert layer multiplies every held expert
    #: with every token under a mask (a decode step); above it pairs are sorted
    #: by expert and multiplied in groups
    dense_pairs_max: int = 1024
    #: rows of one chunk of the update's whole-sequence blocks (:func:`block_by_rows`)
    row_chunk: int = 8

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def operator(self, layer: int) -> str:
        return self.layer_types[layer] if self.layer_types else LATENT

    def rope(self, kind: str) -> RopeTable:
        """The rotary table of the layers of ``kind``."""
        return dict(self.rope_parameters or ()).get(kind) or RopeTable(rope_theta=self.rope_theta)


def config_from(node: Any) -> SeqPolConfig:
    """``algo.core`` of a composed recipe (a mapping) as a :class:`SeqPolConfig`."""
    get = node.get if hasattr(node, "get") else node.__getitem__
    fields = {f: get(f) for f in SeqPolConfig.__dataclass_fields__ if get(f) is not None}
    fields["held_experts"] = tuple(int(e) for e in fields["held_experts"])
    if "layer_types" in fields:
        fields["layer_types"] = tuple(str(t) for t in fields["layer_types"])
    if "rope_parameters" in fields:
        fields["rope_parameters"] = tuple((str(kind), RopeTable(**dict(table))) for kind, table in dict(fields["rope_parameters"]).items())
    return SeqPolConfig(**fields)


# --------------------------------------------------------------------------- #
# parameters
# --------------------------------------------------------------------------- #


def _dense(key: Array, fan_in: int, fan_out: int, std: float = 0.02) -> Params:
    return {"kernel": std * jax.random.normal(key, (fan_in, fan_out), jnp.float32)}


def _norm(width: int) -> Params:
    return {"scale": jnp.ones((width,), jnp.float32)}


def _swiglu_params(key: Array, width: int, inner: int) -> Params:
    k = jax.random.split(key, 3)
    return {"gate": _dense(k[0], width, inner), "up": _dense(k[1], width, inner), "down": _dense(k[2], inner, width)}


def _latent_params(k: Array, cfg: SeqPolConfig) -> Params:
    """An operator's parameters from the first of the layer's keys ``k``."""
    d, h = cfg.hidden_size, cfg.num_attention_heads
    return {
        "q_a": _dense(k[0], d, cfg.q_lora_rank),
        "q_norm": _norm(cfg.q_lora_rank),
        "q_b": _dense(k[1], cfg.q_lora_rank, h * cfg.qk_head_dim),
        "kv_a": _dense(k[2], d, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
        "kv_norm": _norm(cfg.kv_lora_rank),
        "kv_b": _dense(k[3], cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "o": _dense(k[4], h * cfg.v_head_dim, d),
    }


def _gqa_params(k: Array, cfg: SeqPolConfig) -> Params:
    d, h, g, hd = cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    return {"q": _dense(k[0], d, h * hd), "q_norm": _norm(hd), "k": _dense(k[1], d, g * hd), "k_norm": _norm(hd),
            "v": _dense(k[2], d, g * hd), "o": _dense(k[3], h * hd, d)}  # fmt: skip


def _conv_params(k: Array, cfg: SeqPolConfig) -> Params:
    d = cfg.hidden_size
    # the depthwise kernel as ``[taps, channels]``: tap ``j`` weighs the gated input ``conv_L_cache - 1 - j`` positions back
    return {"in_proj": _dense(k[0], d, 3 * d), "conv": _dense(k[1], cfg.conv_L_cache, d), "out_proj": _dense(k[2], d, d)}


def _layer_params(key: Array, cfg: SeqPolConfig, dense: bool, kind: str = LATENT) -> Params:
    k = jax.random.split(key, 10)
    d = cfg.hidden_size
    op = OPERATORS[kind]
    # ``attn_norm`` is the norm in front of the layer's operator, whichever it is
    layer = {"attn_norm": _norm(d), op.key: op.params(k, cfg), "ffn_norm": _norm(d)}
    if dense:
        layer["mlp"] = _swiglu_params(k[5], d, cfg.intermediate_size)
        return layer
    n_held, inner = len(cfg.held_experts), cfg.moe_intermediate_size
    layer["moe"] = {
        # the router keeps its published width
        "router": {"kernel": 0.02 * jax.random.normal(k[6], (d, cfg.n_routed_experts), jnp.float32)},
        # the held experts' weights, stacked on a leading axis in the order of ``held_experts``
        "experts": {name: {"kernel": 0.02 * jax.random.normal(kk, (n_held, *shape), jnp.float32)}
                    for name, kk, shape in (("gate", k[7], (d, inner)), ("up", k[8], (d, inner)), ("down", k[9], (inner, d)))},  # fmt: skip
    }
    if cfg.router_scoring == "sigmoid":  # e_score_correction_bias; a softmax router has none
        layer["moe"]["router"]["bias"] = jnp.zeros((cfg.n_routed_experts,), jnp.float32)
    if cfg.n_shared_experts:
        layer["moe"]["shared"] = _swiglu_params(k[5], d, inner * cfg.n_shared_experts)
    return layer


def init_params(key: Array, cfg: SeqPolConfig) -> Params:
    """Float32 parameters from a key: embedding and head over ``vocab_rows``
    (no head where it is tied to the embedding), ``first_k_dense_replace``
    dense layers then expert layers, each with its operator's parameters, the
    value head, and one multi-token-prediction module where the configuration
    has one."""
    keys = jax.random.split(key, cfg.num_hidden_layers + 6)
    d = cfg.hidden_size
    params: Params = {
        "embed": {"embedding": 0.02 * jax.random.normal(keys[0], (cfg.vocab_rows, d), jnp.float32)},
        "layers": {str(i): _layer_params(keys[1 + i], cfg, i < cfg.first_k_dense_replace, cfg.operator(i)) for i in range(cfg.num_hidden_layers)},
        "final_norm": _norm(d),
        "value_head": _dense(keys[-2], d, 1),
    }
    if not cfg.tie_word_embeddings:
        params["head"] = _dense(keys[-1], d, cfg.vocab_rows)
    if cfg.num_nextn_predict_layers:
        params["mtp"] = {
            "enorm": _norm(d),
            "hnorm": _norm(d),
            "eh_proj": _dense(keys[-3], 2 * d, d),
            "block": _layer_params(keys[-4], cfg, dense=False),
            "final_norm": _norm(d),
        }
    return params


def _computes_in_float32(path: Tuple[Any, ...]) -> bool:
    """The router's and the value head's kernels are used in float32 whatever the compute dtype."""
    names = [getattr(k, "key", None) for k in path]
    return "router" in names or "value_head" in names


def low_precision(params: Params, dtype: Any) -> Params:
    """The copy of ``params`` the matmuls read: kernels and the embedding in
    the compute ``dtype``; norm scales, biases, the router and the value head
    as they are."""
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x if x.ndim < 2 or _computes_in_float32(path) else x.astype(dtype), params
    )


@jax.custom_vjp
def _read_copy(master: Array, copy: Array) -> Array:
    return copy


_read_copy.defvjp(lambda master, copy: (copy, None), lambda _, g: (g.astype(jnp.float32), jnp.zeros_like(g)))


def reading_copy(params: Params, copy: Params) -> Params:
    """``params`` with every leaf that ``copy`` holds in another dtype read
    from ``copy`` (:func:`low_precision` of the same values), its gradient
    going to the float32 leaf: what casting inside the program would compute,
    without the program making, and keeping, a cast of every weight."""
    return jax.tree.map(lambda w, c: w if c.dtype == w.dtype else _read_copy(w, c), params, copy)


# --------------------------------------------------------------------------- #
# the pieces
# --------------------------------------------------------------------------- #


def rms_norm(x: Array, scale: Array, eps: float) -> Array:
    """In float32 whatever ``x`` is; returns ``x``'s dtype."""
    xf = x.astype(jnp.float32)
    return (xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps) * scale).astype(x.dtype)


def rope_frequencies(table: RopeTable, dim: int) -> Tuple[Array, float]:
    """``(inv_freq [dim / 2], what cosine and sine are multiplied by)`` of a
    head of ``dim`` rotated dims. YaRN's, with ``f_i`` the default frequency
    and ``c(r) = dim ln(original context / (2 pi r)) / (2 ln theta)`` the dim
    that turns ``r`` times over the original context: ``f_i`` below ``low =
    floor(c(beta_fast))``, ``f_i / factor`` above ``high = ceil(c(beta_slow))``,
    a linear ramp between; fixed, whatever the sequence's length."""
    half = dim // 2
    inv_freq = table.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if table.rope_type == "default":
        return inv_freq, 1.0
    if table.rope_type != "yarn":
        raise ValueError(f"no rotary table of type {table.rope_type!r}")

    def turns_at(rotations: float) -> float:
        return dim * math.log(table.original_max_position_embeddings / (rotations * 2 * math.pi)) / (2 * math.log(table.rope_theta))

    low, high = max(math.floor(turns_at(table.beta_fast)), 0), min(math.ceil(turns_at(table.beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    factor = table.attention_factor if table.attention_factor is not None else 0.1 * math.log(table.factor) + 1.0
    return inv_freq / table.factor * ramp + inv_freq * (1.0 - ramp), float(factor)


def rope(x: Array, positions: Array, table: RopeTable) -> Array:
    """Rotary embedding of the last axis by ``positions`` (broadcast against
    ``x``'s leading axes) under ``table``: pairs are ``(i, i + d/2)``, all ``d`` dims rotated."""
    half = x.shape[-1] // 2
    inv_freq, factor = rope_frequencies(table, x.shape[-1])
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    xf = x.astype(jnp.float32)
    a, b = xf[..., :half], xf[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)


def _mm(x: Array, kernel: Array) -> Array:
    return jnp.dot(x, kernel.astype(x.dtype))


def swiglu(p: Params, x: Array) -> Array:
    return _mm(jax.nn.silu(_mm(x, p["gate"]["kernel"])) * _mm(x, p["up"]["kernel"]), p["down"]["kernel"])


def _queries(p: Params, cfg: SeqPolConfig, x: Array, positions: Array) -> Tuple[Array, Array]:
    """``q_nope [..., H, nope]`` and rotated ``q_rope [..., H, rope]``."""
    c_q = rms_norm(_mm(x, p["q_a"]["kernel"]), p["q_norm"]["scale"], cfg.rms_norm_eps)
    q = _mm(c_q, p["q_b"]["kernel"]).reshape(*x.shape[:-1], cfg.num_attention_heads, cfg.qk_head_dim)
    q_nope, q_rope = q[..., : cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim :]
    return q_nope, rope(q_rope, positions[..., None], cfg.rope(LATENT))


def latent_kv(p: Params, cfg: SeqPolConfig, x: Array, positions: Array) -> Tuple[Array, Array]:
    """What the cache holds of ``x``: ``c_kv`` after its norm, ``k_rope`` after its rotation."""
    kv = _mm(x, p["kv_a"]["kernel"])
    c_kv = rms_norm(kv[..., : cfg.kv_lora_rank], p["kv_norm"]["scale"], cfg.rms_norm_eps)
    return c_kv, rope(kv[..., cfg.kv_lora_rank :], positions, cfg.rope(LATENT))


def _kv_b(p: Params, cfg: SeqPolConfig, dtype: Any) -> Tuple[Array, Array]:
    """``W_kvb`` as its key part ``[c, H, nope]`` and its value part ``[c, H, v]``."""
    w = p["kv_b"]["kernel"].astype(dtype).reshape(cfg.kv_lora_rank, cfg.num_attention_heads, cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., : cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim :]


#: queries of one block of the whole-sequence attention: the scores of a block
#: are all that is alive at once, and the backward pass computes them again
QUERY_BLOCK = 128

#: what a row continues from, for one layer: ``(state, row [B], length [B])``. ``state`` is the layer's state as a
#: player carried it (:func:`state_shapes`), row ``b`` continues row ``row[b]`` of it, which held ``length[b]`` positions
Context = Tuple[Tuple[Array, ...], Array, Array]


#: rows of up to this many slots whose length is no multiple of :data:`QUERY_BLOCK` are scored in one block (the
#: scores of a few hundred queries fit); longer ones in whole blocks and a last shorter one
WHOLE_UP_TO = 1024


def _query_block(S: int) -> int:
    """The queries of a block of a row of ``S`` slots: :data:`QUERY_BLOCK`, or
    ``S`` where the row is scored in one block (:func:`_by_query_blocks`)."""
    n, rest = divmod(S, QUERY_BLOCK)
    return S if n <= 1 or (rest and S <= WHOLE_UP_TO) else QUERY_BLOCK


def _by_query_blocks(block: Any, queries: Tuple[Array, ...], B: int, S: int) -> Array:
    """``block(*queries of a block, their slots)`` over blocks of
    :func:`_query_block` queries, under ``jax.checkpoint``; ``[B, S, ...]``.
    A length that is no multiple of the block is scored whole up to
    :data:`WHOLE_UP_TO` slots, and beyond in whole blocks with a last shorter
    one behind them."""
    block = jax.checkpoint(block)
    b = _query_block(S)
    if b == S:
        return block(*queries, jnp.arange(S))
    n = S // b
    whole = n * b
    split = lambda a: jnp.moveaxis(a[:, :whole].reshape(B, n, b, *a.shape[2:]), 1, 0)  # noqa: E731
    out = lax.map(lambda t: block(*t), (*(split(q) for q in queries), jnp.arange(whole).reshape(n, b)))
    out = jnp.moveaxis(out, 0, 1).reshape(B, whole, *out.shape[3:])
    if whole == S:
        return out
    return jnp.concatenate([out, block(*(q[:, whole:] for q in queries), jnp.arange(whole, S))], axis=1)


def ring_positions(newest: Array, size: int) -> Array:
    """``[rows, size]``: the position that entry ``j`` of a ring of ``size``
    entries holds for a row whose newest position is ``newest [rows]``
    (position ``p`` lies at entry ``p mod size``): ``newest - ((newest - j) mod
    size)``; negative where the entry holds nothing of the row's episode. A
    cache of ``context`` positions is the ring that never wraps: entry ``j``
    holds position ``j``, or nothing."""
    return newest[:, None] - jnp.mod(newest[:, None] - jnp.arange(size)[None, :], size)


def ring_seen(positions: Array, size: int) -> Array:
    """``[rows, size]``: which entries of a ring a query at ``positions
    [rows]``, whose own entry is written, may see: those that hold a position
    of its episode (:func:`ring_positions` is not negative there): the entries
    up to its own, and all of them once the ring has wrapped."""
    return (jnp.arange(size)[None, :] <= positions[:, None]) | (positions[:, None] >= size)


def _with_context(ctx: Optional[Context], own: Tuple[Array, ...], valid: Array) -> Tuple[Tuple[Array, ...], Array, Array]:
    """The keys a row's queries may see: its own entries ``own`` (``[B, S,
    .]`` each) behind the entries of the cache row it continues, which held
    ``length`` positions: the first ``length`` of them. Returns the joined
    entries, which of them exist and each one's slot (a key is seen by the
    queries at or after it, by slot among the row's own, always for the cache:
    slot ``-1``)."""
    B, S = valid.shape
    key_slot = jnp.broadcast_to(jnp.arange(S), (B, S))
    if ctx is None:
        return own, valid, key_slot
    state, row, length = ctx
    C = state[0].shape[1]
    keys = tuple(jnp.concatenate([c[row].astype(o.dtype), o], axis=1) for c, o in zip(state, own))
    key_ok = jnp.concatenate([jnp.arange(C)[None, :] < length[:, None], valid], axis=1)
    return keys, key_ok, jnp.concatenate([jnp.full((B, C), -1), key_slot], axis=1)


def mla_sequence(p: Params, cfg: SeqPolConfig, x: Array, positions: Array, valid: Array,
                 ctx: Optional[Context] = None) -> Tuple[Array, Tuple[Array, Array]]:  # fmt: skip
    """Whole-sequence latent attention. ``x [B, S, D]``; ``positions [B, S]``
    (rotary positions, rising along a row); ``valid [B, S]`` marks real slots
    (padding neither attends nor is attended to). ``ctx = ((c_kv [E, C, c],
    k_rope [E, C, r]), row [B], length [B])`` is a cache as it stood before the
    rows' first slots: row ``b`` attends to the first ``length[b]`` entries of
    the cache's row ``row[b]`` too. Returns the output ``[B, S, D]`` and the
    rows' own ``(c_kv, k_rope)``."""
    B, S, _ = x.shape
    H = cfg.num_attention_heads
    q_nope, q_rope = _queries(p, cfg, x, positions)
    c_kv, k_rope = latent_kv(p, cfg, x, positions)
    (keys_c, keys_r), key_ok, key_slot = _with_context(ctx, (c_kv, k_rope), valid)
    w_k, w_v = _kv_b(p, cfg, x.dtype)
    k_nope = jnp.einsum("bkc,chd->bkhd", keys_c, w_k)
    v = jnp.einsum("bkc,chd->bkhd", keys_c, w_v)
    scale = cfg.qk_head_dim**-0.5

    def block(qn, qr, q_slot):
        s = jnp.einsum("bqhd,bkhd->bhqk", qn, k_nope, preferred_element_type=jnp.float32)
        s = s + jnp.einsum("bqhr,bkr->bhqk", qr, keys_r, preferred_element_type=jnp.float32)
        seen = key_ok[:, None, None, :] & (key_slot[:, None, None, :] <= q_slot[None, None, :, None])
        w = jax.nn.softmax(jnp.where(seen, s * scale, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w.astype(x.dtype), v)

    out = _by_query_blocks(block, (q_nope, q_rope), B, S)
    out = _mm(out.reshape(B, S, H * cfg.v_head_dim), p["o"]["kernel"])
    return out, (c_kv, k_rope)


def mla_decode(p: Params, cfg: SeqPolConfig, x: Array, positions: Array, state: Tuple[Array, Array]) -> Tuple[Array, Tuple[Array, Array]]:
    """One token per row against one layer's latent cache, in the absorbed
    form. ``x [E, D]``; ``positions [E]`` (the row's cache length: where its
    entry goes); ``state = (cache_c [E, C, c], cache_r [E, C, r])``. Returns the
    output ``[E, D]`` and the two caches with the rows' entries written (in
    place, where the caller donates them)."""
    cache_c, cache_r = state
    E = x.shape[0]
    q_nope, q_rope = _queries(p, cfg, x, positions)
    c_kv, k_rope = latent_kv(p, cfg, x, positions)
    rows = jnp.arange(E)
    cache_c = cache_c.at[rows, positions].set(c_kv.astype(cache_c.dtype), mode="drop")
    cache_r = cache_r.at[rows, positions].set(k_rope.astype(cache_r.dtype), mode="drop")
    w_k, w_v = _kv_b(p, cfg, x.dtype)
    q_lat = jnp.einsum("ehd,chd->ehc", q_nope, w_k)  # W_kvb's key part, absorbed into the query
    s = jnp.einsum("ehc,etc->eht", q_lat, cache_c.astype(x.dtype), preferred_element_type=jnp.float32)
    s = s + jnp.einsum("ehr,etr->eht", q_rope, cache_r.astype(x.dtype), preferred_element_type=jnp.float32)
    seen = jnp.arange(cache_c.shape[1])[None, None, :] <= positions[:, None, None]
    w = jax.nn.softmax(jnp.where(seen, s * cfg.qk_head_dim**-0.5, -1e30), axis=-1)
    o_lat = jnp.einsum("eht,etc->ehc", w.astype(x.dtype), cache_c.astype(x.dtype))
    out = jnp.einsum("ehc,chd->ehd", o_lat, w_v)  # and its value part, into the output
    return _mm(out.reshape(E, -1), p["o"]["kernel"]), (cache_c, cache_r)


def _gqa_qkv(p: Params, cfg: SeqPolConfig, x: Array, positions: Array, table: RopeTable) -> Tuple[Array, Array, Array]:
    """Queries ``[..., H, d]``, keys ``[..., G, d]`` (both RMS-normed over the
    head's dims, then rotated on all of them under ``table``) and values ``[..., G, d]``."""
    H, G, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    lead = x.shape[:-1]
    q = rms_norm(_mm(x, p["q"]["kernel"]).reshape(*lead, H, hd), p["q_norm"]["scale"], cfg.rms_norm_eps)
    k = rms_norm(_mm(x, p["k"]["kernel"]).reshape(*lead, G, hd), p["k_norm"]["scale"], cfg.rms_norm_eps)
    v = _mm(x, p["v"]["kernel"]).reshape(*lead, G, hd)
    return rope(q, positions[..., None], table), rope(k, positions[..., None], table), v


def _window(cfg: SeqPolConfig, kind: str) -> Optional[int]:
    """The window of a grouped-query layer of ``kind``: ``None`` where it attends to everything."""
    return cfg.sliding_window if kind == SLIDING else None


def _core_scope(window: Optional[int]):
    """A window layer's scores, softmax and weighted sum lie under ``seqpol/attn/window``, its projections do not."""
    return jax.named_scope("window") if window else contextlib.nullcontext()


def _ring_of(entries: Tuple[Array, ...], positions: Array, valid: Array, size: int) -> Tuple[Array, ...]:
    """The ring a prefill of these rows leaves: of each row's ``entries [B, S,
    .]`` (one run of real slots a row, at ``positions``) the last ``size``, the
    one at position ``p`` at entry ``p mod size``; zero where the row has no
    such position."""
    last = jnp.argmax(valid, axis=1) + valid.sum(axis=1) - 1  # the slot of each row's newest position
    newest = jnp.take_along_axis(positions, jnp.maximum(last, 0)[:, None], axis=1)[:, 0]
    held = ring_positions(jnp.where(valid.any(axis=1), newest, -1), size)
    slot = jnp.maximum(last[:, None] - (newest[:, None] - held), 0)
    return tuple(jnp.where((held >= 0)[..., None], jnp.take_along_axis(e, slot[..., None], axis=1), 0) for e in entries)


def gqa_sequence(p: Params, cfg: SeqPolConfig, x: Array, positions: Array, valid: Array,
                 ctx: Optional[Context] = None, *, kind: str = ATTENTION) -> Tuple[Array, Tuple[Array, Array]]:  # fmt: skip
    """Whole-sequence grouped-query attention, with :func:`mla_sequence`'s
    arguments: query head ``i`` reads key-value head ``i // (H / G)``. ``ctx``'s
    state is ``(keys [E, C, G x d], values [E, C, G x d])``. A layer of ``kind``
    :data:`SLIDING` masks by position too: a query at ``q`` sees the keys at
    ``q - W < p <= q``, among the row's own and in the ring it continues, whose
    entries it stops seeing one by one. Returns the output and the rows' own
    keys (after norm and rotation) and values: ``[B, S, G x d]``, or for a
    window layer the ring a prefill leaves, ``[B, W, G x d]`` (:func:`_ring_of`).
    A window layer's blocks of queries score only the keys their band can
    reach (:func:`_band_blocks`)."""
    B, S, _ = x.shape
    H, G, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    window = _window(cfg, kind)
    q, k, v = _gqa_qkv(p, cfg, x, positions, cfg.rope(kind))
    own = (k.reshape(B, S, G * hd), v.reshape(B, S, G * hd))

    def attend(qb, keys, values, seen):  # ``seen [B, 1, 1, q, k]``: which keys each query of the block sees
        qb = qb.reshape(B, -1, G, H // G, hd)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qb, keys, preferred_element_type=jnp.float32)
        w = jax.nn.softmax(jnp.where(seen, s * hd**-0.5, -1e30), axis=-1)
        return jnp.einsum("bgrqk,bkgd->bqgrd", w.astype(x.dtype), values).reshape(B, -1, H * hd)

    if window:
        with _core_scope(window):
            out = _band_blocks(attend, q, (k, v), valid, positions, ctx, window)
        return _mm(out, p["o"]["kernel"]), _ring_of(own, positions, valid, window)
    (keys, values), key_ok, key_slot = _with_context(ctx, own, valid)
    keys, values = keys.reshape(B, -1, G, hd), values.reshape(B, -1, G, hd)

    def block(qb, q_slot):
        return attend(qb, keys, values, key_ok[:, None, None, None, :] & (key_slot[:, None, None, None, :] <= q_slot[None, None, None, :, None]))

    return _mm(_by_query_blocks(block, (q,), B, S), p["o"]["kernel"]), own


def ring_reach(positions: Array, valid: Array, length: Array, window: int) -> Array:
    """``[B, S]``: which queries, at ``positions`` in rows that continue rings
    that held ``length [B]`` positions, are real and see an entry of the ring
    through a band of ``window`` positions: the newest entry, position ``length
    - 1``, lies inside the band (a ring holds nothing newer, and nothing of a
    row that begins: ``length`` 0)."""
    return valid & (length[:, None] > 0) & (positions - (length[:, None] - 1) < window)


def _band_width(S: int, window: int) -> int:
    """The slots of a row's own keys that a block of queries of a window layer scores (:func:`_band_blocks`)."""
    return min(S, window + _query_block(S))


def _band_blocks(attend: Any, q: Array, own: Tuple[Array, Array], valid: Array, positions: Array,
                 ctx: Optional[Context], window: int) -> Array:  # fmt: skip
    """A window layer's blocks of queries (:func:`_by_query_blocks`), each
    scored by ``attend(queries, keys, values, seen)`` against the keys its band
    can reach: a slice of ``min(S, W + b)`` of the row's own slots (``own``:
    keys and values ``[B, S, G, d]``) that ends at the block's last slot,
    clamped to the row, under the slot and position masks as over the whole
    row; and before it the ring the rows continue (``ctx``) only where a real
    query of the block sees one of its entries (:func:`ring_reach`), by
    ``lax.cond``. Along a row a real slot's position is at least one more than
    the real slot's before it, so no key ``W`` or more slots before a query is
    inside its band: the slice holds every key a query of the block sees, and
    a key left out would have added ``exp(-1e30 - max) = 0``. Each of the two
    forms is under ``jax.checkpoint`` of its own inside the block's: the
    backward pass then makes again the scores of the form the block took, and
    gives neither form room for what the other would keep."""
    B, S = valid.shape
    keys, values = own
    width = _band_width(S, window)

    def own_part(q_pos, q_slot, start):
        take = lambda a: lax.dynamic_slice_in_dim(a, start, width, axis=1)  # noqa: E731
        slot = start + jnp.arange(width)
        seen = take(valid)[:, None, :] & (slot[None, None, :] <= q_slot[None, :, None]) & (q_pos[:, :, None] - take(positions)[:, None, :] < window)
        return take(keys), take(values), seen

    def alone(qb, q_pos, q_slot, start):
        k, v, seen = own_part(q_pos, q_slot, start)
        return attend(qb, k, v, seen[:, None, None])

    if ctx is not None:
        state, row, length = ctx
        C = state[0].shape[1]
        ring_k, ring_v = (c[row].astype(keys.dtype).reshape(B, C, *keys.shape[2:]) for c in state)
        ring_ok, ring_at = jnp.arange(C)[None, :] < length[:, None], ring_positions(length - 1, C)

        def with_ring(qb, q_pos, q_slot, start):
            k, v, seen = own_part(q_pos, q_slot, start)
            ring_seen = ring_ok[:, None, :] & (q_pos[:, :, None] - ring_at[:, None, :] < window)
            seen = jnp.concatenate([ring_seen, seen], axis=-1)[:, None, None]
            return attend(qb, jnp.concatenate([ring_k, k], axis=1), jnp.concatenate([ring_v, v], axis=1), seen)

    def block(qb, q_pos, *at):  # where the rows continue a ring, which queries see it (:func:`ring_reach`); then the slots
        q_slot = at[-1]
        start = jnp.clip(q_slot[-1] + 1 - width, 0, S - width)
        if ctx is None:
            return alone(qb, q_pos, q_slot, start)
        return lax.cond(at[0].any(), jax.checkpoint(with_ring), jax.checkpoint(alone), qb, q_pos, q_slot, start)

    reach = () if ctx is None else (ring_reach(positions, valid, ctx[2], window),)
    return _by_query_blocks(block, (q, positions, *reach), B, S)


def gqa_decode(p: Params, cfg: SeqPolConfig, x: Array, positions: Array, state: Tuple[Array, Array],
               *, kind: str = ATTENTION) -> Tuple[Array, Tuple[Array, Array]]:  # fmt: skip
    """One token per row against one layer's key-value cache, ``state = (keys
    [E, C, G x d], values [E, C, G x d])``, with :func:`mla_decode`'s arguments:
    a ring of ``C`` entries, the whole context's (it never wraps) or a window
    layer's ``W``. The row's entry is written at ``position mod C``, in place,
    and the row sees the entries that hold its episode (:func:`ring_seen`).
    Every query head is laid out over the cache's whole width, zero outside its
    own key-value head's dims: the scores and the output are then products with
    the cache entries as they lie (what the zeros add is exactly nothing), and
    no head is sliced or transposed out of the cache."""
    cache_k, cache_v = state
    E, C = x.shape[0], cache_k.shape[1]
    H, G, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q, k, v = _gqa_qkv(p, cfg, x, positions, cfg.rope(kind))
    rows, at = jnp.arange(E), positions % C
    cache_k = cache_k.at[rows, at].set(k.reshape(E, G * hd).astype(cache_k.dtype), mode="drop")
    cache_v = cache_v.at[rows, at].set(v.reshape(E, G * hd).astype(cache_v.dtype), mode="drop")
    own = (jnp.arange(H)[:, None] // (H // G) == jnp.arange(G)[None, :])[None, :, :, None]  # [1, H, G, 1]
    q_wide = jnp.where(own, q[:, :, None, :], 0).reshape(E, H, G * hd)
    with _core_scope(_window(cfg, kind)):
        s = jnp.einsum("ehc,etc->eht", q_wide, cache_k.astype(x.dtype), preferred_element_type=jnp.float32)
        w = jax.nn.softmax(jnp.where(ring_seen(positions, C)[:, None, :], s * hd**-0.5, -1e30), axis=-1)
        o_wide = jnp.einsum("eht,etc->ehc", w.astype(x.dtype), cache_v.astype(x.dtype))
    out = jnp.where(own, o_wide.reshape(E, H, G, hd), 0).sum(2)
    return _mm(out.reshape(E, H * hd), p["o"]["kernel"]), (cache_k, cache_v)


def conv_in_episode(positions: Array, taps: int) -> Array:
    """``[rows, taps]``: which entries of a convolution state, taken at
    ``positions [rows]``, are of the rows' own episode: at or after its first
    position (entry ``j`` is the gated input ``taps - 1 - j`` positions back).
    The others read as zero: that is the reset of this kind of state."""
    return positions[:, None] - (taps - 1 - jnp.arange(taps))[None, :] >= 0


def _conv_mix(p: Params, window: Sequence[Array]) -> Array:
    """The depthwise causal convolution: ``sum_j w[j] * window[j]``, ``window[j]`` the gated input ``taps - 1 - j`` back."""
    w = p["conv"]["kernel"]
    return sum(w[j].astype(z.dtype) * z for j, z in enumerate(window))


def conv_sequence(p: Params, cfg: SeqPolConfig, x: Array, positions: Array, valid: Array,
                  ctx: Optional[Context] = None) -> Tuple[Array, Tuple[Array]]:  # fmt: skip
    """The gated short convolution over whole rows: ``[B, C, X] = W_in x``,
    ``z = B * X``, ``out = W_out (C * conv(z))`` with ``conv`` depthwise and
    causal over ``conv_L_cache`` gated inputs. ``valid`` marks one run of real
    slots a row; before it ``z`` is zero, or, for a row that continues
    (``ctx = ((state [E, L, D],), row [B], length [B])``), what ``state`` held
    of the ``length`` positions before. Returns the output and ``(the last L
    gated inputs up to each row's last real slot [B, L, D],)``: the state a
    prefill leaves behind."""
    B, S, _ = x.shape
    L = cfg.conv_L_cache
    with jax.named_scope("proj"):
        gates = _mm(x, p["in_proj"]["kernel"])
    with jax.named_scope("mix"):
        b, c, xx = jnp.split(gates, 3, axis=-1)
        z = jnp.where(valid[..., None], b * xx, 0)
        zp = jnp.concatenate([jnp.zeros((B, L - 1, z.shape[-1]), z.dtype), z], axis=1)  # slot ``i`` of ``z`` is ``i + L - 1`` here
        first = jnp.argmax(valid, axis=1)
        if ctx is not None:
            (state,), row, length = ctx
            carried = state[row].astype(z.dtype)  # entry ``j`` lies ``L - 1 - j`` before the position that wrote it
            kept = conv_in_episode(length - 1, L)
            at = jnp.arange(S + L - 1)[None, :]
            for back in range(1, L):  # the gated input ``back`` before the row's first slot
                here = (at == (first + L - 1 - back)[:, None]) & kept[:, L - back, None]
                zp = jnp.where(here[..., None], carried[:, L - back, None, :], zp)
        y = c * _conv_mix(p, [zp[:, j : j + S] for j in range(L)])
        last = first + valid.sum(axis=1) - 1
        tail = jnp.take_along_axis(zp, jnp.maximum(last[:, None] + jnp.arange(L)[None, :], 0)[..., None], axis=1)
    with jax.named_scope("proj"):
        return _mm(y, p["out_proj"]["kernel"]), (tail,)


def conv_decode(p: Params, cfg: SeqPolConfig, x: Array, positions: Array, state: Tuple[Array]) -> Tuple[Array, Tuple[Array]]:
    """One token per row through the convolution state ``(z [E, L, D],)``: the
    state moves one entry on and takes the row's gated input, and the entries
    that lie before the row's first position are put to zero
    (:func:`conv_in_episode`: a row at position 0 starts from nothing,
    whatever the episode before left)."""
    (z_state,) = state
    L = cfg.conv_L_cache
    with jax.named_scope("proj"):
        gates = _mm(x, p["in_proj"]["kernel"])
    with jax.named_scope("mix"):
        b, c, xx = jnp.split(gates, 3, axis=-1)
        z_state = jnp.concatenate([z_state[:, 1:], (b * xx).astype(z_state.dtype)[:, None]], axis=1)
        z_state = jnp.where(conv_in_episode(positions, L)[..., None], z_state, 0)
        y = c * _conv_mix(p, [z_state[:, j].astype(x.dtype) for j in range(L)])
    with jax.named_scope("proj"):
        return _mm(y, p["out_proj"]["kernel"]), (z_state,)


@dataclass(frozen=True)
class Operator:
    """What a layer's operator declares: where its parameters lie in the
    layer's tree and how they are made, its ``jax.named_scope``, its two forms
    (``sequence(p, cfg, x, positions, valid, ctx) -> (out, own entries)`` and
    ``decode(p, cfg, x, positions, state) -> (out, state)``) and the shape of
    each array of its state for one row."""

    key: str
    scope: str
    params: Any
    sequence: Any
    decode: Any
    state: Any


OPERATORS: Dict[str, Operator] = {
    LATENT: Operator("attn", "seqpol/attn", _latent_params, mla_sequence, mla_decode,
                     lambda cfg: ((cfg.context, cfg.kv_lora_rank), (cfg.context, cfg.qk_rope_head_dim))),
    ATTENTION: Operator("attn", "seqpol/attn", _gqa_params, gqa_sequence, gqa_decode,
                        lambda cfg: ((cfg.context, cfg.num_key_value_heads * cfg.head_dim),) * 2),
    SLIDING: Operator("attn", "seqpol/attn", _gqa_params, partial(gqa_sequence, kind=SLIDING), partial(gqa_decode, kind=SLIDING),
                      lambda cfg: ((cfg.sliding_window, cfg.num_key_value_heads * cfg.head_dim),) * 2),
    CONV: Operator("conv", "seqpol/conv", _conv_params, conv_sequence, conv_decode, lambda cfg: ((cfg.conv_L_cache, cfg.hidden_size),)),
}  # fmt: skip


def state_shapes(cfg: SeqPolConfig, rows: int) -> Tuple[Tuple[Tuple[int, ...], ...], ...]:
    """The shapes of the state ``rows`` rows carry: for each layer, one shape
    for each array its operator declares."""
    return tuple(tuple((rows, *shape) for shape in OPERATORS[cfg.operator(i)].state(cfg)) for i in range(cfg.num_hidden_layers))


# --------------------------------------------------------------------------- #
# the expert layer
# --------------------------------------------------------------------------- #


def route(p: Params, cfg: SeqPolConfig, x: Array) -> Tuple[Array, Array]:
    """``(expert ids [T, k], weights [T, k])`` over all ``n_routed_experts``,
    the scores in float32 by ``cfg.router_scoring``: each expert's sigmoid or a
    softmax over all of them; the choice by score (plus the correction bias,
    where the tree has one); the weights the scores themselves at the chosen
    experts, normalised and scaled."""
    logits = jnp.dot(x.astype(jnp.float32), p["router"]["kernel"], precision=lax.Precision.HIGHEST)
    scores = jax.nn.softmax(logits, axis=-1) if cfg.router_scoring == "softmax" else jax.nn.sigmoid(logits)
    _, chosen = lax.top_k(scores + p["router"]["bias"] if "bias" in p["router"] else scores, cfg.num_experts_per_tok)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.norm_topk_prob:
        weights = weights / (weights.sum(-1, keepdims=True) + cfg.router_eps)
    return chosen, weights * cfg.routed_scaling_factor


def _held_slot(cfg: SeqPolConfig, chosen: Array) -> Array:
    """Each chosen expert's index among the held ones, or ``-1``."""
    table = [-1] * cfg.n_routed_experts
    for slot, expert in enumerate(cfg.held_experts):
        table[expert] = slot
    return jnp.asarray(table, jnp.int32)[chosen]


def _experts_dense(p: Params, x: Array, slot: Array, weights: Array, n_held: int) -> Array:
    """Every held expert on every token, kept where the token chose it: for a
    handful of tokens, where the weights' bytes are the cost either way."""
    gate = jnp.einsum("td,edf->etf", x, p["gate"]["kernel"].astype(x.dtype))
    up = jnp.einsum("td,edf->etf", x, p["up"]["kernel"].astype(x.dtype))
    y = jnp.einsum("etf,efd->etd", jax.nn.silu(gate) * up, p["down"]["kernel"].astype(x.dtype))
    w = jnp.where(slot[None] == jnp.arange(n_held)[:, None, None], weights[None], 0.0).sum(-1)  # [e, T]
    return jnp.einsum("etd,et->td", y, w.astype(x.dtype))


def _experts_grouped(p: Params, x: Array, slot: Array, weights: Array, n_held: int, rows: Optional[int] = None) -> Array:
    """The routed pairs on held experts, sorted by expert and multiplied in
    groups (``lax.ragged_dot``); pairs on absent experts sort last, fall in no
    group and add nothing. A grouped product costs what its buffer holds, not
    what its groups hold, so the products run over the first ``rows`` sorted
    pairs when every held pair lies among them and over all pairs when not:
    none can be dropped. A row in no group comes out of a grouped product as
    whatever the memory held, NaN included, forward and backward: every
    product's result is put to zero there before anything is multiplied with it."""
    T, k = slot.shape
    flat = slot.reshape(-1)
    order = jnp.argsort(jnp.where(flat < 0, n_held, flat), stable=True)
    sizes = jnp.bincount(jnp.where(flat < 0, n_held, flat), length=n_held + 1)[:n_held].astype(jnp.int32)
    n_pairs = sizes.sum()

    def over(n: int) -> Array:
        first = order[:n]
        token = first // k
        held = (jnp.arange(n) < n_pairs)[:, None]

        def grouped(lhs: Array, kernel: Array) -> Array:
            return jnp.where(held, lax.ragged_dot(jnp.where(held, lhs, 0), kernel.astype(x.dtype), sizes), 0)

        xs = x[token]
        y = grouped(jax.nn.silu(grouped(xs, p["gate"]["kernel"])) * grouped(xs, p["up"]["kernel"]), p["down"]["kernel"])
        return jnp.zeros_like(x).at[token].add(y * weights.reshape(-1)[first][:, None].astype(x.dtype))

    if rows is None or rows >= T * k:
        return over(T * k)
    return lax.cond(n_pairs <= rows, lambda: over(rows), lambda: over(T * k))


#: the grouped products run over this many times the pairs that even routing
#: puts on the held experts when the held pairs fit in that many rows, and over
#: the whole buffer of pairs when they do not (:func:`_experts_grouped`)
GROUPED_ROWS_FACTOR = 2.0


def grouped_rows(cfg: SeqPolConfig, pairs: int) -> int:
    """Rows of the sorted buffer that the grouped products cover when the held
    pairs fit: :data:`GROUPED_ROWS_FACTOR` times the held experts' even share
    of ``pairs``, in whole eights, and never more than ``pairs``."""
    even = pairs * len(cfg.held_experts) / cfg.n_routed_experts
    return min(pairs, -(-int(GROUPED_ROWS_FACTOR * even) // 8) * 8)


def moe(p: Params, cfg: SeqPolConfig, x: Array, real: Optional[Array] = None) -> Tuple[Array, Array]:
    """``x [T, D]`` -> the shared expert (where the layer has one) plus the held experts' part of the
    routed sum, and the layer's counts ``[routed pairs, pairs on each held
    expert...]`` (float32). ``real [T]`` marks the rows that are tokens:
    padding is routed nowhere."""
    T, n_held = x.shape[0], len(cfg.held_experts)
    with jax.named_scope("seqpol/moe/route"):
        chosen, weights = route(p, cfg, x)
        slot = _held_slot(cfg, chosen)
        if real is not None:  # a padded slot routes nowhere: it costs no expert and counts in no load
            slot = jnp.where(real[:, None], slot, -1)
        load = (slot[None] == jnp.arange(n_held)[:, None, None]).sum((1, 2))
        n_real = T if real is None else real.sum()
        counts = jnp.concatenate([jnp.reshape(n_real * cfg.num_experts_per_tok, (1,)), load]).astype(jnp.float32)
    with jax.named_scope("seqpol/moe/experts"):
        if T * cfg.num_experts_per_tok <= cfg.dense_pairs_max:
            routed = _experts_dense(p["experts"], x, slot, weights, n_held)
        else:
            routed = _experts_grouped(p["experts"], x, slot, weights, n_held, grouped_rows(cfg, T * cfg.num_experts_per_tok))
    if "shared" not in p:
        return routed, counts
    with jax.named_scope("seqpol/moe/shared"):
        shared = swiglu(p["shared"], x)
    return shared + routed, counts


def counters_of(counts: Array) -> Array:
    """A layer's counts as the three counters ``[routed pairs, pairs on held
    experts, the busiest held expert's pairs]``."""
    return jnp.stack([counts[0], counts[1:].sum(), counts[1:].max()])


def merge_counters(counters: Array, layer: Array) -> Array:
    """Pair counts add over layers; the busiest expert's load is a maximum over them."""
    return jnp.stack([counters[0] + layer[0], counters[1] + layer[1], jnp.maximum(counters[2], layer[2])])


# --------------------------------------------------------------------------- #
# blocks and the two forward forms
# --------------------------------------------------------------------------- #


def _ffn(p: Params, cfg: SeqPolConfig, x: Array, real: Optional[Array]) -> Tuple[Array, Array]:
    if "mlp" in p:
        with jax.named_scope("seqpol/mlp"):
            return swiglu(p["mlp"], x), jnp.zeros((1 + len(cfg.held_experts),), jnp.float32)
    flat = x.reshape(-1, x.shape[-1])
    y, counts = moe(p["moe"], cfg, flat, None if real is None else real.reshape(-1))
    return y.reshape(x.shape), counts


def block_sequence(p: Params, cfg: SeqPolConfig, kind: str, x: Array, positions: Array, valid: Array,
                   ctx: Optional[Context]) -> Tuple[Array, Tuple[Array, ...], Array]:  # fmt: skip
    """One decoder block on whole rows, its operator of ``kind``: the output,
    the rows' own entries of the operator's state and the expert layer's counts."""
    op = OPERATORS[kind]
    with jax.named_scope(op.scope):
        a, own = op.sequence(p[op.key], cfg, rms_norm(x, p["attn_norm"]["scale"], cfg.rms_norm_eps), positions, valid, ctx)
    x = x + a
    f, counts = _ffn(p, cfg, rms_norm(x, p["ffn_norm"]["scale"], cfg.rms_norm_eps), valid)
    return x + f, own, counts


def _chunk_rows(cfg: SeqPolConfig, B: int, remat: bool) -> int:
    """The rows of one chunk of :func:`block_by_rows` over ``B`` rows: ``cfg.row_chunk`` where they divide into such chunks
    and the update asks for it, else all of them."""
    return cfg.row_chunk if remat and B > cfg.row_chunk and not B % cfg.row_chunk else B


def window_layers(cfg: SeqPolConfig) -> int:
    """How many layers attend inside a sliding window."""
    return sum(cfg.operator(i) == SLIDING for i in range(cfg.num_hidden_layers))


def window_pairs_scored(cfg: SeqPolConfig, positions: Array, valid: Array, length: Array, remat: bool) -> Array:
    """The pairs of a query and a key that the window layers of
    :func:`forward_sequence` score over rows ``[B, S]`` that continue rings
    which held ``length [B]`` positions, by the rule their blocks follow
    (:func:`_band_blocks`): in each chunk of rows (:func:`block_by_rows`) and
    each block of queries, the block's queries times the slice of the row's own
    keys, and times the ring's entries too where a real query of the chunk's
    block sees one (:func:`ring_reach`). Float32, summed over the window layers."""
    B, S = valid.shape
    rows, b, W = _chunk_rows(cfg, B, remat), _query_block(S), cfg.sliding_window
    n = -(-S // b)
    reach = jnp.pad(ring_reach(positions, valid, length, W), ((0, 0), (0, n * b - S)))
    with_ring = reach.reshape(B // rows, rows, n, b).any(axis=(1, 3))  # [chunks, blocks]
    queries = jnp.minimum(b, S - b * jnp.arange(n)).astype(jnp.float32)  # of each block
    return window_layers(cfg) * rows * (queries * (_band_width(S, W) + W * with_ring)).sum()


def block_by_rows(p: Params, cfg: SeqPolConfig, kind: str, x: Array, positions: Array, valid: Array,
                  ctx: Optional[Context], remat: bool) -> Tuple[Array, Tuple[Array, ...], Array]:  # fmt: skip
    """:func:`block_sequence`, ``cfg.row_chunk`` rows at a time where the
    update asks for it (``remat``): rows do not see each other, so a chunk's
    intermediates are all that is alive at once, and the backward pass, which
    keeps only each chunk's input, makes them again."""
    B = x.shape[0]
    rc = _chunk_rows(cfg, B, remat)
    if not remat:
        x, own, counts = block_sequence(p, cfg, kind, x, positions, valid, ctx)
        return x, own, counters_of(counts)
    state, of_rows = (None, None) if ctx is None else (ctx[0], ctx[1:])  # the state is whole in every chunk; its rows and lengths go with theirs

    def fn(args):
        x, positions, valid, of_rows = args
        return block_sequence(p, cfg, kind, x, positions, valid, None if state is None else (state, *of_rows))

    fn = jax.checkpoint(fn)
    if rc == B:
        x, own, counts = fn((x, positions, valid, of_rows))
        return x, own, counters_of(counts)
    split = lambda a: a.reshape(B // rc, rc, *a.shape[1:])  # noqa: E731
    x, own, counts = lax.map(fn, jax.tree.map(split, (x, positions, valid, of_rows)))
    join = lambda a: a.reshape(B, *a.shape[2:])  # noqa: E731
    return join(x), jax.tree.map(join, own), counters_of(counts.sum(0))


def embed(params: Params, tokens: Array, dtype: Any) -> Array:
    with jax.named_scope("seqpol/embed"):
        return params["embed"]["embedding"].astype(dtype)[tokens]


def forward_sequence(params: Params, cfg: SeqPolConfig, tokens: Array, positions: Array, valid: Array,
                     ctx: Optional[Tuple[Sequence[Tuple[Array, ...]], Array, Array]] = None, *, dtype: Any = jnp.float32,
                     remat: bool = False) -> Tuple[Array, Tuple[Tuple[Array, ...], ...], Array]:  # fmt: skip
    """The trunk over ``tokens [B, S]``: the hidden state before the final
    norm ``[B, S, D]``, for each layer the rows' own entries of its operator's
    state (what a prefill writes into the rows it fills: ``[B, S, .]`` for a
    cache of positions, ``[B, L, D]`` for a convolution state) and the expert
    layers' counters summed. ``ctx = (state, row [B], length [B])`` is the
    state the rows continue from, ``state[i]`` layer ``i``'s arrays
    (:func:`state_shapes`). ``remat`` keeps only each chunk of a block's input
    for the backward pass."""
    x = embed(params, tokens, dtype)
    entries, counters = [], jnp.zeros((3,), jnp.float32)
    for i in range(cfg.num_hidden_layers):
        layer_ctx = None if ctx is None else (ctx[0][i], ctx[1], ctx[2])
        x, own, n = block_by_rows(params["layers"][str(i)], cfg, cfg.operator(i), x, positions, valid, layer_ctx, remat)
        entries.append(own)
        counters = merge_counters(counters, n)
    return x, tuple(entries), counters


def decode_step(params: Params, cfg: SeqPolConfig, tokens: Array, positions: Array, state: Sequence[Tuple[Array, ...]],
                *, dtype: Any = jnp.float32) -> Tuple[Array, Tuple[Tuple[Array, ...], ...], Array]:  # fmt: skip
    """One token for each of ``E`` rows: ``tokens [E]`` at ``positions [E]``
    through the state, each layer's arrays on their own (:func:`state_shapes`:
    a layer's entries are then written in place and read where they lie, and
    no layer is sliced out of a stack). Returns the hidden state before the
    final norm ``[E, D]``, the state with the rows' entries written, and the
    expert layers' counters."""
    x = embed(params, tokens, dtype)
    counters = jnp.zeros((3,), jnp.float32)
    new_state = []
    for i in range(cfg.num_hidden_layers):
        p, op = params["layers"][str(i)], OPERATORS[cfg.operator(i)]
        with jax.named_scope(op.scope):
            a, layer_state = op.decode(p[op.key], cfg, rms_norm(x, p["attn_norm"]["scale"], cfg.rms_norm_eps), positions, state[i])
        new_state.append(layer_state)
        x = x + a
        f, n = _ffn(p, cfg, rms_norm(x, p["ffn_norm"]["scale"], cfg.rms_norm_eps), None)
        x = x + f
        counters = merge_counters(counters, counters_of(n))
    return x, tuple(new_state), counters


def head_kernel(params: Params) -> Array:
    """``[D, vocabulary rows]``: the head's own kernel, or the embedding's rows where the two are tied."""
    return params["head"]["kernel"] if "head" in params else params["embed"]["embedding"].T


def heads(params: Params, cfg: SeqPolConfig, h: Array) -> Tuple[Array, Array]:
    """Final norm, then the logits over the held rows (float32) and the value."""
    with jax.named_scope("seqpol/head"):
        z = rms_norm(h, params["final_norm"]["scale"], cfg.rms_norm_eps)
        logits = _mm(z, head_kernel(params)).astype(jnp.float32)
        value = jnp.dot(z.astype(jnp.float32), params["value_head"]["kernel"])[..., 0]
    return logits, value


def mtp_hidden(params: Params, cfg: SeqPolConfig, h: Array, next_tokens: Array, positions: Array, valid: Array,
               *, remat: bool = False) -> Tuple[Array, Array]:  # fmt: skip
    """The multi-token-prediction module's state ``[B, S, D]`` (before its
    final norm; the shared head turns it into logits for the token after
    ``next_tokens``): ``W_eh [RMSNorm(h) ; RMSNorm(Emb(next_tokens))]`` through
    one expert block, which attends over the row's own slots."""
    with jax.named_scope("seqpol/mtp"):
        m = params["mtp"]
        e = embed(params, next_tokens, h.dtype)
        joined = jnp.concatenate([rms_norm(h, m["hnorm"]["scale"], cfg.rms_norm_eps), rms_norm(e, m["enorm"]["scale"], cfg.rms_norm_eps)], axis=-1)
        x, _, counters = block_by_rows(m["block"], cfg, LATENT, _mm(joined, m["eh_proj"]["kernel"]), positions, valid, None, remat)
    return x, counters


def token_stats(params: Params, cfg: SeqPolConfig, h: Array, norm_scale: Array, targets: Array, block: int = 2048) -> Tuple[Array, Array]:
    """``(log-probability of targets, entropy)`` of the head's distribution at
    every row of ``h [N, D]``, in float32, a ``block`` of rows at a time so
    that the ``[rows, vocabulary]`` logits never exist whole (the backward pass
    makes each block's again)."""

    @jax.checkpoint
    def one(hb, tb):
        z = rms_norm(hb, norm_scale, cfg.rms_norm_eps)
        logp = jax.nn.log_softmax(_mm(z, head_kernel(params)).astype(jnp.float32), axis=-1)
        return jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0], -(jnp.exp(logp) * logp).sum(-1)

    N = h.shape[0]
    with jax.named_scope("seqpol/head"):
        if N <= block or N % block:
            return one(h, targets)
        logp, ent = lax.map(lambda t: one(*t), (h.reshape(N // block, block, -1), targets.reshape(N // block, block)))
    return logp.reshape(N), ent.reshape(N)


def held_shares(cfg: SeqPolConfig, size: int) -> List[Tuple[int, ...]]:
    """The expert ids of an uncut layer in shares of ``size``: what each of
    ``n_routed_experts / size`` chips would be told it holds."""
    return [tuple(range(i, i + size)) for i in range(0, cfg.n_routed_experts, size)]
