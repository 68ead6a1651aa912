"""Recurrent PPO with the decoder core on a model of sliding-window and
full-attention layers (``mellum``: grouped-query attention in every layer,
three of four inside a window whose state is a ring, one over the whole
context under YaRN, each over a routed expert layer with a softmax router), as
``"reference": "token_ppo_mellum2"`` in a configuration's file names it:
everything README.md asks of an algorithm module, and the functions that count
the FLOPs and bytes of its programs.

The loop, its programs and what is recorded of a run are the token policy's
(``algorithms/token_ppo.py``: ``Capture``, the recording train function, the
look at the rollout, GAE, the gaps of the losses and the gradient); what is
this model's is here: the bridge that seeds it, the reference it is compared
with, the player's numbers behind a prefill and past a wrapped ring, the
counts, and the device's time under the window layers' own scope.
"""

from __future__ import annotations

import contextlib
import json
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from perfbench.algorithms.token_ppo import (  # noqa: F401  (what is no model's)
    PLAYER_FORWARDS,
    TRAIN_STEPS,
    Capture,
    _reference_programs_stay_out_of_a_limited_cache,
    aligned_sequences,
    check_stated,
    episodes_of,
    gae_gap,
    installed as installed_token_policy,
    leaf_spans,
    program_side,
    programs,
    rollout_rows,
    train_gaps,
    train_program,
)
from perfbench.correct import judge

#: the scopes inside the train step (the player's programs carry the same). ``seqpol/attn`` takes the window layers'
#: ``seqpol/attn/window`` with it: ``train_step.attn_device_ms`` is the attention of both kinds, whole
scopes = ("seqpol/embed", "seqpol/attn", "seqpol/moe/route", "seqpol/moe/experts", "seqpol/head", "seqpol/optimizer")
#: a window layer's scores, softmax and weighted sum, without its projections: read by a reduction of its own
#: (:func:`window_scope_ms`). The second form is the same scope on an op whose path a transformation's name closes
#: between the two (``jvp(seqpol/attn)/window/...``: a minibatch of one chunk of rows, which is not mapped over)
window_scopes = ("seqpol/attn/window", "seqpol/attn)/window")

SLIDING, FULL = "sliding_attention", "full_attention"

# --------------------------------------------------------------------------- #
# FLOPs and bytes, from shapes and counters
# --------------------------------------------------------------------------- #


def _layers(m: Dict[str, Any]) -> Dict[str, int]:
    kinds = list(m["layer_types"])
    return {"window": kinds.count(SLIDING), "full": kinds.count(FULL), "moe": m["num_hidden_layers"] - m["first_k_dense_replace"]}


def _per_token(m: Dict[str, Any]) -> Dict[str, float]:
    """Multiply-adds of one position in one layer's parts (weights only)."""
    d, H, G, hd = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    return {
        "attn": d * H * hd + 2 * d * G * hd + H * hd * d,  # W_q, W_k, W_v, W_o
        "score": 2 * H * hd,  # per query and key: q k^T and the weighted sum of values
        "router": d * m["n_routed_experts"],
        "expert": 3 * d * m["moe_intermediate_size"],  # per routed pair
        "head": d * m["vocab_rows"] + d,
    }


def _expected_pairs(m: Dict[str, Any], positions: float) -> float:
    """Routed pairs on held experts under even routing, over all expert layers."""
    return positions * _layers(m)["moe"] * m["num_experts_per_tok"] * len(m["held_experts"]) / m["n_routed_experts"]


def sequence_flops(m: Dict[str, Any], rows: float, slots: float, held_pairs: float, head_slots: float, continues: bool) -> float:
    """Forward FLOPs of the whole-sequence form as the program computes it on
    ``rows x slots`` positions: every query scores every key of its row, masked
    or not, the row's own ``slots`` and, where the rows continue from a state
    (``continues``), the ``context`` entries of a full layer's cache and the
    ``sliding_window`` entries of a window layer's ring; the routed experts on
    ``held_pairs`` pairs in all; the head on ``head_slots`` positions a row."""
    t, n = _per_token(m), _layers(m)
    positions = rows * slots
    keys = n["full"] * (slots + continues * m["context"]) + n["window"] * (slots + continues * m["sliding_window"])
    total = positions * ((n["full"] + n["window"]) * t["attn"] + n["moe"] * t["router"]) + positions * keys * t["score"]
    return 2.0 * (total + held_pairs * t["expert"] + rows * head_slots * t["head"])


def train_step_flops(config: Dict[str, Any], held_pairs: Optional[float] = None) -> float:
    """Model FLOPs of one ``seqpol_train_step``: forward and backward (three
    times the forward) over one minibatch of padded sequences, each scoring
    the whole state it may continue from; routed pairs at their expected share
    of the held experts unless the counted ``held_pairs`` are given."""
    m, a = config["model"], config["algo"]
    rows, slots = a["batch_size"], a["sequence_length"]
    pairs = _expected_pairs(m, rows * slots) if held_pairs is None else held_pairs
    return 3.0 * sequence_flops(m, rows, slots, pairs, slots - m["prompt_max"], True)


def model_flops(config: Dict[str, Any]) -> int:
    """What ``model_flops_per_grad_step`` in a configuration's file is held against."""
    return int(train_step_flops(config))


def prefill_flops(config: Dict[str, Any], held_pairs: Optional[float] = None) -> float:
    """One ``seqpol_prefill``: ``prefill_rows`` prompts of ``prompt_max`` slots, no state behind them, no head."""
    m, a = config["model"], config["algo"]
    rows, slots = a["prefill_rows"], m["prompt_max"]
    return sequence_flops(m, rows, slots, _expected_pairs(m, rows * slots) if held_pairs is None else held_pairs, 0, False)


def decode_flops(config: Dict[str, Any]) -> float:
    """One ``seqpol_decode``: one position a row, the full layer against its
    whole cache and each window layer against its whole ring, every held
    expert on every row under a mask, the head."""
    m = config["model"]
    t, n = _per_token(m), _layers(m)
    per_row = (n["full"] + n["window"]) * t["attn"] + (n["full"] * m["context"] + n["window"] * m["sliding_window"]) * t["score"]
    per_row += n["moe"] * (t["router"] + len(m["held_experts"]) * t["expert"]) + t["head"]
    return 2.0 * config["algo"]["num_envs"] * per_row


def decode_bytes(config: Dict[str, Any], cache_positions: float) -> float:
    """The bytes one decode step cannot avoid: the weights it touches in the
    compute dtype (every layer's attention, router and held experts, the head,
    of the embedding a row a token), ``cache_positions`` key and value entries
    in every attention layer (the ``seqpol/update`` counter: for each row the
    full layer's entries up to its length and each ring's up to ``min(length,
    sliding_window)``, as a mean over the four layers), and the logits it writes."""
    m = config["model"]
    t, n = _per_token(m), _layers(m)
    E = config["algo"]["num_envs"]
    weights = (n["full"] + n["window"]) * t["attn"] + n["moe"] * (t["router"] + len(m["held_experts"]) * t["expert"]) + t["head"] + E * m["hidden_size"]
    cache = cache_positions * (n["full"] + n["window"]) * 2 * m["num_key_value_heads"] * m["head_dim"]
    return 2.0 * (weights + cache) + 4.0 * E * m["vocab_rows"]


def window_flops(config: Dict[str, Any], window_keys: float) -> float:
    """Forward and backward FLOPs (three times the forward) of the scores and
    weighted sums that the band cannot avoid: ``window_keys`` pairs of a query
    and a key inside its window (the ``seqpol/update`` counter, summed over the
    window layers), ``q k^T`` and the weighted values over every head's dims."""
    return 3.0 * 2.0 * window_keys * _per_token(config["model"])["score"]


def scored_keys(config: Dict[str, Any]) -> float:
    """The pairs of a query and a key that the window layers of one
    ``seqpol_train_step`` score, masked or not: what ``window_keys`` a
    gradient step is a share of."""
    m, a = config["model"], config["algo"]
    return _layers(m)["window"] * a["batch_size"] * a["sequence_length"] * (a["sequence_length"] + m["sliding_window"])


def window_scope_ms(run: Any) -> Optional[float]:
    """Device self time per train-step execution under ``seqpol/attn/window``,
    forward and backward, over the traced stretch as ``device_time.of_run``
    reads the step, in a reduction of its own whose scopes are the inner
    one's two forms; ``None`` where the run has no trace or the program no
    such scope."""
    from perfbench import device_time

    if "_window_scope_ms" not in run.__dict__:
        reduced = device_time.reduce_run(run, window_scopes, leaves=False)
        run.__dict__["_window_scope_ms"] = device_time.scope_ms(reduced, window_scopes) if reduced else None
    return run.__dict__["_window_scope_ms"]


# --------------------------------------------------------------------------- #
# the bridge into the program
# --------------------------------------------------------------------------- #


@contextlib.contextmanager
def installed(capture: Capture):
    """``token_ppo.installed`` (the recording player, rollout and train
    function are no model's) with this model's reference behind the seeded
    weights: while open, ``ppo_recurrent``'s decoder core builds its policy
    from the benchmark's weights for this configuration."""
    import jax

    from sheeprl_tpu.algos.ppo_recurrent import token_policy as program

    from perfbench.references import token_ppo_mellum2 as reference

    real = program.build_token_agent

    def build_token_agent(fabric, cfg, obs_space, action_space, agent_state=None):
        if agent_state is not None:
            raise RuntimeError("perfbench: the benchmark does not resume a checkpoint")
        check_stated(capture.cfg, cfg)
        seeded = reference.init_weights(capture.cfg, capture.seed)
        capture.seeded = jax.device_get(seeded)
        return real(fabric, cfg, obs_space, action_space, seeded)

    with installed_token_policy(capture):  # on the way out it puts back all four names it patched, this one among them
        program.build_token_agent = build_token_agent
        yield capture


# --------------------------------------------------------------------------- #
# the comparison that decides ``correct``
# --------------------------------------------------------------------------- #


def player_gaps(m: Dict[str, Any], weights: Any, forwards: List[Dict[str, np.ndarray]], against: Optional[Dict[str, np.ndarray]] = None,
                without: Tuple[int, ...] = (), faults: Tuple[str, ...] = (), ring_kept: bool = False) -> Tuple[Dict[str, float], Dict[str, np.ndarray]]:  # fmt: skip
    """The reference's full forward over every recorded episode, against what
    the player's decode gave through both kinds of state (or ``against``,
    another side's arrays): per recorded output the gap of the logits (norm of
    the difference over the norm of the reference's) and of the value (over the
    values' root mean square); their medians, the worst,
    ``player_reset_logits`` (the median over each episode's first recorded
    forward, the decode right behind a prefill) and ``player_window_logits``:
    the median over the forwards whose row stands at position
    ``sliding_window`` or beyond, where the ring has wrapped (a median over
    all rows would hide a fault that only a wrapped ring shows); 0 where the
    run recorded none. ``faults`` and ``ring_kept`` plant the reference's
    faults (``references``' ``attention``). Returns the reference's arrays too."""
    import jax
    import jax.numpy as jnp

    from perfbench.references import token_ppo_mellum2 as reference

    F, E = len(forwards), forwards[0]["logits"].shape[0]
    size = forwards[0]["tokens"].shape[1] + F  # a prompt and every recorded step: one length, one compilation
    W, G, hd = m["sliding_window"], m["num_key_value_heads"], m["head_dim"]
    logits = np.zeros((F, E, m["vocab_rows"]), np.float32)
    values = np.zeros((F, E), np.float32)
    first = np.zeros((F, E), bool)
    sound = jax.jit(lambda w, tokens: reference.forward(w, m, tokens, without, faults)[:2])
    faulty = jax.jit(lambda w, tokens, p, rings: reference.forward(w, m, tokens, without, faults, (p, F, rings)))
    window_layers = [i for i, kind in enumerate(m["layer_types"]) if kind == reference.SLIDING]
    for row in range(E):
        rings = [(jnp.zeros((W, G, hd), jnp.float32),) * 2 for _ in window_layers]  # as the player's state begins
        for episode in episodes_of(forwards, row):
            tokens = np.zeros((size,), np.int32)
            tokens[: len(episode["inputs"])] = episode["inputs"]
            if ring_kept:
                p, end = episode["at"][0][1], len(episode["inputs"])
                lg, vl, entries = faulty(weights, jnp.asarray(tokens), p, rings)
                at = np.arange(p, end)  # what the faulty player wrote: its decodes' entries, each at its position mod W
                rings = [tuple(r.at[at % W].set(e[at]) for r, e in zip(rings[n], entries[i])) for n, i in enumerate(window_layers)]
            else:
                lg, vl = sound(weights, jnp.asarray(tokens))
            for f, position in episode["at"]:
                logits[f, row], values[f, row] = np.asarray(lg[position]), float(vl[position])
            first[episode["at"][0][0], row] = True
    theirs = against or {"logits": np.stack([c["logits"] for c in forwards]), "values": np.stack([c["values"] for c in forwards])}
    gap_l = np.linalg.norm(theirs["logits"] - logits, axis=-1) / np.linalg.norm(logits, axis=-1)
    gap_v = np.abs(theirs["values"] - values) / np.sqrt(np.mean(values**2))
    wrapped = np.stack([c["positions"] for c in forwards]) >= W
    numbers = {"player_logits": float(np.median(gap_l)), "player_values": float(np.median(gap_v)),
               "player_reset_logits": float(np.median(gap_l[first])),
               "player_window_logits": float(np.median(gap_l[wrapped])) if wrapped.any() else 0.0,
               "player_logits_worst": float(gap_l.max()), "player_values_worst": float(gap_v.max())}  # fmt: skip
    return numbers, {"logits": logits, "values": values}


def train_side(cfg: Dict[str, Any], weights: Any, steps: List[Dict[str, Any]], without: Tuple[int, ...] = (), half_batch: bool = False,
               faults: Tuple[str, ...] = ()) -> Dict[str, Any]:  # fmt: skip
    """The reference through the recorded gradient steps: the first step's
    losses, clipped gradient and the weights after one AdamW step (both on the
    host), and the second step's losses at those weights. The model has no
    multi-token-prediction term: it reads 0 on both sides."""
    import jax

    from perfbench.references import token_ppo_mellum2 as reference

    m, a = cfg["model"], cfg["algo"]
    sequences = aligned_sequences(steps[0]["batch"])
    if half_batch:
        sequences = sequences[: len(sequences) // 2]
    losses, grads = reference.loss_and_grad(weights, m, a, sequences, without, faults)
    grads = reference.clip_by_global_norm(grads, a["max_grad_norm"])
    after = reference.adamw_first_step(weights, grads, a)
    out = {"losses": [{**losses, "mtp_loss": 0.0}], "grad": jax.device_get(grads), "grad_scale": 1.0}
    del grads
    if len(steps) > 1:
        out["losses"].append({**reference.losses_only(after, m, a, aligned_sequences(steps[1]["batch"]), without, faults), "mtp_loss": 0.0})
    out["after"] = jax.device_get(after)
    return out


def verify(cfg: Dict[str, Any], seed: int, capture: Capture, limits: Dict[str, float], stamps: Optional[str] = None):
    """``(correct, compared, not_compared)`` for one run of the program."""
    import jax

    numbers: Dict[str, float] = {"program_renamed": float(capture.program != train_program),
                                 "steps_missing": float(TRAIN_STEPS - len(capture.steps)),
                                 "forwards_missing": float(PLAYER_FORWARDS - len(capture.player))}  # fmt: skip
    took: Dict[str, float] = {}

    @contextlib.contextmanager
    def timed(name: str):
        t0 = time.monotonic()
        yield
        took[name] = round(time.monotonic() - t0, 2)

    with _reference_programs_stay_out_of_a_limited_cache():
        weights = jax.device_put(capture.seeded) if capture.seeded is not None else None
        if weights is not None and len(capture.player) == PLAYER_FORWARDS:
            with timed("player"):
                numbers.update(player_gaps(cfg["model"], weights, capture.player)[0])
            resets = sum(int((c["n_tokens"] > 1).sum()) for c in capture.player[1:])
            numbers["resets_missing"] = float(resets == 0)  # the forwards have to cross a reset and its prefill
            wrapped = sum(int((c["positions"] >= cfg["model"]["sliding_window"]).sum()) for c in capture.player)
            numbers["wraps_missing"] = float(wrapped == 0)  # and some of them have to stand on a wrapped ring
        if capture.rollout is not None:
            with timed("rollout"):
                if stamps is not None:
                    numbers["rollout_rows"] = float(rollout_rows(cfg, seed, capture.rollout, stamps))
                numbers["gae"] = gae_gap(cfg, capture.rollout)
        if weights is not None and len(capture.steps) == TRAIN_STEPS:
            with timed("reference_steps"):
                ref = train_side(cfg, weights, capture.steps)
            with timed("gaps"):
                ours = program_side(cfg, capture.steps)
                numbers.update(train_gaps(ours, ref, capture.seeded))
            for side, tree in (("program", ours["grad"]), ("reference", ref["grad"])):
                bad = [jax.tree_util.keystr(path) for path, leaf in jax.tree_util.tree_leaves_with_path(tree) if not np.isfinite(leaf).all()]
                if bad:
                    print(f"[perfbench] leaves of the {side}'s first gradient that hold a NaN or an infinity: {bad}", flush=True)
    print(f"[perfbench] the comparison's parts took (s): {json.dumps(took)}", flush=True)
    compared = judge(numbers, limits)
    return all(v["ok"] for v in compared.values()), compared, {k: v for k, v in numbers.items() if k not in limits}
