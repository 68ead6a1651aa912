"""Recurrent PPO with the decoder core on a hybrid model (gated short
convolutions beside grouped-query attention over a dense or a routed expert
layer: ``lfm2_moe``), as ``"reference": "token_ppo_lfm2"`` in a configuration's
file names it: everything README.md asks of an algorithm module, and the
functions that count the FLOPs and bytes of its programs.

The loop, its programs and what is recorded of a run are the token policy's
(``algorithms/token_ppo.py``: ``Capture``, the recording train function, the
look at the rollout, GAE, the gaps of the losses and the gradient); what is
this model's is here: the bridge that seeds it, the reference it is compared
with, the player's number behind a prefill, and the counts.
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

#: the scopes inside the train step (the player's programs carry the same); the first scope found in an op's path
#: takes it, so the convolution's two inner scopes stand before the scope they lie in
scopes = ("seqpol/embed", "seqpol/conv/proj", "seqpol/conv/mix", "seqpol/conv", "seqpol/attn", "seqpol/moe/route", "seqpol/moe/experts",
          "seqpol/mlp", "seqpol/head", "seqpol/optimizer")  # fmt: skip
#: the scopes that make up the gated short convolution's time
conv_scopes = ("seqpol/conv/proj", "seqpol/conv/mix", "seqpol/conv")

# --------------------------------------------------------------------------- #
# FLOPs and bytes, from shapes and counters
# --------------------------------------------------------------------------- #


def _layers(m: Dict[str, Any]) -> Dict[str, int]:
    kinds = list(m["layer_types"])
    n_dense = m["first_k_dense_replace"]
    return {"conv": kinds.count("conv"), "attn": kinds.count("full_attention"), "dense": n_dense, "moe": m["num_hidden_layers"] - n_dense}


def _per_token(m: Dict[str, Any]) -> Dict[str, float]:
    """Multiply-adds of one position in one layer's parts (weights; the convolution's taps too)."""
    d, H, G, hd = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    return {
        "conv": 3 * d * d + d * d + m["conv_L_cache"] * d,  # W_in, W_out, the depthwise taps
        "attn": d * H * hd + 2 * d * G * hd + H * hd * d,  # W_q, W_k, W_v, W_o
        "score": 2 * H * hd,  # per query and key: q k^T and the weighted sum of values
        "mlp": 3 * d * m["intermediate_size"],
        "router": d * m["n_routed_experts"],
        "expert": 3 * d * m["moe_intermediate_size"],  # per routed pair
        "head": d * m["vocab_rows"] + d,
    }


def _expected_pairs(m: Dict[str, Any], positions: float) -> float:
    """Routed pairs on held experts under even routing, over all expert layers."""
    return positions * _layers(m)["moe"] * m["num_experts_per_tok"] * len(m["held_experts"]) / m["n_routed_experts"]


def sequence_flops(m: Dict[str, Any], rows: float, slots: float, ctx: float, held_pairs: float, head_slots: float) -> float:
    """Forward FLOPs of the whole-sequence form on ``rows x slots`` positions,
    each row's queries scoring ``slots + ctx`` keys in every attention layer;
    the routed experts on ``held_pairs`` pairs in all; the head on
    ``head_slots`` positions a row."""
    t, n = _per_token(m), _layers(m)
    positions = rows * slots
    total = positions * (n["conv"] * t["conv"] + n["attn"] * t["attn"] + n["dense"] * t["mlp"] + n["moe"] * t["router"])
    total += n["attn"] * positions * (slots + ctx) * t["score"] + held_pairs * t["expert"] + rows * head_slots * t["head"]
    return 2.0 * total


def train_step_flops(config: Dict[str, Any], held_pairs: Optional[float] = None) -> float:
    """Model FLOPs of one ``seqpol_train_step``: forward and backward (three
    times the forward) over one minibatch of padded sequences, each attending
    to a full context; routed pairs at their expected share of the held
    experts unless the counted ``held_pairs`` are given."""
    m, a = config["model"], config["algo"]
    rows, slots = a["batch_size"], a["sequence_length"]
    pairs = _expected_pairs(m, rows * slots) if held_pairs is None else held_pairs
    return 3.0 * sequence_flops(m, rows, slots, m["context"], pairs, slots - m["prompt_max"])


def model_flops(config: Dict[str, Any]) -> int:
    """What ``model_flops_per_grad_step`` in a configuration's file is held against."""
    return int(train_step_flops(config))


def prefill_flops(config: Dict[str, Any], held_pairs: Optional[float] = None) -> float:
    """One ``seqpol_prefill``: ``prefill_rows`` prompts of ``prompt_max`` slots, no head."""
    m, a = config["model"], config["algo"]
    rows, slots = a["prefill_rows"], m["prompt_max"]
    return sequence_flops(m, rows, slots, 0, _expected_pairs(m, rows * slots) if held_pairs is None else held_pairs, 0)


def decode_flops(config: Dict[str, Any]) -> float:
    """One ``seqpol_decode``: one position a row, its attention layers
    against the whole cache, every held expert on every row under a mask, the
    head."""
    m = config["model"]
    t, n = _per_token(m), _layers(m)
    per_row = n["conv"] * t["conv"] + n["attn"] * (t["attn"] + m["context"] * t["score"]) + n["dense"] * t["mlp"]
    per_row += n["moe"] * (t["router"] + len(m["held_experts"]) * t["expert"]) + t["head"]
    return 2.0 * config["algo"]["num_envs"] * per_row


def decode_bytes(config: Dict[str, Any], cache_positions: float) -> float:
    """The bytes one decode step cannot avoid: the weights it touches in the
    compute dtype (every layer's operator, the dense layer, every router and
    held expert, the embedding's rows as the head), ``cache_positions`` key
    and value entries (the rows' lengths summed) in every attention layer, the
    convolution states read and written, and the logits it writes."""
    m = config["model"]
    t, n = _per_token(m), _layers(m)
    E, d = config["algo"]["num_envs"], m["hidden_size"]
    weights = n["conv"] * t["conv"] + n["attn"] * t["attn"] + n["dense"] * t["mlp"] + n["moe"] * (t["router"] + len(m["held_experts"]) * t["expert"])
    weights += t["head"] + (0 if m["tie_word_embeddings"] else E * d)
    cache = cache_positions * n["attn"] * 2 * m["num_key_value_heads"] * m["head_dim"]
    states = 2 * E * n["conv"] * m["conv_L_cache"] * d
    return 2.0 * (weights + cache + states) + 4.0 * E * m["vocab_rows"]


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

    from perfbench.references import token_ppo_lfm2 as reference

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
                without: Tuple[int, ...] = (), conv_state_kept: bool = False) -> Tuple[Dict[str, float], Dict[str, np.ndarray]]:  # fmt: skip
    """The reference's full forward over every recorded episode, against what the player's decode gave through
    both kinds of state (or ``against``, another side's arrays): per recorded
    output the gap of the logits (norm of the difference over the norm of the
    reference's) and of the value (over the values' root mean square); their
    medians, the worst, and ``player_reset_logits``: the median over each
    episode's first recorded forward, the decode right behind a prefill, which
    reads what the prefill left of both kinds (the convolution state matters
    to the two positions behind it and to nothing later). ``conv_state_kept``
    plants the fault of that name in the reference (``references``'
    ``conv``). Returns the reference's arrays too."""
    import jax
    import jax.numpy as jnp

    from perfbench.references import token_ppo_lfm2 as reference

    F, E = len(forwards), forwards[0]["logits"].shape[0]
    size = forwards[0]["tokens"].shape[1] + F  # a prompt and every recorded step: one length, one compilation
    logits = np.zeros((F, E, m["vocab_rows"]), np.float32)
    values = np.zeros((F, E), np.float32)
    first = np.zeros((F, E), bool)
    sound = jax.jit(lambda w, tokens: reference.forward(w, m, tokens, without))
    faulty = jax.jit(lambda w, tokens, p, held: reference.forward(w, m, tokens, without, (p, held)))
    taps = m["conv_L_cache"] - 1
    for row in range(E):
        held = [jnp.zeros((taps, m["hidden_size"]), jnp.float32) for kind in m["layer_types"] if kind == "conv"]
        for episode in episodes_of(forwards, row):
            tokens = np.zeros((size,), np.int32)
            tokens[: len(episode["inputs"])] = episode["inputs"]
            if conv_state_kept:
                lg, vl, gated = faulty(weights, jnp.asarray(tokens), episode["at"][0][1], held)
                end = len(episode["inputs"])  # the state as the episode's last decode left it: its latest entries
                held = [jnp.concatenate([jnp.zeros((taps, z.shape[1])), z[:end]])[-taps:] for z in gated]
            else:
                lg, vl, _ = sound(weights, jnp.asarray(tokens))
            for f, position in episode["at"]:
                logits[f, row], values[f, row] = np.asarray(lg[position]), float(vl[position])
            first[episode["at"][0][0], row] = True
    theirs = against or {"logits": np.stack([c["logits"] for c in forwards]), "values": np.stack([c["values"] for c in forwards])}
    gap_l = np.linalg.norm(theirs["logits"] - logits, axis=-1) / np.linalg.norm(logits, axis=-1)
    gap_v = np.abs(theirs["values"] - values) / np.sqrt(np.mean(values**2))
    numbers = {"player_logits": float(np.median(gap_l)), "player_values": float(np.median(gap_v)),
               "player_reset_logits": float(np.median(gap_l[first])),
               "player_logits_worst": float(gap_l.max()), "player_values_worst": float(gap_v.max())}  # fmt: skip
    return numbers, {"logits": logits, "values": values}


def train_side(cfg: Dict[str, Any], weights: Any, steps: List[Dict[str, Any]], without: Tuple[int, ...] = (), half_batch: bool = False) -> Dict[str, Any]:
    """The reference through the recorded gradient steps: the first step's
    losses, clipped gradient and the weights after one AdamW step (both on the
    host), and the second step's losses at those weights. The model has no
    multi-token-prediction term: it reads 0 on both sides."""
    import jax

    from perfbench.references import token_ppo_lfm2 as reference

    m, a = cfg["model"], cfg["algo"]
    sequences = aligned_sequences(steps[0]["batch"])
    if half_batch:
        sequences = sequences[: len(sequences) // 2]
    losses, grads = reference.loss_and_grad(weights, m, a, sequences, without)
    grads = reference.clip_by_global_norm(grads, a["max_grad_norm"])
    after = reference.adamw_first_step(weights, grads, a)
    out = {"losses": [{**losses, "mtp_loss": 0.0}], "grad": jax.device_get(grads), "grad_scale": 1.0}
    del grads
    if len(steps) > 1:
        out["losses"].append({**reference.losses_only(after, m, a, aligned_sequences(steps[1]["batch"]), without), "mtp_loss": 0.0})
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
