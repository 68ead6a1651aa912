"""Recurrent PPO with the decoder core (a policy over tokens), as
``"reference": "token_ppo"`` in a configuration's file names it: everything
README.md asks of an algorithm module, and the functions that count the
FLOPs and bytes of its programs.

It patches four names of ``sheeprl_tpu.algos.ppo_recurrent.token_policy`` for
the length of a run: ``build_token_agent`` gets the benchmark's seeded weights
through its own state argument (and the composed configuration is held against
the file's there), ``TokenPlayer`` records its first forwards (the logits and
values the timed ``seqpol_decode`` produced, across the first reset and its
prefill), ``token_sequences`` the first rollout as the update got it, and
``make_token_train_fn``'s result the first two gradient steps through the
compiled ``seqpol_train_step`` that the window then times.
"""

from __future__ import annotations

import contextlib
import json
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from perfbench.bridge import check_stated  # noqa: F401  (the same rule: ``program_keys``, key by key)
from perfbench.correct import judge

#: the programs the loop dispatches
programs = ("seqpol_prefill", "seqpol_decode", "seqpol_train_step", "seqpol_snapshot", "seqpol_player_params")
train_program = "seqpol_train_step"
#: the scopes inside the train step (the player's programs carry the same)
#: ``seqpol/mtp`` first: its block carries the trunk's scopes inside its own, and the first scope found in an op's path takes it
scopes = ("seqpol/mtp", "seqpol/embed", "seqpol/attn", "seqpol/moe/route", "seqpol/moe/experts", "seqpol/moe/shared", "seqpol/mlp",
          "seqpol/head", "seqpol/optimizer")  # fmt: skip
#: the host's leaf spans, nested in the loop's two window spans
leaf_spans = ("player/prefill", "player/decode", "player/get_actions", "env/step", "rollout/store", "train/dispatch", "train/block")

#: forwards of the player that are recorded: the first episode ends inside them, so a reset and its prefill lie among them
PLAYER_FORWARDS = 12
#: gradient steps that are recorded
TRAIN_STEPS = 2
#: a leaf counts as large from this many numbers on (at the tests' widths every leaf does)
LARGE_LEAF = 1_000_000


# --------------------------------------------------------------------------- #
# FLOPs and bytes, from shapes and counters
# --------------------------------------------------------------------------- #


def _per_token(m: Dict[str, Any]) -> Dict[str, float]:
    """Multiply-adds of one position in one layer's parts (weights only)."""
    d, H = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return {
        "attn_in": d * m["q_lora_rank"] + m["q_lora_rank"] * H * qk + d * (m["kv_lora_rank"] + m["qk_rope_head_dim"]),
        "attn_out": H * m["v_head_dim"] * d,
        "kv_expand": m["kv_lora_rank"] * H * (m["qk_nope_head_dim"] + m["v_head_dim"]),  # per key
        "score": H * (qk + m["v_head_dim"]),  # per query and key
        "mlp": 3 * d * m["intermediate_size"],
        "router": d * m["n_routed_experts"],
        "shared": 3 * d * m["moe_intermediate_size"] * m["n_shared_experts"],
        "expert": 3 * d * m["moe_intermediate_size"],  # per routed pair
        "head": d * m["vocab_rows"] + d,
        "mtp_in": 2 * d * d,
    }


def sequence_flops(m: Dict[str, Any], rows: float, slots: float, ctx: float, held_pairs: float, *, head_slots: float, mtp_slots: float,
                   mtp_pairs: float = 0.0) -> float:  # fmt: skip
    """Forward matmul FLOPs of the whole-sequence form on ``rows x slots``
    positions, each row expanding and scoring ``slots + ctx`` keys; the routed
    experts on ``held_pairs`` pairs in all; the head on ``head_slots``
    positions a row; the multi-token-prediction module on ``mtp_slots`` a row."""
    t = _per_token(m)
    n_dense = m["first_k_dense_replace"]
    n_moe = m["num_hidden_layers"] - n_dense
    positions = rows * slots
    layer = positions * (t["attn_in"] + t["attn_out"]) + rows * (slots + ctx) * t["kv_expand"] + rows * slots * (slots + ctx) * t["score"]
    total = m["num_hidden_layers"] * layer + n_dense * positions * t["mlp"] + n_moe * positions * (t["router"] + t["shared"]) + held_pairs * t["expert"]
    total += rows * head_slots * t["head"]
    if mtp_slots:
        p = rows * mtp_slots
        total += p * (t["mtp_in"] + t["attn_in"] + t["attn_out"] + t["kv_expand"] + mtp_slots * t["score"] + t["router"] + t["shared"] + t["head"])
        total += mtp_pairs * t["expert"]
    return 2.0 * total


def train_step_flops(config: Dict[str, Any], held_pairs: Optional[float] = None) -> float:
    """Model FLOPs of one ``seqpol_train_step``: forward and backward (three
    times the forward) over one minibatch of padded sequences, each attending
    to a full context; routed pairs at their expected share of the held
    experts unless the counted ``held_pairs`` are given."""
    m, a = config["model"], config["algo"]
    rows = a["batch_size"]
    slots, steps = a["sequence_length"], a["sequence_length"] - m["prompt_max"]
    share = len(m["held_experts"]) / m["n_routed_experts"]
    n_moe = m["num_hidden_layers"] - m["first_k_dense_replace"]
    mtp = steps if m["num_nextn_predict_layers"] else 0
    expected = rows * share * m["num_experts_per_tok"] * (slots * n_moe + mtp)
    pairs = expected if held_pairs is None else held_pairs
    trunk_pairs = pairs * (slots * n_moe) / (slots * n_moe + mtp)
    return 3.0 * sequence_flops(m, rows, slots, m["context"], trunk_pairs, head_slots=steps, mtp_slots=mtp, mtp_pairs=pairs - trunk_pairs)


def expert_pair_flops(config: Dict[str, Any], pairs: float) -> float:
    """Forward and backward FLOPs of ``pairs`` routed pairs through one expert each."""
    return 3.0 * 2.0 * pairs * _per_token(config["model"])["expert"]


def model_flops(config: Dict[str, Any]) -> int:
    """What ``model_flops_per_grad_step`` in a configuration's file is held against."""
    return int(train_step_flops(config))


def prefill_flops(config: Dict[str, Any], held_pairs: Optional[float] = None) -> float:
    """One ``seqpol_prefill``: ``prefill_rows`` prompts of ``prompt_max`` slots, no head."""
    m, a = config["model"], config["algo"]
    rows, slots = a["prefill_rows"], m["prompt_max"]
    n_moe = m["num_hidden_layers"] - m["first_k_dense_replace"]
    expected = rows * slots * n_moe * m["num_experts_per_tok"] * len(m["held_experts"]) / m["n_routed_experts"]
    return sequence_flops(m, rows, slots, 0, expected if held_pairs is None else held_pairs, head_slots=0, mtp_slots=0)


def decode_flops(config: Dict[str, Any]) -> float:
    """One ``seqpol_decode``: one position a row in the absorbed form against
    the whole cache, every held expert on every row under a mask, the head."""
    m = config["model"]
    t = _per_token(m)
    E, C, H = config["algo"]["num_envs"], m["context"], m["num_attention_heads"]
    absorbed = H * m["kv_lora_rank"] * (m["qk_nope_head_dim"] + m["v_head_dim"]) + C * H * (2 * m["kv_lora_rank"] + m["qk_rope_head_dim"])
    n_dense = m["first_k_dense_replace"]
    n_moe = m["num_hidden_layers"] - n_dense
    layer = t["attn_in"] + t["attn_out"] + absorbed
    total = m["num_hidden_layers"] * layer + n_dense * t["mlp"] + n_moe * (t["router"] + t["shared"] + len(m["held_experts"]) * t["expert"]) + t["head"]
    return 2.0 * E * total


def decode_bytes(config: Dict[str, Any], cache_positions: float) -> float:
    """The bytes one decode step cannot avoid: the weights it touches in the
    compute dtype (every layer, every held expert, the head; of the embedding a
    row a token), ``cache_positions`` cache entries (the rows' lengths summed)
    in every layer, and the logits it writes."""
    m = config["model"]
    t = _per_token(m)
    E = config["algo"]["num_envs"]
    n_dense = m["first_k_dense_replace"]
    n_moe = m["num_hidden_layers"] - n_dense
    weights = m["num_hidden_layers"] * (t["attn_in"] + t["attn_out"] + t["kv_expand"]) + n_dense * t["mlp"]
    weights += n_moe * (t["router"] + t["shared"] + len(m["held_experts"]) * t["expert"]) + t["head"] + E * m["hidden_size"]
    cache = cache_positions * m["num_hidden_layers"] * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
    return 2.0 * (weights + cache) + 4.0 * E * m["vocab_rows"]


# --------------------------------------------------------------------------- #
# the bridge into the program
# --------------------------------------------------------------------------- #


class Capture:
    """What is kept of the program's run: how often it trained (``calls``,
    read by the harness), where (``placement``), the seeded weights, the
    player's first forwards, the first rollout and the first gradient steps."""

    def __init__(self, cfg: Dict[str, Any], seed: int) -> None:
        self.cfg, self.seed = cfg, seed
        self.calls = 0
        self.placement: Dict[str, Any] = {}
        self.seeded: Any = None
        self.player: List[Dict[str, np.ndarray]] = []
        self.rollout: Optional[Dict[str, np.ndarray]] = None
        self.steps: List[Dict[str, Any]] = []
        self.program: Optional[str] = None


def _first_moment(opt_state: Any) -> Any:
    """Adam's ``mu`` inside the optimizer's state, whatever it is chained with."""
    import jax

    found = [s for s in jax.tree.leaves(opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    return found[0].mu


class _TrainFn:
    def __init__(self, fn, capture: Capture) -> None:
        self._fn, self._capture = fn, capture

    def __getattr__(self, name):
        return getattr(self._fn, name)

    def __call__(self, params, opt_state, params_lo, batch, *rest):
        import jax

        cap = self._capture
        cap.calls += 1
        if len(cap.steps) >= TRAIN_STEPS:
            return self._fn(params, opt_state, params_lo, batch, *rest)
        step: Dict[str, Any] = {"batch": {k: np.array(v) for k, v in batch.items()}}
        if not cap.steps:
            cap.placement["train_device"] = sorted(d.platform for d in jax.tree.leaves(params)[0].devices())[0]
        params, opt_state, params_lo, metrics = self._fn(params, opt_state, params_lo, batch, *rest)
        step["metrics"] = np.asarray(metrics)
        if not cap.steps:
            # the first step's clipped gradient is Adam's first moment over (1 - b1); the weights after it give the change
            step["mu"] = jax.device_get(_first_moment(opt_state))
            step["params"] = jax.device_get(params)
        cap.steps.append(step)
        return params, opt_state, params_lo, metrics


@contextlib.contextmanager
def installed(capture: Capture):
    """While open, ``ppo_recurrent``'s decoder core builds its policy from the
    benchmark's weights, plays through the recording player and trains through
    the recording wrapper."""
    import jax

    from sheeprl_tpu.algos.ppo_recurrent import token_policy as program

    from perfbench.references import token_ppo as reference

    real = {name: getattr(program, name) for name in ("build_token_agent", "make_token_train_fn", "TokenPlayer", "token_sequences")}

    def build_token_agent(fabric, cfg, obs_space, action_space, agent_state=None):
        if agent_state is not None:
            raise RuntimeError("perfbench: the benchmark does not resume a checkpoint")
        check_stated(capture.cfg, cfg)
        seeded = reference.init_weights(capture.cfg, capture.seed)
        capture.seeded = jax.device_get(seeded)
        return real["build_token_agent"](fabric, cfg, obs_space, action_space, seeded)

    def make_token_train_fn(fabric, agent, tx, cfg):
        fn = real["make_token_train_fn"](fabric, agent, tx, cfg)
        capture.program = getattr(fn, "__name__", None)
        return _TrainFn(fn, capture)

    class TokenPlayer(real["TokenPlayer"]):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            capture.placement["player_device"] = sorted(d.platform for d in self.cache_c[0].devices())[0]

        def act(self, tokens, n_tokens, key, counter):
            out = super().act(tokens, n_tokens, key, counter)
            if len(capture.player) < PLAYER_FORWARDS:
                capture.player.append({"tokens": np.array(tokens), "n_tokens": np.array(n_tokens), "positions": np.array(out[3]),
                                       "logits": np.asarray(self.last_logits), "values": np.asarray(out[2]), "actions": np.asarray(out[0])})  # fmt: skip
            return out

    def token_sequences(local_data, *args):
        if capture.rollout is None:
            capture.rollout = {k: np.array(v) for k, v in local_data.items()}  # a copy: the loop writes the next rollout into the same arrays
        return real["token_sequences"](local_data, *args)

    patched = {"build_token_agent": build_token_agent, "make_token_train_fn": make_token_train_fn, "TokenPlayer": TokenPlayer,
               "token_sequences": token_sequences}  # fmt: skip
    for name, fn in patched.items():
        setattr(program, name, fn)
    try:
        yield capture
    finally:
        for name, fn in real.items():
            setattr(program, name, fn)


# --------------------------------------------------------------------------- #
# the comparison that decides ``correct``
# --------------------------------------------------------------------------- #


def produced(cfg: Dict[str, Any], seed: int, index: int, handed: np.ndarray) -> List[Tuple[np.ndarray, int, float, float]]:
    """What env ``index`` gave the loop, step by step, on the actions it was
    ``handed``: the observation each action was chosen on (tokens, count), the
    reward, the episode end. Made again from the seed, through the env's own factory."""
    import importlib

    module, _, name = cfg["env"]["make"].rpartition(".")
    env = getattr(importlib.import_module(module), name)(cfg["name"], cfg["env"], seed + index, index)
    obs, _ = env.reset()
    rows = []
    for action in handed:
        now, reward, terminated, truncated, _ = env.step(int(action))
        rows.append((obs["tokens"], int(obs["n_tokens"][0]), reward, float(terminated or truncated)))
        obs = env.reset()[0] if terminated or truncated else now  # the vector env resets in the same step
    return rows


def rollout_rows(cfg: Dict[str, Any], seed: int, rollout: Dict[str, np.ndarray], stamps: str) -> int:
    """How many of the first rollout's ``steps x envs`` rows are not what the
    environments produced there (observation, reward, end) and were handed
    (the action, as each env logged it)."""
    from perfbench.env import read_action_log

    steps, envs = rollout["actions"].shape[:2]
    bad = 0
    for e in range(envs):
        handed = read_action_log(stamps, e, 1)[:steps, 0]
        for t, (tokens, n, reward, done) in enumerate(produced(cfg, seed, e, handed)):
            same = (np.array_equal(rollout["tokens"][t, e], tokens) and int(rollout["n_tokens"][t, e, 0]) == n
                    and rollout["rewards"][t, e, 0] == np.float32(reward) and rollout["dones"][t, e, 0] == done
                    and int(rollout["actions"][t, e, 0]) == int(handed[t]))  # fmt: skip
            bad += not same
    return bad


def episodes_of(forwards: List[Dict[str, np.ndarray]], row: int) -> List[Dict[str, Any]]:
    """The recorded forwards of one row as episodes: each ``inputs`` (every
    token the policy had been fed by its last recorded forward: the prompt,
    then the tokens taken) and ``at``, the ``(forward, position)`` pairs
    whose outputs were recorded."""
    out: List[Dict[str, Any]] = []
    for f, call in enumerate(forwards):
        n = int(call["n_tokens"][row])
        if n > 1 or not out:  # a prompt: a new episode
            out.append({"inputs": [int(t) for t in call["tokens"][row, :n]], "at": []})
        else:
            out[-1]["inputs"].append(int(call["tokens"][row, 0]))
        out[-1]["at"].append((f, len(out[-1]["inputs"]) - 1))
        assert int(call["positions"][row]) == len(out[-1]["inputs"]) - 1, "the player's cache length is not the episode's"
    return out


def player_gaps(m: Dict[str, Any], weights: Any, forwards: List[Dict[str, np.ndarray]], against: Optional[Dict[str, np.ndarray]] = None,
                without: Tuple[int, ...] = ()) -> Tuple[Dict[str, float], Dict[str, np.ndarray]]:  # fmt: skip
    """The reference's full forward over every recorded episode, against what
    the player's decode gave through the cache (or ``against``, another side's
    arrays): per recorded output the gap of the logits (norm of the difference
    over the norm of the reference's) and of the value (over the values' root
    mean square); the medians and the worst. Returns the reference's arrays too."""
    import jax
    import jax.numpy as jnp

    from perfbench.references import token_ppo as reference

    F, E = len(forwards), forwards[0]["logits"].shape[0]
    size = forwards[0]["tokens"].shape[1] + F  # a prompt and every recorded step: one length, one compilation
    logits = np.zeros((F, E, m["vocab_rows"]), np.float32)
    values = np.zeros((F, E), np.float32)
    fwd = jax.jit(lambda w, tokens: reference.forward(w, m, tokens, without))
    for row in range(E):
        for episode in episodes_of(forwards, row):
            tokens = np.zeros((size,), np.int32)
            tokens[: len(episode["inputs"])] = episode["inputs"]
            lg, vl = fwd(weights, jnp.asarray(tokens))
            for f, position in episode["at"]:
                logits[f, row], values[f, row] = np.asarray(lg[position]), float(vl[position])
    theirs = against or {"logits": np.stack([c["logits"] for c in forwards]), "values": np.stack([c["values"] for c in forwards])}
    gap_l = np.linalg.norm(theirs["logits"] - logits, axis=-1) / np.linalg.norm(logits, axis=-1)
    gap_v = np.abs(theirs["values"] - values) / np.sqrt(np.mean(values**2))
    numbers = {"player_logits": float(np.median(gap_l)), "player_values": float(np.median(gap_v)),
               "player_logits_worst": float(gap_l.max()), "player_values_worst": float(gap_v.max())}  # fmt: skip
    return numbers, {"logits": logits, "values": values}


def aligned_sequences(batch: Dict[str, np.ndarray]) -> List[Dict[str, np.ndarray]]:
    """A recorded minibatch (sequence-major, as ``seqpol_train_step`` got it) as
    the reference takes it: one entry a real sequence, every array aligned to
    the episode's positions from its first and padded to one length (one
    compilation). Only sequences that begin their episode are taken (``len0``
    0): the first update has no other."""
    out = []
    size = batch["prompt"].shape[1] + batch["mask"].shape[1]
    for j in range(batch["mask"].shape[0]):
        n = int(batch["mask"][j].sum())
        if n == 0:
            continue
        if int(batch["len0"][j]) != 0:
            raise RuntimeError("perfbench: a recorded sequence continues an episode from before the rollout; the reference has no earlier tokens for it")
        prefix = int(batch["n0"][j]) - 1
        seq = {"tokens": np.zeros((size,), np.int32), "steps": np.zeros((size,), np.float32)}
        seq["tokens"][:prefix] = batch["prompt"][j, :prefix]
        seq["tokens"][prefix : prefix + n] = batch["tok_in"][j, :n]
        seq["steps"][prefix : prefix + n] = 1.0
        for key in ("actions", "logprobs", "advantages", "returns", "values"):
            seq[key] = np.zeros((size,), batch[key].dtype)
            seq[key][prefix : prefix + n] = batch[key][j, :n]
        out.append(seq)
    return out


def train_side(cfg: Dict[str, Any], weights: Any, steps: List[Dict[str, Any]], without: Tuple[int, ...] = (), half_batch: bool = False) -> Dict[str, Any]:
    """The reference through the recorded gradient steps: the first step's
    losses, clipped gradient and the weights after one AdamW step (both on the
    host), and the second step's losses at those weights."""
    import jax

    from perfbench.references import token_ppo as reference

    m, a = cfg["model"], cfg["algo"]
    sequences = aligned_sequences(steps[0]["batch"])
    if half_batch:
        sequences = sequences[: len(sequences) // 2]
    losses, grads = reference.loss_and_grad(weights, m, a, sequences, without)
    grads = reference.clip_by_global_norm(grads, a["max_grad_norm"])
    after = reference.adamw_first_step(weights, grads, a)
    out = {"losses": [losses], "grad": jax.device_get(grads), "grad_scale": 1.0}
    del grads
    if len(steps) > 1:
        out["losses"].append(reference.losses_only(after, m, a, aligned_sequences(steps[1]["batch"]), without))
    out["after"] = jax.device_get(after)
    return out


def program_side(cfg: Dict[str, Any], steps: List[Dict[str, Any]]) -> Dict[str, Any]:
    """The same from what the program's recorded steps left: its first
    clipped gradient is Adam's first moment over ``1 - b1``."""
    names = ("policy_loss", "value_loss", "entropy_loss", "mtp_loss")
    return {
        "losses": [dict(zip(names, (float(x) for x in s["metrics"][:4]))) for s in steps],
        "grad": steps[0]["mu"],
        "grad_scale": 1.0 / (1.0 - cfg["algo"].get("b1", 0.9)),
        "after": steps[0]["params"],
    }


def train_gaps(ours: Dict[str, Any], ref: Dict[str, Any], seeded: Any) -> Dict[str, float]:
    """Each loss (largest relative gap over the steps; the policy loss's over
    the mean magnitude of its terms); ``first_grad`` (worst
    large leaf's gap of the first gradient's norm); ``grad_direction`` (1 -
    cosine, median large leaf); ``change`` (norm of the difference of the
    weights' change from ``seeded`` over the norm of the reference's, worst
    large leaf; a state returned unchanged reads 1). Leaf by leaf: the trees
    are gigabytes."""
    import jax

    numbers = {}
    for name in ("policy_loss", "value_loss", "entropy_loss", "mtp_loss"):
        # the policy loss is a mean of terms of both signs that all but cancel (at the first step it is minus the mean
        # advantage): its gap is held against the mean magnitude of its terms, the others against themselves
        scale = "policy_scale" if name == "policy_loss" else name
        numbers[name] = max(abs(o[name] - r[name]) / max(abs(r[scale]), 1e-12) for o, r in zip(ours["losses"], ref["losses"]))
    leaves = [jax.tree.leaves(t) for t in (ours["grad"], ref["grad"], ours["after"], ref["after"], seeded)]
    threshold = min(LARGE_LEAF, max(leaf.size for leaf in leaves[1]))
    norm = lambda x: float(np.linalg.norm(x.reshape(-1)))  # noqa: E731
    grad_norm, direction, change = [], [], []
    for g_o, g_r, a_o, a_r, w in zip(*leaves):
        if g_r.size < threshold:
            continue
        g_o = np.asarray(g_o, np.float32) * np.float32(ours["grad_scale"])
        g_r = np.asarray(g_r, np.float32) * np.float32(ref["grad_scale"])
        grad_norm.append(abs(norm(g_o) - norm(g_r)) / max(norm(g_r), 1e-30))
        direction.append(1.0 - float(np.vdot(g_o.reshape(-1), g_r.reshape(-1))) / max(norm(g_o) * norm(g_r), 1e-30))
        c_r = np.asarray(a_r, np.float32) - np.asarray(w, np.float32)
        change.append(norm(np.asarray(a_o, np.float32) - np.asarray(w, np.float32) - c_r) / max(norm(c_r), 1e-30))
    numbers.update({"first_grad": max(grad_norm), "grad_direction": float(np.median(direction)), "change": max(change)})
    return {k: float(v) for k, v in numbers.items()}


def gae_gap(cfg: Dict[str, Any], rollout: Dict[str, np.ndarray]) -> float:
    """The rollout's returns and advantages against the reference's GAE on
    the recorded rewards, values and ends: largest gap over the values' scale."""
    from perfbench.references import token_ppo as reference

    a = cfg["algo"]
    returns, advantages = reference.gae(rollout["rewards"][..., 0], rollout["values"][..., 0], rollout["dones"][..., 0],
                                        rollout["next_values"][:, 0], a["gamma"], a["gae_lambda"])  # fmt: skip
    scale = max(float(np.abs(rollout["values"]).max()), 1.0)
    return float(max(np.abs(returns - rollout["returns"][..., 0]).max(), np.abs(advantages - rollout["advantages"][..., 0]).max()) / scale)


@contextlib.contextmanager
def _reference_programs_stay_out_of_a_limited_cache():
    """The reference's programs are compiled once a run, after the window, and
    are large (its gradient's executable alone is 80 MB). Where the persistent
    compile cache has a size limit (the chip tool's machine: 192 MiB), writing
    them evicts the timed programs of every cell, so while this is open
    nothing is written there (JAX writes an entry only if it took this long
    to compile); reads go on. A cache without a limit, as a checkout's own
    ``.jax_cache`` is, keeps them, and a warm run's comparison is shorter."""
    import jax

    limit = jax.config.jax_compilation_cache_max_size
    if limit is None or limit < 0:
        yield
        return
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)
    try:
        yield
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", before)


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
