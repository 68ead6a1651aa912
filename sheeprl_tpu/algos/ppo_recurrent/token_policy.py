"""The decoder core of recurrent PPO: a policy over tokens.

``ppo_recurrent``'s sequence machinery with another thing carried from step to
step. Where the LSTM core carries ``(hx, cx)`` per env, this core carries, for
each layer, the state its operator declares (``models/seqpol.py``
``state_shapes``: a cache of positions for an attention layer, a ring of the
last ``sliding_window`` positions for a window layer, the last few gated
inputs for a convolution layer) and one length per env: one env
step is one token, the reset observation carries the prompt, and the update
runs teacher-forced over each sequence of the rollout. A sequence that
continues an episode begun before the rollout starts from that state **as it
stood at the rollout's start** (the snapshot: what ``prev_hx/prev_cx`` are to
the LSTM), as constants without gradient.

What a sequence core supplies, for either kind:

- the initial carry for ``E`` rows (an empty state, :class:`TokenPlayer`; zeros for the LSTM);
- one rollout step (:meth:`TokenPlayer.act`; ``RecurrentPPOPlayer.rollout_actions``);
- the evaluation of padded sequences given each sequence's starting carry
  (:func:`token_loss`; ``evaluate_actions``), whose losses the masks of
  ``build_sequences`` zero on the padding.

Three named programs: ``seqpol_prefill`` (the prompts of the rows that reset,
at most ``prefill_rows`` a call, each call the smallest rung of
:attr:`TokenPlayer.rungs` that holds its rows, so its cost follows the resets
and not ``num_envs``), ``seqpol_decode`` (one token for all rows through the
state: sampling, log-probability, value) and ``seqpol_train_step`` (one gradient step
on one minibatch of ``per_rank_batch_size`` sequences; an update dispatches as
many as its rollout's sequences fill, ``update_epochs`` times). Every shape is
static, and the player compiles each of its prefill's sizes when it is built.
"""

from __future__ import annotations

import os
from collections import Counter
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.ppo.loss import entropy_loss, policy_loss, value_loss
from sheeprl_tpu.config.compose import instantiate
from sheeprl_tpu.models import seqpol
from sheeprl_tpu.obs import telemetry_advance, telemetry_counters
from sheeprl_tpu.ops.math import gae
from sheeprl_tpu.utils.prealloc import RolloutStore
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import save_configs

Array = jax.Array

#: what a rollout stores of the core's carry at each step, and ``build_sequences`` emits per sequence
CARRY_KEYS = ("prev_len", "prev_env")
TRAIN_KEYS = ["tokens", "n_tokens", "actions", "logprobs", "values", "returns", "advantages"]
#: what ``seqpol_train_step`` returns, in order (the last only where the core has window layers)
METRICS = ("policy_loss", "value_loss", "entropy_loss", "mtp_loss", "routed_pairs", "held_pairs", "max_expert_pairs",
           "real_positions", "padded_positions", "window_pairs_scored")  # fmt: skip


class TokenPolicy:
    """The sizes (:class:`seqpol.SeqPolConfig`), the compute dtype and the
    shapes of the env's side: ``prompt_max`` observation slots."""

    def __init__(self, core: seqpol.SeqPolConfig, prompt_max: int, dtype: Any) -> None:
        self.core, self.prompt_max, self.dtype = core, int(prompt_max), dtype


def build_token_agent(fabric: Any, cfg: Dict[str, Any], obs_space: Any, action_space: Any, agent_state: Optional[Any] = None):
    """The policy and its float32 parameters (from ``cfg.seed``, or a checkpoint's)."""
    core = seqpol.config_from(cfg.algo.core)
    if int(action_space.n) != core.vocab_rows:
        raise ValueError(f"the env has {action_space.n} actions and algo.core.vocab_rows is {core.vocab_rows}")
    if fabric.world_size > 1:
        raise NotImplementedError("the decoder core runs on one device: the exchange between chips that share a layer is not built")
    agent = TokenPolicy(core, obs_space["tokens"].shape[-1], fabric.precision.compute_dtype)
    if agent_state is not None:
        params = jax.tree.map(jnp.asarray, agent_state)
    else:
        init = jax.jit(lambda k: seqpol.init_params(k, core))  # one program on the device, not an eager op a leaf
        params = init(jax.random.PRNGKey(int(cfg.seed)))
    params = jax.tree.map(lambda x: x.astype(fabric.precision.param_dtype), params)
    return agent, fabric.replicate(params)


# --------------------------------------------------------------------------- #
# the player
# --------------------------------------------------------------------------- #


def make_player_programs(agent: TokenPolicy) -> Dict[str, Any]:
    """The player's jitted programs, each under its own name: ``cast`` (the
    parameters in the compute dtype), ``prefill``, ``decode`` and ``snapshot``.
    The two that write the state take it donated."""
    core, dtype = agent.core, agent.dtype

    def seqpol_player_params(p):
        return seqpol.low_precision(p, dtype)

    def seqpol_prefill(p, state, rows, tokens, n_prefix):
        slots = jnp.arange(tokens.shape[1])[None, :]
        _, own, counters = seqpol.forward_sequence(p, core, tokens, jnp.broadcast_to(slots, tokens.shape), slots < n_prefix[:, None], dtype=dtype)
        # each array of a layer's state takes the rows' own entries from its first position on: a cache the prompt's
        # slots, a convolution state and a window layer's ring all they hold (the prompt's last entries, a ring's in
        # ring order). A row index past the last env marks an unused slot of this call: its write is dropped
        state = jax.tree.map(lambda held, new: held.at[rows, : new.shape[1]].set(new.astype(held.dtype), mode="drop"), state, own)
        return state, counters

    def seqpol_decode(p, state, tokens, positions, key, counter):
        h, state, counters = seqpol.decode_step(p, core, tokens, positions, state, dtype=dtype)
        logits, values = seqpol.heads(p, core, h)
        with jax.named_scope("seqpol/head"):
            actions = jax.random.categorical(jax.random.fold_in(key, counter), logits, axis=-1)
            logprobs = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), actions[:, None], axis=-1)[:, 0]
        return actions.astype(jnp.int32), logprobs, values, logits, state, counters

    def seqpol_snapshot(state):
        return jax.tree.map(jnp.copy, state)

    return {"cast": jax.jit(seqpol_player_params), "prefill": jax.jit(seqpol_prefill, donate_argnums=(1,)),
            "decode": jax.jit(seqpol_decode, donate_argnums=(1,)), "snapshot": jax.jit(seqpol_snapshot)}  # fmt: skip


class TokenPlayer:
    """The rollout's side: the parameters in the compute dtype, the state on
    the device (``state[i]``: the arrays layer ``i``'s operator declares, each
    ``[E, ...]``) and each row's length on the host."""

    def __init__(self, agent: TokenPolicy, params: Any, num_envs: int, prefill_rows: int) -> None:
        core, dtype = agent.core, agent.dtype
        self.agent, self.num_envs, self.prefill_rows = agent, int(num_envs), int(prefill_rows)
        programs = make_player_programs(agent)
        self._cast, self._prefill, self._decode, self._snapshot = (programs[k] for k in ("cast", "prefill", "decode", "snapshot"))
        self.params = self._cast(params)
        # placed like the parameters: the first snapshot then has the sharding of every later one, and the
        # train step's second call finds its first's program
        placed = jax.tree.leaves(self.params)[0].sharding
        self.state = tuple(tuple(jax.device_put(jnp.zeros(shape, dtype), placed) for shape in layer)
                           for layer in seqpol.state_shapes(core, self.num_envs))  # fmt: skip
        self.lengths = np.zeros((self.num_envs,), np.int32)
        self.last_logits: Optional[Array] = None
        #: the rows of one ``seqpol_prefill`` call: the powers of two under ``prefill_rows``, then ``prefill_rows``
        self.rungs = tuple(1 << k for k in range(self.prefill_rows.bit_length()) if 1 << k < self.prefill_rows) + (self.prefill_rows,)
        # every rung compiled now, with every row at ``num_envs`` so that each write is dropped: the first step with
        # that many resets may come at any time
        for rung in self.rungs:
            self.state, _ = self._prefill(self.params, self.state, np.full((rung,), self.num_envs, np.int32),
                                          np.zeros((rung, agent.prompt_max), np.int32), np.zeros((rung,), np.int32))  # fmt: skip
        #: entries of each layer that keeps positions (a cache: ``context``; a window layer's ring: ``sliding_window``) -> how many such layers
        self._cache_sizes = Counter(layer[0][1] for i, layer in enumerate(seqpol.state_shapes(core, 1)) if seqpol.OPERATORS[core.operator(i)].key == "attn")
        #: counters since the start: rows prefilled, and the ``seqpol_prefill`` calls that did it, the slots those calls
        #: computed and the prompts' tokens among them; tokens decoded, entries the decodes attended to (a mean over the
        #: layers that keep positions: a window layer attends to ``min(length, sliding_window)``)
        self.rows_prefilled = 0
        self.prefill_calls = 0
        self.prefill_slots = 0
        self.prefill_tokens = 0
        self.tokens_decoded = 0
        self.cache_positions = 0

    @property
    def cache_c(self) -> Tuple[Array, ...]:
        """The first array of every layer's state (``perfbench``'s recording player asks where it lives)."""
        return tuple(layer[0] for layer in self.state)

    def reset_rows(self, dones: np.ndarray) -> None:
        """A row whose episode ended starts its next from nothing: no entry of
        either kind of state lies at or after its first position."""
        dones = np.asarray(dones, bool).reshape(-1)
        self.lengths[dones] = 0

    def snapshot(self) -> Tuple[Tuple[Array, ...], ...]:
        """A copy of the state as it stands: what a rollout's continuing sequences start from in the update."""
        return self._snapshot(self.state)

    def peek_values(self, obs: Dict[str, np.ndarray]) -> np.ndarray:
        """The value of the observation the rollout stopped at, ``[E, 1]``: the
        rows that continue take one decode on a throw-away copy of the state
        (their next rollout step writes the same entry for real); a row that
        has just been reset is masked by its ``done``."""
        tokens, n_tokens = np.asarray(obs["tokens"], np.int32), np.asarray(obs["n_tokens"], np.int32).reshape(self.num_envs)
        current = tokens[np.arange(self.num_envs), n_tokens - 1].astype(np.int32)
        out = self._decode(self.params, self.snapshot(), current, self.lengths.copy(), jax.random.PRNGKey(0), np.uint32(0))
        return np.asarray(out[2], np.float32)[:, None]

    def prefill(self, tokens: np.ndarray, n_tokens: np.ndarray) -> None:
        """The rows whose observation holds more than one token (a prompt) get
        all but its last written into their state: ``prefill_rows`` rows a
        call while more are left, then one call of the smallest rung that
        holds the rest. A call computes its rung's rows x the observation's
        slots, whatever the rows and prompts (``prefill_slots``)."""
        rows = np.nonzero(n_tokens > 1)[0]
        for at in range(0, len(rows), self.prefill_rows):
            chunk = rows[at : at + self.prefill_rows]
            rung = next(r for r in self.rungs if r >= len(chunk))
            idx = np.full((rung,), self.num_envs, np.int32)
            idx[: len(chunk)] = chunk
            toks = np.zeros((rung, tokens.shape[1]), np.int32)
            toks[: len(chunk)] = tokens[chunk]
            n_prefix = np.zeros((rung,), np.int32)
            n_prefix[: len(chunk)] = n_tokens[chunk] - 1
            self.state, _ = self._prefill(self.params, self.state, idx, toks, n_prefix)
            self.lengths[chunk] = n_tokens[chunk] - 1
            self.rows_prefilled += len(chunk)
            self.prefill_calls += 1
            self.prefill_slots += toks.size
            self.prefill_tokens += int(n_prefix.sum())

    def act(self, tokens: np.ndarray, n_tokens: np.ndarray, key: Array, counter: int):
        """One rollout step on the observation ``tokens [E, P]``, ``n_tokens
        [E]``: the prompts' prefill, then one decode. Returns device arrays
        ``(actions, logprobs, values)`` and the positions the step was taken at."""
        with timer("player/prefill"):
            self.prefill(tokens, n_tokens)
        with timer("player/decode"):
            current = tokens[np.arange(self.num_envs), n_tokens - 1].astype(np.int32)
            positions = self.lengths.copy()
            actions, logprobs, values, self.last_logits, self.state, _ = self._decode(
                self.params, self.state, current, positions, key, np.uint32(counter)
            )
            self.lengths += 1
            self.tokens_decoded += self.num_envs
            attended = sum(n * int(np.minimum(positions + 1, size).sum()) for size, n in self._cache_sizes.items())
            self.cache_positions += attended / max(sum(self._cache_sizes.values()), 1)
        return actions, logprobs, values, positions


# --------------------------------------------------------------------------- #
# the update
# --------------------------------------------------------------------------- #


def sequence_layout(batch: Dict[str, Array], prompt_max: int) -> Dict[str, Array]:
    """A minibatch of ``build_sequences``' step-aligned sequences as slots:
    ``prompt_max`` slots for the prompt's prefix, right-aligned (an episode
    that began in the rollout; none for one that continues), then one slot a
    step. Returns ``tokens``, ``positions`` and ``valid`` ``[B, S]``."""
    prompt, n0, steps, mask = batch["prompt"], batch["n0"], batch["tok_in"], batch["mask"]
    Q = prompt_max
    pad = Q - (n0 - 1)  # slots of the prefix region that stay empty
    slot = jnp.arange(Q)[None, :]
    prefix = jnp.take_along_axis(prompt, jnp.clip(slot - pad[:, None], 0, Q - 1), axis=1)
    tokens = jnp.concatenate([jnp.where(slot >= pad[:, None], prefix, 0), steps], axis=1)
    valid = jnp.concatenate([slot >= pad[:, None], mask > 0], axis=1)
    positions = batch["len0"][:, None] + jnp.arange(tokens.shape[1])[None, :] - pad[:, None]
    return {"tokens": tokens, "positions": jnp.maximum(positions, 0), "valid": valid}


def token_loss(params: Any, agent: TokenPolicy, batch: Dict[str, Array], snap: Any, clip_coef, ent_coef, *,
               vf_coef: float, mtp_coef: float, clip_vloss: bool = False, normalize_adv: bool = False, reduction: str = "mean",
               remat: bool = True, params_lo: Optional[Any] = None):  # fmt: skip
    """The PPO loss of one minibatch of sequences, its terms and the expert
    layers' counters. ``batch``: ``prompt [B, P]``, ``n0 [B]`` (tokens in the
    first step's observation), ``tok_in [B, L]`` (each step's input token),
    ``actions``, ``logprobs``, ``values``, ``returns``, ``advantages``, ``mask``
    ``[B, L]``, and each sequence's starting carry ``len0 [B]``, ``env0 [B]``:
    the length of, and the row in, the snapshot ``snap`` (the player's state
    as it stood at the rollout's start, each layer's arrays). ``params_lo`` is the player's copy of the same parameters in the
    compute dtype: the matmuls read it, the gradient goes to ``params``."""
    core = agent.core
    if params_lo is not None:
        params = seqpol.reading_copy(params, params_lo)
    Q = agent.prompt_max
    lay = sequence_layout(batch, Q)
    ctx = (jax.lax.stop_gradient(snap), batch["env0"], batch["len0"])
    h, _, counters = seqpol.forward_sequence(params, core, lay["tokens"], lay["positions"], lay["valid"], ctx, dtype=agent.dtype, remat=remat)
    h = h[:, Q:]  # the steps' slots
    B, L, D = h.shape
    mask = batch["mask"]
    msum = mask.sum() + 1e-8
    new_logp, entropy = seqpol.token_stats(params, core, h.reshape(B * L, D), params["final_norm"]["scale"], batch["actions"].reshape(-1))
    new_logp, entropy = new_logp.reshape(B, L), entropy.reshape(B, L)
    with jax.named_scope("seqpol/head"):
        z = seqpol.rms_norm(h, params["final_norm"]["scale"], core.rms_norm_eps)
        new_values = jnp.dot(z.astype(jnp.float32), params["value_head"]["kernel"])[..., 0]
        adv = batch["advantages"]
        if normalize_adv:
            mean = (adv * mask).sum() / msum
            var = (jnp.square(adv - mean) * mask).sum() / jnp.maximum(msum - 1, 1.0)
            adv = (adv - mean) / (jnp.sqrt(var) + 1e-8)
        pg = (policy_loss(new_logp, batch["logprobs"], adv, clip_coef, "none") * mask).sum() / msum
        v = (value_loss(new_values, batch["values"], batch["returns"], clip_coef, clip_vloss, "none") * mask).sum() / msum
        ent = (entropy_loss(entropy, "none") * mask).sum()
        if reduction == "mean":
            ent = ent / msum
    mtp = jnp.zeros((), jnp.float32)
    if core.num_nextn_predict_layers:
        # the state at step t with the token taken at t predicts the token taken at t + 1, inside one episode
        x, extra = seqpol.mtp_hidden(params, core, h, batch["actions"], lay["positions"][:, Q:], mask > 0, remat=remat)
        targets = jnp.concatenate([batch["actions"][:, 1:], jnp.zeros((B, 1), batch["actions"].dtype)], axis=1)
        both = mask * jnp.concatenate([mask[:, 1:], jnp.zeros((B, 1), mask.dtype)], axis=1)
        with jax.named_scope("seqpol/mtp"):
            logp, _ = seqpol.token_stats(params, core, x.reshape(B * L, D), params["mtp"]["final_norm"]["scale"], targets.reshape(-1))
            mtp = -(logp.reshape(B, L) * both).sum() / (both.sum() + 1e-8)
        counters = seqpol.merge_counters(counters, extra)
    total = pg + vf_coef * v + ent_coef * ent + mtp_coef * mtp
    positions = jnp.stack([lay["valid"].sum().astype(jnp.float32), jnp.asarray(float(lay["valid"].size), jnp.float32)])
    metrics = [jnp.stack([pg, v, ent, mtp]), counters, positions]
    if seqpol.window_layers(core):
        metrics.append(seqpol.window_pairs_scored(core, lay["positions"], lay["valid"], batch["len0"], remat)[None])
    return total, jnp.concatenate(metrics)


def make_token_train_fn(fabric: Any, agent: TokenPolicy, tx: optax.GradientTransformation, cfg: Dict[str, Any]):
    """``seqpol_train_step(params, opt_state, params_lo, batch, snap,
    clip_coef, ent_coef) -> (params, opt_state, params_lo, metrics)``: one
    gradient step on one minibatch of sequences (:data:`METRICS` names the
    metrics). ``params_lo`` is the player's copy in the compute dtype, read by
    the step and made again from the weights it leaves, so the player's next
    decode has them and no program holds a second cast of every weight."""
    consts = dict(vf_coef=float(cfg.algo.vf_coef), mtp_coef=float(cfg.algo.core.mtp_loss_coef), clip_vloss=bool(cfg.algo.clip_vloss),
                  normalize_adv=bool(cfg.algo.normalize_advantages), reduction=str(cfg.algo.loss_reduction))  # fmt: skip

    def seqpol_train_step(params, opt_state, params_lo, batch, snap, clip_coef, ent_coef):
        def loss_fn(p):
            return token_loss(p, agent, batch, snap, clip_coef, ent_coef, params_lo=params_lo, **consts)

        (_, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        with jax.named_scope("seqpol/optimizer"):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            params_lo = seqpol.low_precision(params, agent.dtype)
        return params, opt_state, params_lo, metrics

    return jax.jit(seqpol_train_step, donate_argnums=(0, 1, 2))


def window_keys(seqs: Dict[str, np.ndarray], core: seqpol.SeqPolConfig) -> int:
    """Over the real queries of ``seqs`` (a prompt's prefix and the steps), the
    keys inside each one's window, summed over the window layers: a query at
    position ``q`` has ``min(q + 1, sliding_window)``. What the band cannot avoid."""
    layers = seqpol.window_layers(core)
    if not layers:
        return 0
    count = (seqs["n0"] - 1) + seqs["mask"].sum(axis=1).astype(np.int64)  # a sequence's real slots lie at len0, len0 + 1, ...
    at = np.arange(int(count.max(initial=0)))[None, :]
    keys = np.minimum(seqs["len0"][:, None] + at + 1, core.sliding_window)
    return layers * int(np.where(at < count[:, None], keys, 0).sum())


def token_sequences(local_data: Dict[str, np.ndarray], seq_steps: int, num_envs: int, batch_size: int) -> Dict[str, np.ndarray]:
    """What the update trains on, from the rollout with its returns and
    advantages: the rollout cut at episode ends (``build_sequences``, as it
    is), padded to a whole number of minibatches of ``batch_size`` sequences,
    sequence-major, with each step's input token picked out of its observation."""
    from sheeprl_tpu.algos.ppo_recurrent.ppo_recurrent import build_sequences

    seqs = build_sequences(local_data, TRAIN_KEYS, seq_steps, num_envs, batch_size, carry_keys=CARRY_KEYS)
    tokens, n_tokens = seqs["tokens"], seqs["n_tokens"][..., 0]  # [L, N, P], [L, N]
    tok_in = np.take_along_axis(tokens, np.maximum(n_tokens - 1, 0)[..., None], axis=-1)[..., 0]
    out = {k: np.ascontiguousarray(seqs[k][..., 0].T) for k in ("actions", "logprobs", "values", "returns", "advantages", "mask")}
    out.update({"prompt": np.ascontiguousarray(tokens[0]), "n0": np.maximum(n_tokens[0], 1).astype(np.int32),
                "tok_in": np.ascontiguousarray(tok_in.T), "len0": seqs["len0"][:, 0].astype(np.int32), "env0": seqs["env0"][:, 0].astype(np.int32)})  # fmt: skip
    return out


# --------------------------------------------------------------------------- #
# the loop
# --------------------------------------------------------------------------- #


def run_token_policy(fabric: Any, cfg: Dict[str, Any], envs: Any, state: Optional[Dict[str, Any]], log_dir: str, logger: Any,
                     resil: Any, aggregator: Any) -> bool:  # fmt: skip
    """The rollout-and-update loop of the decoder core; returns whether it was
    preempted. Everything around it (envs, logger, resilience, the test run
    and the way out) is ``ppo_recurrent.main``'s."""
    rank = fabric.process_index
    num_envs = int(cfg.env.num_envs)
    rollout_steps = int(cfg.algo.rollout_steps)
    agent, params = build_token_agent(fabric, cfg, envs.single_observation_space, envs.single_action_space, state["agent"] if state else None)
    seq_steps = int(cfg.algo.per_rank_sequence_length) - agent.prompt_max
    if seq_steps < rollout_steps:
        raise ValueError(f"algo.per_rank_sequence_length ({cfg.algo.per_rank_sequence_length}) must hold the prompt ({agent.prompt_max}) and "
                         f"algo.rollout_steps ({rollout_steps}): a sequence cut inside the rollout would need the cache as it stood there")  # fmt: skip
    # a minibatch is ``per_rank_batch_size`` sequences, whatever the rollout holds: the gradient steps of an
    # update follow the count of sequences (``per_rank_num_batches`` is the LSTM core's), and no shape ever changes
    batch_size = int(cfg.algo.per_rank_batch_size)
    update_epochs = int(cfg.algo.update_epochs)
    policy_steps_per_update = num_envs * rollout_steps
    num_updates = int(cfg.algo.total_steps) // policy_steps_per_update if not cfg.dry_run else 1

    opt_cfg = dict(cfg.algo.optimizer.to_dict() if hasattr(cfg.algo.optimizer, "to_dict") else cfg.algo.optimizer)
    if cfg.algo.max_grad_norm and float(cfg.algo.max_grad_norm) > 0:
        opt_cfg["max_grad_norm"] = float(cfg.algo.max_grad_norm)
    tx = instantiate(opt_cfg)
    # pinned like the parameters, and like what the train step gives back: its second call then finds its first's program
    init_opt = jax.jit(tx.init)
    opt_state = fabric.replicate(init_opt(params))
    if state:
        opt_state = fabric.replicate(jax.tree.map(jnp.asarray, state["opt_state"], is_leaf=lambda x: isinstance(x, np.ndarray)))
    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    if "Loss/mtp_loss" not in aggregator.metrics:
        aggregator.add("Loss/mtp_loss", "mean")
    player = TokenPlayer(agent, params, num_envs, int(cfg.algo.core.prefill_rows))
    train_fn = make_token_train_fn(fabric, agent, tx, cfg)
    gae_fn = jax.jit(partial(gae, gamma=float(cfg.algo.gamma), gae_lambda=float(cfg.algo.gae_lambda)))

    start_update = (state["update"] + 1) if state else 1
    policy_step = state["update"] * policy_steps_per_update if state else 0
    last_log = state["last_log"] if state else 0
    last_checkpoint = state["last_checkpoint"] if state else 0
    player_key = jax.random.fold_in(jax.random.PRNGKey(int(cfg.seed)), 1)
    shuffle = np.random.default_rng(int(cfg.seed))
    clip_coef, ent_coef = np.float32(cfg.algo.clip_coef), np.float32(cfg.algo.ent_coef)

    def ckpt_state_fn(completed_update: int) -> Dict[str, Any]:
        return {"agent": jax.device_get(params), "opt_state": jax.device_get(opt_state), "update": completed_update,
                "batch_size": batch_size, "last_log": last_log, "last_checkpoint": last_checkpoint}  # fmt: skip

    def ckpt_path_fn(step: int) -> str:
        return os.path.join(log_dir, "checkpoint", f"ckpt_{step}_{rank}.ckpt")

    resil.arm_crash_guard(path_fn=lambda: ckpt_path_fn(policy_step), state_fn=lambda: ckpt_state_fn(update - 1))

    obs, _ = envs.reset(seed=cfg.seed)
    store = RolloutStore(rollout_steps)
    env_index = np.arange(num_envs, dtype=np.int32)[:, None]
    preempted = False
    # An update's work lies in spans other than the two window spans (howto/telemetry.md): ``loop/head``,
    # ``update/assemble`` and ``loop/tail`` around them and leaves inside them, which a window span joins with a
    # few bindings of glue
    counted = ("rows_prefilled", "prefill_calls", "prefill_slots", "prefill_tokens", "tokens_decoded", "cache_positions")
    for update in range(start_update, num_updates + 1):
        with timer("loop/head"):
            telemetry_advance(policy_step)
            if resil.preempt_requested():
                last_checkpoint = policy_step
                resil.emergency_checkpoint(ckpt_path_fn(policy_step), ckpt_state_fn(update - 1))
                preempted = True
                break
            buf = store.begin(update)
            snap = player.snapshot()
            before = {k: getattr(player, k) for k in counted}
        with timer("Time/env_interaction_time"):
            for t in range(rollout_steps):
                policy_step += num_envs
                tokens, n_tokens = np.asarray(obs["tokens"], np.int32), np.asarray(obs["n_tokens"], np.int32).reshape(num_envs)
                # a row that continues carries its cache's length; one that begins here carries none
                prev_len = np.where(n_tokens > 1, 0, player.lengths).astype(np.int32)[:, None]
                out = player.act(tokens, n_tokens, player_key, policy_step)
                with timer("player/get_actions"):
                    actions, logprobs, values = jax.device_get(out[:3])
                with timer("env/step"):
                    obs, rewards, terminated, truncated, info = envs.step(actions.reshape(envs.action_space.shape))
                with timer("rollout/store"):
                    # an episode cut at the context's end is over: the verifier has spoken, nothing is bootstrapped
                    dones = np.logical_or(terminated, truncated).reshape(num_envs, 1).astype(np.float32)
                    buf.put(t, {"tokens": tokens, "n_tokens": n_tokens[:, None], "actions": actions[:, None], "logprobs": logprobs[:, None],
                                "values": values[:, None], "rewards": np.asarray(rewards, np.float32).reshape(num_envs, 1), "dones": dones,
                                "prev_len": prev_len, "prev_env": env_index})  # fmt: skip
                    player.reset_rows(dones)
                    if cfg.metric.log_level > 0 and "final_info" in info:
                        ep = info["final_info"].get("episode")
                        if ep is not None:
                            for i in np.nonzero(ep.get("_r", []))[0]:
                                aggregator.update("Rewards/rew_avg", float(ep["r"][i]))
                                aggregator.update("Game/ep_len_avg", float(ep["l"][i]))
                                print(f"Rank-0: policy_step={policy_step}, reward_env_{i}={ep['r'][i]}")

        with timer("update/assemble"):
            wrapped = int((player.lengths >= (agent.core.sliding_window or np.inf)).sum())
            local_data = buf.arrays()
        with timer("Time/train_time"):
            with timer("train/dispatch"):
                with timer("update/bootstrap"):
                    # the value of the observation the rollout stopped at: one more decode on a copy of the carry, kept out of the cache
                    next_values = player.peek_values(obs) * (1.0 - local_data["dones"][-1])
                with timer("update/sequences"):
                    returns, advantages = gae_fn(local_data["rewards"], local_data["values"], local_data["dones"], next_values)
                    local_data.update({"returns": np.asarray(returns), "advantages": np.asarray(advantages), "next_values": next_values})
                    seqs = token_sequences(local_data, seq_steps, num_envs, batch_size)
                n_seq = seqs["mask"].shape[0]
                pending = []
                for _ in range(update_epochs):
                    for idx in shuffle.permutation(n_seq).reshape(n_seq // batch_size, batch_size):
                        batch = {k: v[idx] for k, v in seqs.items()}
                        params, opt_state, player.params, metrics = train_fn(
                            params, opt_state, player.params, batch, snap, clip_coef, ent_coef
                        )
                        pending.append(metrics)
            with timer("train/block"):
                # stacked on the host: an update of another count of gradient steps then compiles nothing
                metrics = np.stack(jax.device_get(pending))
        with timer("loop/tail"):
            _report(metrics, aggregator if cfg.metric.log_level > 0 else None, core=agent.core,
                    window_keys=update_epochs * window_keys(seqs, agent.core), ring_wrapped_rows=wrapped,
                    **{k: getattr(player, k) - v for k, v in before.items()})  # fmt: skip
            if cfg.metric.log_level > 0 and (policy_step - last_log >= cfg.metric.log_every or update == num_updates):
                logger.log_metrics(aggregator.compute(), policy_step)
                aggregator.reset()
                if not timer.disabled:
                    timer.reset()
                last_log = policy_step
            if (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every) or (
                update == num_updates and cfg.checkpoint.save_last
            ):
                last_checkpoint = policy_step
                fabric.call("on_checkpoint_coupled", ckpt_path=ckpt_path_fn(policy_step), state=ckpt_state_fn(update))
    return preempted


def _report(metrics: np.ndarray, aggregator: Any, *, core: seqpol.SeqPolConfig, rows_prefilled: int, prefill_calls: int,
            prefill_slots: int, prefill_tokens: int, tokens_decoded: int, cache_positions: float, window_keys: int,
            ring_wrapped_rows: int) -> None:  # fmt: skip
    """One update's losses into the aggregator and its counters into ``telemetry.jsonl``."""
    mean = dict(zip(METRICS, metrics.mean(0)))
    total = dict(zip(METRICS, metrics.sum(0)))
    if aggregator is not None:
        aggregator.update("Loss/policy_loss", float(mean["policy_loss"]))
        aggregator.update("Loss/value_loss", float(mean["value_loss"]))
        aggregator.update("Loss/entropy_loss", float(mean["entropy_loss"]))
        aggregator.update("Loss/mtp_loss", float(mean["mtp_loss"]))
    expert_layers = core.num_hidden_layers - core.first_k_dense_replace + core.num_nextn_predict_layers
    telemetry_counters(
        "seqpol/update",
        gradient_steps=int(metrics.shape[0]),
        routed_pairs=float(total["routed_pairs"]),
        held_pairs=float(total["held_pairs"]),
        # the busiest held expert of any layer in any step, over the mean load of a held expert
        max_expert_load=float(metrics[:, METRICS.index("max_expert_pairs")].max()
                              / max(mean["held_pairs"] / (expert_layers * len(core.held_experts)), 1e-9)),  # fmt: skip
        real_positions=float(total["real_positions"]),
        padded_positions=float(total["padded_positions"]),
        rows_prefilled=int(rows_prefilled),
        # the rollout's ``seqpol_prefill`` executions, the slots they computed (a call's rung rows x the observation's
        # slots), and the prompts' tokens written among them: the useful share of the slots a prefill computes
        prefill_calls=int(prefill_calls),
        prefill_slots=int(prefill_slots),
        prefill_tokens=int(prefill_tokens),
        tokens_decoded=int(tokens_decoded),
        # the rows' lengths summed over the decodes: what each layer that keeps positions attended to (a window
        # layer's ring ``min(length, sliding_window)``; the mean over such layers where they differ)
        cache_positions=float(cache_positions),
        # over the update's real queries, the keys inside each one's window, summed over the window layers and the epochs
        window_keys=int(window_keys),
        # the pairs of a query and a key that the window layers' blocks scored, with the ring or without, over the
        # update's gradient steps: ``window_keys`` is the share of them that the band needed
        window_pairs_scored=int(total.get("window_pairs_scored", 0)),
        # rows that stood at position ``sliding_window`` or beyond as the rollout ended: their rings had wrapped
        ring_wrapped_rows=int(ring_wrapped_rows),
    )
