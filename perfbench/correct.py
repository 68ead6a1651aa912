"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed, the reference
starts from the same seeded weights, follows the program's first three
gradient steps on the batches and keys those steps were fed, and the numbers
below are compared, each against a limit of its own (the cell's file holds
the limits; PERF.md the readings they were set from):

- ``wm_loss``, ``policy_loss``, ``value_loss``: the largest relative gap of
  that loss over the three steps;
- ``first_grad``: over all leaves, the gap between the program's and the
  reference's norm of the first gradient as the optimizer got it (after
  clipping; the program's from Adam's first moment), against the
  reference's norm of that leaf or of its tree's median leaf, whichever is
  larger;
- ``first_grad_wm``, ``first_grad_actor``, ``first_grad_critic``: the same
  over one tree's leaves of :data:`LARGE_LEAF` entries or more (a small
  leaf's norm is a sum of few terms and swings from seed to seed as far as a
  fault moves it; a tree that has no such leaf is read on all of its leaves);
- ``grad_direction``: one minus the cosine between the two sides' first
  gradient of a world-model leaf of :data:`LARGE_LEAF` entries or more, on
  the same evenly spaced entries, by the median leaf: the norms do not see
  a change that goes both ways, this does;
- ``change``: as ``first_grad`` for the norm of each leaf's change over the
  three steps; leaves whose reference gradient is under a thousandth of the
  median leaf's move under Adam by round-off alone and are left out;
- ``player_h``: the recurrent state that the player's compiled step gives
  on the seeded weights, an observation of the loop and the initial state
  (``bridge.py`` calls that same step once more, after the window, for each of
  the loop's first :data:`bridge.PLAYER_FORWARDS` calls: in the loop the two
  sides' weights differ after a gradient step by Adam's signs, which moves
  the state as far as a lower precision does), against the reference's: the
  largest difference of a component, of values in [-1, 1] (``player_h_rms``:
  the root of the mean square of them);
- ``player_z``: the latent those calls sampled from the posterior on the
  observation (encoder, recurrent model, representation model), against the
  reference's posterior perturbed with the same key: how far the chosen
  class's perturbed logit lies below the reference's best, by the widest of
  all categoricals of all the calls (0 where every sample is the same);
- ``player_action``: the actions of those calls against the reference's sampled
  with the same key at the latent the player itself reached (a categorical
  latent flips on rounding, so each side is judged at its own): continuous,
  the largest difference of a component; discrete, how far the chosen
  action's perturbed logit lies below the reference's best;
- ``ring_rows``: how many of the positions of the three batches the ring gave
  back are not what the environment produced there: the frames, rewards and
  episode ends are made again from ``--seed`` (``env.SeededEnv``), the
  actions are those the environments were handed (``env.py`` keeps them),
  and each batch row has to be consecutive entries of one environment, as the
  loop writes them. Exact: the limit is 0.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import numpy as np

from perfbench import bridge

LOSSES = ("world_model", "policy", "value")
NEGLIGIBLE_GRADIENT = 1e-3
#: entries from which a leaf's norm is steady from seed to seed
LARGE_LEAF = 4096


def _rel_gap(got: float, want: float) -> float:
    return abs(got - want) / max(abs(want), 1e-12)


def worst_leaf(got: Dict[str, float], want: Dict[str, float], keep=None) -> Dict[str, Any]:
    """Gap of norms by the worst leaf, against that leaf's reference norm or
    the median leaf's, whichever is larger."""
    names = [k for k in want if keep is None or keep(k)]
    median = float(np.median([want[k] for k in names])) if names else 0.0
    worst, where = 0.0, None
    for k in names:
        gap = abs(got[k] - want[k]) / max(want[k], median, 1e-30)
        gap = gap if np.isfinite(gap) else float("inf")
        if gap > worst or where is None:
            worst, where = gap, k
    return {"gap": worst, "leaf": where, "median": median, "leaves": len(names)}


def direction_gap(got: np.ndarray, want: np.ndarray) -> float:
    """One minus the cosine of two leaves' sampled entries."""
    a, b = np.asarray(got, np.float64).reshape(-1), np.asarray(want, np.float64).reshape(-1)
    gap = 1.0 - float(a @ b) / max(float(np.linalg.norm(a) * np.linalg.norm(b)), 1e-300)
    return gap if np.isfinite(gap) else float("inf")


def follow(cfg: Dict[str, Any], capture: "bridge.Capture", policy: str, fault: Optional[str] = None):
    """The reference's three steps on the captured feed. Returns its losses,
    per-leaf first-gradient norms, per-leaf change norms, the per-leaf change
    norms of ``capture.params_after`` (the program's) and its player action."""
    import jax
    import jax.numpy as jnp

    from perfbench.references import dreamer_v3 as reference

    model = reference.Model(cfg, policy)
    start = jax.device_put(capture.seeded)
    state = reference.initial_state(start, model.pr.weights)
    step = jax.jit(functools.partial(reference.train_step, model), donate_argnums=(0,))
    norms = jax.jit(lambda t: (bridge._leaf_norms(t), bridge._leaf_samples(t)))
    diff_norms = jax.jit(lambda a, b: bridge._leaf_norms({k: a[k] - b[k] for k in a}))
    losses, first_grads, grad_samples = [], {}, {}
    player = player_side(model, start[0], start[1], capture.player) if capture.player else None
    for i, (batch, key) in enumerate(zip(capture.batches, capture.keys)):
        state = reference.refresh_target(state, i, cfg["algo"]["critic_tau"])
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        if fault == "half_batch":
            batch = {k: v[:, : v.shape[1] // 2] for k, v in batch.items()}
        state, out = step(state, batch, jnp.asarray(key))
        losses.append({k: float(v) for k, v in out["losses"].items()})
        if i == 0:
            for name in bridge.TREES:
                grads = bridge.flat(out["grads"][name], capture.pairs[name])
                grad_norms, samples = jax.device_get(norms(grads))
                first_grads[name] = {k: float(v) for k, v in grad_norms.items()}
                grad_samples[name] = {k: np.asarray(v) for k, v in samples.items()}
        del out
    change, program_change = {}, {}
    trees = dict(zip(bridge.TREES, start))
    for name in bridge.TREES:
        before = bridge.flat(trees[name], capture.pairs[name])
        after = bridge.flat(state[name], capture.pairs[name])
        change[name] = {k: float(v) for k, v in jax.device_get(diff_norms(after, before)).items()}
        if capture.params_after:
            theirs = {k: jnp.asarray(v) for k, v in capture.params_after[name].items()}
            program_change[name] = {k: float(v) for k, v in jax.device_get(diff_norms(theirs, before)).items()}
            del theirs
    return {"losses": losses, "first_grads": first_grads, "first_grad_samples": grad_samples, "change": change,
            "program_change": program_change, "player": player}  # fmt: skip


def player_side(model, wm0, actor0, seen) -> Dict[str, Any]:
    """``model``'s player on what the program's player saw in each of its
    recorded forwards: its own recurrent state and latent from the initial
    state, and its action at the latent the program reached."""
    import jax
    import jax.numpy as jnp

    from perfbench.references import dreamer_v3 as reference

    latent = jax.jit(functools.partial(reference.player_latent, model))
    act = jax.jit(functools.partial(reference.player_action, model))
    n = seen[0]["action"].shape[0]
    h0, z0 = model.initial(wm0, n)
    prev = jnp.zeros((n, seen[0]["action"].shape[-1]), jnp.float32)
    out: Dict[str, Any] = {"h": [], "z": [], "noisy_z": [], "action": [], "noisy": []}
    for call in seen:
        key = jnp.asarray(call["key"])
        h, z, noisy_z = latent(wm0, {k: jnp.asarray(v) for k, v in call["obs"].items()}, h0, z0, prev, key)
        action, noisy = act(actor0, jnp.asarray(call["h"]), jnp.asarray(call["z"]), key)
        for name, value in (("h", h), ("z", z), ("noisy_z", noisy_z), ("action", action), ("noisy", noisy)):
            out[name].append(None if value is None else np.asarray(value, np.float64))
    return {k: None if v[0] is None else np.stack(v) for k, v in out.items()}


def _below_best(noisy: np.ndarray, chosen: np.ndarray) -> float:
    """How far the chosen class's perturbed logit lies below the best, by the
    widest case; ``chosen`` is one-hot over the last axis."""
    picked = np.take_along_axis(noisy, chosen.argmax(-1)[..., None], -1)[..., 0]
    return float((noisy.max(-1) - picked).max())


def player_gaps(theirs: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """``theirs``: the recurrent states, latents and actions of one side's
    forwards, stacked; ``ref``: the reference's, its action at their latent."""
    action = np.asarray(theirs["action"], np.float64)
    if ref["noisy"] is None:
        gap = float(np.abs(action - ref["action"]).max())
    else:
        gap = _below_best(ref["noisy"], action)
    off = np.asarray(theirs["h"], np.float64) - ref["h"]
    z = np.asarray(theirs["z"], np.float64).reshape(ref["noisy_z"].shape)
    return {"player_h": float(np.abs(off).max()), "player_h_rms": float(np.sqrt(np.square(off).mean())),
            "player_z": _below_best(ref["noisy_z"], z), "player_action": gap}  # fmt: skip


def _stacked(seen) -> Dict[str, np.ndarray]:
    return {k: np.stack([call[k] for call in seen]) for k in ("h", "z", "action")}


# --------------------------------------------------------------------------- #
# what the ring gave back, against what the environment produced
# --------------------------------------------------------------------------- #


def ring_entries(cfg: Dict[str, Any], seed: int, rank: int, handed: np.ndarray):
    """The entries that the loop writes to the ring for env ``rank``, in
    order, while ``handed`` (the actions of its ``step()`` calls) lasts: the
    frame, the reward that came with it (summed over the repeated frames),
    its ends, whether it begins an episode, and the action taken from it. An
    episode's last frame is an entry of its own with a zero action, and the
    frame of the reset that follows begins the next."""
    from perfbench.env import SeededEnv

    repeat, discrete = int(cfg["algo"]["action_repeat"]), cfg["env"]["action"]["type"] == "discrete"
    width = int(cfg["env"]["action"]["dim"])
    env = SeededEnv(cfg["env"], seed, rank, None)

    def taken(row):
        return np.eye(width, dtype=np.float32)[int(row[0])] if discrete else row.astype(np.float32)

    entries, n = [], 0
    obs, _ = env.reset()
    now = {"rgb": obs["rgb"], "reward": 0.0, "terminated": 0.0, "truncated": 0.0, "is_first": 1.0}
    while n < len(handed):
        entries.append({**now, "action": taken(handed[n])})
        total, ended = 0.0, (False, False)
        for _ in range(repeat):
            if n >= len(handed):
                return entries
            obs, reward, terminated, truncated, _ = env.step(None)
            n, total, ended = n + 1, total + reward, (terminated, truncated)
            if terminated or truncated:
                break
        now = {"rgb": obs["rgb"], "reward": total, "terminated": float(ended[0]), "truncated": float(ended[1]), "is_first": 0.0}
        if any(ended):
            entries.append({**now, "action": np.zeros(width, np.float32)})
            obs, _ = env.reset()
            now = {"rgb": obs["rgb"], "reward": 0.0, "terminated": 0.0, "truncated": 0.0, "is_first": 1.0}
    return entries


def ring_rows(cfg: Dict[str, Any], seed: int, batches, stamps: str) -> Dict[str, Any]:
    """The positions of ``batches`` (each ``[T, B]``, as the train step was
    fed them) that are not what the environments produced, with the first
    few of them named."""
    import hashlib

    from perfbench.env import read_action_log

    width = int(cfg["env"]["action"]["dim"]) if cfg["env"]["action"]["type"] == "continuous" else 1
    where, streams = {}, []
    for rank in range(int(cfg["algo"]["num_envs"])):
        entries = ring_entries(cfg, seed, rank, read_action_log(stamps, rank, width))
        streams.append(entries)
        for i, entry in enumerate(entries):
            where[hashlib.blake2b(entry["rgb"].tobytes(), digest_size=16).digest()] = (rank, i)
    bad, named = 0, []
    for n, batch in enumerate(batches):
        T, B = batch["rewards"].shape[:2]
        for b in range(B):
            before = None
            for t in range(T):
                at = where.get(hashlib.blake2b(np.ascontiguousarray(batch["rgb"][t, b]).tobytes(), digest_size=16).digest())
                why = None
                if at is None:
                    why = "frame not of any environment"
                elif before is not None and at != (before[0], before[1] + 1):
                    why = f"entry {at} after {before}"
                else:
                    entry = streams[at[0]][at[1]]
                    for key, want in (("rewards", entry["reward"]), ("terminated", entry["terminated"]), ("truncated", entry["truncated"]),
                                      ("is_first", entry["is_first"]), ("actions", entry["action"]), ("reward", entry["reward"])):  # fmt: skip
                        if key in batch and not np.allclose(np.asarray(batch[key][t, b], np.float64).reshape(-1), want, rtol=0, atol=1e-6):
                            why = f"{key} {np.asarray(batch[key][t, b]).reshape(-1)[:6]} where the environment has {want}"
                            break
                before = at
                if why is not None:
                    bad += 1
                    if len(named) < 3:
                        named.append(f"batch {n} row {b} step {t}: {why}")
    return {"bad": bad, "positions": sum(int(np.prod(b["rewards"].shape[:2])) for b in batches), "named": named,
            "entries": [len(s) for s in streams]}  # fmt: skip


def compare(theirs: Dict[str, Any], ref: Dict[str, Any], detail: Optional[Dict[str, Any]] = None) -> Dict[str, float]:
    """The numbers compared, from two sides' losses, first-gradient norms and
    change norms (``theirs`` may be the program or a control). ``detail``, if
    given, is filled with each tree's worst leaves."""
    numbers: Dict[str, float] = {}
    for loss, name in zip(LOSSES, ("wm_loss", "policy_loss", "value_loss")):
        numbers[name] = max(_rel_gap(a[loss], b[loss]) for a, b in zip(theirs["losses"], ref["losses"]))
    grad, change = 0.0, 0.0
    detail = {} if detail is None else detail
    for tree in bridge.TREES:
        want = ref["first_grads"][tree]
        g = worst_leaf(theirs["first_grads"][tree], want)
        floor = NEGLIGIBLE_GRADIENT * g["median"]
        moved = lambda k: want[k] >= floor and want[k] > 0.0  # noqa: E731
        c = worst_leaf(theirs["change"][tree], ref["change"][tree], keep=moved)
        detail[tree] = {"first_grad": g, "change": c}
        grad, change = max(grad, g["gap"]), max(change, c["gap"])
        ours = ref["first_grad_samples"][tree]
        smallest = LARGE_LEAF if any(v.size >= LARGE_LEAF for v in ours.values()) else 0  # a tree of small leaves: all of them
        large = worst_leaf(theirs["first_grads"][tree], want, keep=lambda k: ours[k].size >= smallest)
        detail[tree][f"first_grad_{tree}"] = large
        numbers[f"first_grad_{tree}"] = large["gap"]
        if tree == "wm":
            samples = theirs["first_grad_samples"][tree]
            turns = sorted((direction_gap(samples[k], ours[k]), k) for k in want if moved(k) and ours[k].size >= LARGE_LEAF)
            middle = turns[len(turns) // 2] if turns else (float("inf"), None)
            detail[tree]["grad_direction"] = {"gap": middle[0], "leaf": middle[1]}
            numbers["grad_direction"] = middle[0]
    numbers.update({"first_grad": grad, "change": change})
    if theirs.get("player") is not None and ref.get("player") is not None:
        numbers.update(player_gaps(theirs["player"], ref["player"]))
    return numbers


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict[str, Dict[str, float]]:
    """Each number that has a limit beside it; a limit without a number is
    not correct. A number without a limit is not compared (PERF.md names
    those and why): the caller prints it all the same."""
    out = {}
    for name in sorted(limits):
        value, limit = numbers.get(name), limits[name]
        ok = value is not None and np.isfinite(value) and value <= limit
        out[name] = {"value": value, "limit": limit, "ok": bool(ok)}
    return out


def program_side(capture: "bridge.Capture", ref: Dict[str, Any]) -> Dict[str, Any]:
    """What the program's recorded steps gave, in the form :func:`compare`
    takes; its change is measured by ``follow`` against the seeded weights."""
    return {
        "losses": capture.losses,
        "first_grads": capture.first_grad_norms,
        "first_grad_samples": capture.first_grad_samples,
        "change": ref["program_change"],
        "player": _stacked(capture.player) if capture.player else None,
    }


def verify(cfg: Dict[str, Any], seed: int, capture: "bridge.Capture", limits: Dict[str, float], stamps: Optional[str] = None):
    """``(correct, compared, not_compared)`` for one run of the program: each
    number that has a limit beside it, and the numbers read that have none.
    ``stamps`` is the run's stamp file, beside which the environments kept
    the actions they were handed; without it the ring is not looked at."""
    if not capture.complete:
        return False, {"captured_steps": {"value": len(capture.losses), "limit": bridge.FOLLOWED, "ok": False}}, {}
    capture.replay_player()
    ref = follow(cfg, capture, "float32")
    numbers = compare(program_side(capture, ref), ref)
    if stamps is not None:
        ring = ring_rows(cfg, seed, capture.batches, stamps)
        numbers["ring_rows"] = float(ring["bad"])
        print(f"[perfbench] ring: {ring['bad']} of {ring['positions']} batch positions differ from the environments' "
              f"{ring['entries']} entries{': ' + '; '.join(ring['named']) if ring['named'] else ''}", flush=True)  # fmt: skip
    compared = judge(numbers, limits)
    return all(v["ok"] for v in compared.values()), compared, {k: v for k, v in numbers.items() if k not in limits}
