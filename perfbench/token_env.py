"""A seeded token environment for a recipe that reads ``tokens`` and no pixels:
``perfbench.env.SeededEnv``'s clock on env 0 and each env's log of the actions
it was handed, in the shapes of ``sheeprl_tpu/envs/tokens.py``.

Observation ``{"tokens": int32[prompt_max], "n_tokens": int32[1]}`` (the prompt
at reset, else the token just taken), ``Discrete(vocab_rows)`` actions. Prompt
lengths and ids, response lengths and rewards all come from the seed and the
env's index, none from the actions: with random weights an end-of-sequence id
would fall anywhere, so the env ends the episode (``terminated``) after the
drawn number of response tokens and then gives the drawn 0 or 1. A
configuration names it through ``env.make``.
"""

import json
import time

import gymnasium as gym
import numpy as np

from perfbench.env import ACTION_LOG_STEPS, STAMP_CAPACITY, SeededEnv, open_stamps


def draw_episode(spec, seed, index, episode):
    """``(prompt ids, response length, reward)`` of env ``index``'s ``episode``-th episode."""
    rng = np.random.default_rng([seed, index, 2, episode])
    prompt = rng.integers(0, spec["vocab_rows"], int(rng.integers(spec["prompt"]["low"], spec["prompt"]["high"] + 1))).astype(np.int32)
    response = spec["response"]
    length = int(np.clip(np.rint(response["median"] * np.exp(response["sigma"] * rng.standard_normal())), response["low"], response["high"]))
    if episode == 0 and response.get("first"):
        length = int(response["first"])  # every seed meets its first end, and the reset path, at the same step
    reward = float(rng.choice(spec["reward"]["values"], p=spec["reward"]["probs"]))
    return prompt, length, reward


class SeededTokenEnv(SeededEnv):
    def __init__(self, spec, seed, index, stamps):
        super().__init__({**spec, "frame": [1], "episode_frames": {"low": 1, "high": 1}}, seed, index, stamps)
        self._slots = int(spec["prompt"]["high"])
        self.observation_space = gym.spaces.Dict({
            "tokens": gym.spaces.Box(0, int(spec["vocab_rows"]) - 1, (self._slots,), np.int32),
            "n_tokens": gym.spaces.Box(1, self._slots, (1,), np.int32),
        })  # fmt: skip
        self._seed, self._index = seed, index
        self._length, self._reward = 0, 0.0

    def _tokens(self, ids):
        slots = np.zeros((self._slots,), np.int32)
        slots[: len(ids)] = ids
        return {"tokens": slots, "n_tokens": np.asarray([len(ids)], np.int32)}

    def reset(self, *, seed=None, options=None):
        gym.Env.reset(self, seed=seed)
        self._episode += 1
        self._frame = 0
        prompt, self._length, self._reward = draw_episode(self.spec, self._seed, self._index, self._episode)
        return self._tokens(prompt), {}

    def step(self, action):
        t_in = time.monotonic_ns()
        self._frame += 1
        done = self._frame >= self._length
        out = (self._tokens([int(action)]), self._reward if done else 0.0, done, False, {})
        if self._action_path is not None:
            if self._actions is None:
                self._actions = np.memmap(self._action_path, dtype=np.float32, mode="w+", shape=(1 + ACTION_LOG_STEPS,))
            n = int(self._actions[0])
            if n < ACTION_LOG_STEPS:
                self._actions[1 + n] = np.float32(action)
                self._actions[0] = n + 1
        if self._stamp_path is not None:
            if self._stamps is None:
                self._stamps = open_stamps(self._stamp_path, "r+")
            n = int(self._stamps[0])
            if n < STAMP_CAPACITY:
                self._stamps[1 + 2 * n] = t_in
                self._stamps[2 + 2 * n] = time.monotonic_ns()
                self._stamps[0] = n + 1
        return out


def make(id, spec, seed=0, rank=0, stamps=None, **_):
    """``env.wrapper._target_``, with the arguments of ``perfbench.env.make``."""
    spec = json.loads(spec) if isinstance(spec, str) else (spec.to_dict() if hasattr(spec, "to_dict") else dict(spec))
    return SeededTokenEnv(spec, int(seed) - int(rank), int(rank), stamps or None)
