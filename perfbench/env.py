"""The benchmark's own environment: frames, rewards and episode ends from a
seed, in the shapes of the recipe's real environment, and a clock on env 0.

The program is timed from here: env 0 writes ``time.monotonic_ns()`` at entry
and exit of every ``step()`` into a preallocated file that the harness maps
too. The vector env steps its envs in lockstep, so env 0 stands for all. It
works the same in-process (``sync``) and in a forked or spawned worker
(``async``, ``pool``): the file is opened on the first step, in whichever
process that happens.
"""

from __future__ import annotations

import json
import time
from typing import Any, Dict, Optional, Union

import gymnasium as gym
import numpy as np

#: int64 slots: [0] = steps completed, then (entry, exit) pairs
STAMP_CAPACITY = 1 << 20
#: ``step()`` calls of each env whose action is kept, from the first on: float32
#: slots, [0] = steps kept, then one row a step. The comparison for ``correct``
#: holds what the ring gives back against them (``correct.ring_rows``).
ACTION_LOG_STEPS = 1 << 15


def create_stamps(path: str) -> np.memmap:
    stamps = np.memmap(path, dtype=np.int64, mode="w+", shape=(1 + 2 * STAMP_CAPACITY,))
    stamps.flush()
    return stamps


def open_stamps(path: str, mode: str = "r") -> np.memmap:
    return np.memmap(path, dtype=np.int64, mode=mode, shape=(1 + 2 * STAMP_CAPACITY,))


def action_log_path(stamps: str, index: int) -> str:
    return f"{stamps}.actions{index}"


def read_action_log(stamps: str, index: int, width: int) -> np.ndarray:
    """``[steps kept, width]``: what env ``index`` was handed, step by step."""
    log = np.memmap(action_log_path(stamps, index), dtype=np.float32, mode="r", shape=(1 + ACTION_LOG_STEPS * width,))
    return np.array(log[1 : 1 + int(log[0]) * width]).reshape(-1, width)


def episode_lengths(spec: Dict[str, Any], seed: int, index: int) -> np.ndarray:
    """The same set of episode lengths for every seed, in another order.
    (``first``, if given, is the length of the episode before them: see
    :class:`SeededEnv`.)"""
    ep = spec["episode_frames"]
    lengths = np.linspace(ep["low"], ep["high"], int(ep.get("count", 1))).astype(np.int64)
    multiple = int(ep.get("multiple_of", 1))
    lengths = np.maximum(multiple, (lengths // multiple) * multiple)
    return np.random.default_rng([seed, index, 1]).permutation(lengths)


class SeededEnv(gym.Env):
    """64x64x3 ``uint8`` noise frames under the key ``rgb``, the recipe's
    action space, rewards drawn from ``spec['reward']`` and episodes that end
    after the drawn number of frames, by ``terminated`` or ``truncated`` as
    the real environment would."""

    metadata = {"render_modes": ["rgb_array"]}

    def __init__(self, spec: Dict[str, Any], seed: int, index: int, stamps: Optional[str]) -> None:
        self.spec = spec
        shape = tuple(spec["frame"])
        self.observation_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, shape, np.uint8)})
        action = spec["action"]
        if action["type"] == "continuous":
            self.action_space = gym.spaces.Box(-1.0, 1.0, (int(action["dim"]),), np.float32)
        elif action["type"] == "discrete":
            self.action_space = gym.spaces.Discrete(int(action["dim"]))
        else:
            raise ValueError(f"perfbench.env: unknown action type {action['type']!r}")
        reward = spec["reward"]
        self._reward_values = np.asarray(reward["values"], np.float64)
        self._reward_probs = np.asarray(reward["probs"], np.float64)
        self.reward_range = (float(self._reward_values.min()), float(self._reward_values.max()))
        self.render_mode = "rgb_array"
        self._rng = np.random.default_rng([seed, index, 0])
        self._lengths = episode_lengths(spec, seed, index)
        self._episode = -1
        self._frame = 0
        #: frames of the first episode, where the configuration fixes them: every
        #: seed then meets its first end, and with it the program's reset path,
        #: at the same step, which a configuration puts before the window
        self._first = int(spec["episode_frames"].get("first", 0))
        self._terminates = spec["episode_end"] == "terminated"
        self._stamp_path = stamps if index == 0 else None
        self._stamps: Optional[np.memmap] = None
        self._action_path = action_log_path(stamps, index) if stamps else None
        self._action_width = int(action["dim"]) if action["type"] == "continuous" else 1
        self._actions: Optional[np.memmap] = None
        self._last = np.zeros(shape, np.uint8)

    def _obs(self) -> Dict[str, np.ndarray]:
        self._last = self._rng.integers(0, 256, self._last.shape, dtype=np.uint8)
        return {"rgb": self._last}

    def reset(self, *, seed=None, options=None):
        super().reset(seed=seed)
        self._episode += 1
        self._frame = 0
        return self._obs(), {}

    def step(self, action):
        t_in = time.monotonic_ns()
        self._frame += 1
        reward = float(self._rng.choice(self._reward_values, p=self._reward_probs))
        if self._first and self._episode == 0:
            length = self._first
        else:
            length = self._lengths[(self._episode - bool(self._first)) % len(self._lengths)]
        done = self._frame >= length
        out = (self._obs(), reward, done and self._terminates, done and not self._terminates, {})
        if self._action_path is not None:
            width = self._action_width
            if self._actions is None:
                self._actions = np.memmap(self._action_path, dtype=np.float32, mode="w+", shape=(1 + ACTION_LOG_STEPS * width,))
            n = int(self._actions[0])
            if n < ACTION_LOG_STEPS:
                self._actions[1 + n * width : 1 + (n + 1) * width] = np.asarray(action, np.float32).reshape(-1)
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

    def render(self):
        return self._last

    def close(self):
        self._stamps = self._actions = None


def make(id: str, spec: Union[str, Dict[str, Any]], seed: int = 0, rank: int = 0, stamps: Optional[str] = None, **_: Any):
    """``env.wrapper._target_``: ``rank`` is the env's index in the vector
    env, ``seed`` the run's seed plus that index (``envs/factory.py``)."""
    if isinstance(spec, str):
        spec = json.loads(spec)
    spec = spec.to_dict() if hasattr(spec, "to_dict") else dict(spec)
    return SeededEnv(spec, int(seed) - int(rank), int(rank), stamps or None)
