"""A seeded environment with a vector observation, for a recipe that reads
``state`` and no pixels: ``perfbench.env.SeededEnv`` (rewards, episode ends,
env 0's clock and each env's log of the actions it was handed) with
``spec['state_dim']`` uniform ``float32`` numbers under the key ``state`` in
place of the frame. A configuration names it through ``env.make``."""

import json

import gymnasium as gym
import numpy as np

from perfbench.env import SeededEnv


class SeededVectorEnv(SeededEnv):
    def __init__(self, spec, seed, index, stamps):
        super().__init__({**spec, "frame": [int(spec["state_dim"])]}, seed, index, stamps)
        self.observation_space = gym.spaces.Dict({"state": gym.spaces.Box(-1.0, 1.0, self._last.shape, np.float32)})

    def _obs(self):
        self._last = self._rng.uniform(-1.0, 1.0, self._last.shape).astype(np.float32)
        return {"state": self._last}


def make(id, spec, seed=0, rank=0, stamps=None, **_):
    """``env.wrapper._target_``, with the arguments of ``perfbench.env.make``."""
    spec = json.loads(spec) if isinstance(spec, str) else (spec.to_dict() if hasattr(spec, "to_dict") else dict(spec))
    return SeededVectorEnv(spec, int(seed) - int(rank), int(rank), stamps or None)
