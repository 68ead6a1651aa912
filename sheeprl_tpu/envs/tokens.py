"""A token environment: the reset observation carries a prompt, one env step
is one token, and a verifier's reward comes at the episode's end.

Observation ``{"tokens": int32[prompt_max], "n_tokens": int32[1]}``: at reset
the prompt in the first ``n_tokens`` slots, afterwards the token just taken in
slot 0 and ``n_tokens`` 1. Actions are ``Discrete(vocab_rows)``. The episode
ends by ``terminated`` when the response is complete (the task's length, or
the end-of-sequence id) and by ``truncated`` at the context limit (prompt plus
response). The reward is 0 until the end and then the verifier's 0 or 1.

The task is seeded copy-the-prompt: the response is right when it repeats the
prompt token for token. It is what a CPU test needs to show that a token
policy learns; a real verifier takes its place through ``env.wrapper``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import gymnasium as gym
import numpy as np


class TokenEnv(gym.Env):
    def __init__(self, id: str = "copy", vocab_rows: int = 16, prompt_min: int = 1, prompt_max: int = 4, context: int = 16,
                 alphabet: Optional[int] = None, eos_id: Optional[int] = None, seed: int = 0, **_: Any) -> None:  # fmt: skip
        if id != "copy":
            raise ValueError(f"TokenEnv knows the task 'copy', not {id!r}")
        if not 1 <= prompt_min <= prompt_max or 2 * prompt_max > context:
            raise ValueError(f"prompts of {prompt_min} to {prompt_max} tokens and their copies do not fit a context of {context}")
        self.vocab_rows, self.prompt_min, self.prompt_max, self.context = int(vocab_rows), int(prompt_min), int(prompt_max), int(context)
        #: prompts draw their ids from the first ``alphabet`` rows (all of them if not given), never the end-of-sequence id
        self.alphabet = int(alphabet or vocab_rows)
        self.eos_id = None if eos_id is None else int(eos_id)
        self.observation_space = gym.spaces.Dict({
            "tokens": gym.spaces.Box(0, self.vocab_rows - 1, (self.prompt_max,), np.int32),
            "n_tokens": gym.spaces.Box(1, self.prompt_max, (1,), np.int32),
        })  # fmt: skip
        self.action_space = gym.spaces.Discrete(self.vocab_rows)
        self._rng = np.random.default_rng(seed)
        self._prompt = np.zeros((0,), np.int32)
        self._taken = 0
        self._right = True

    def _obs(self, tokens: np.ndarray) -> Dict[str, np.ndarray]:
        slots = np.zeros((self.prompt_max,), np.int32)
        slots[: len(tokens)] = tokens
        return {"tokens": slots, "n_tokens": np.asarray([len(tokens)], np.int32)}

    def reset(self, *, seed: Optional[int] = None, options: Optional[Dict[str, Any]] = None) -> Tuple[Dict[str, np.ndarray], Dict]:
        super().reset(seed=seed)
        if seed is not None:
            self._rng = np.random.default_rng(seed)
        n = int(self._rng.integers(self.prompt_min, self.prompt_max + 1))
        ids = [i for i in range(self.alphabet) if i != self.eos_id]
        self._prompt = self._rng.choice(ids, size=n).astype(np.int32)
        self._taken, self._right = 0, True
        return self._obs(self._prompt), {}

    def step(self, action):
        action = int(action)
        ended = self.eos_id is not None and action == self.eos_id
        if not ended:
            self._right = self._right and action == int(self._prompt[self._taken])
            self._taken += 1
        terminated = ended or self._taken == len(self._prompt)
        truncated = not terminated and len(self._prompt) + self._taken >= self.context
        reward = float(terminated and self._right and self._taken == len(self._prompt))
        return self._obs(np.asarray([action], np.int32)), reward, terminated, truncated, {}
