"""Host-side utilities (reference: sheeprl/utils/utils.py — dotdict :34,
polynomial_decay :133, save_configs :257, print_config :208, Ratio :261).

Numeric transforms (symlog, two-hot, GAE) live in ``sheeprl_tpu.ops.math`` as
jittable functions; this module is pure-Python host logic.
"""

from __future__ import annotations

import os
import warnings
from typing import Any, Dict, Mapping, Sequence

import yaml


class dotdict(dict):
    """Attribute-access dict with recursive conversion.

    Mirrors reference ``utils/utils.py:34-60`` semantics: nested mappings become
    dotdicts; attribute get/set/del proxy to the dict.
    """

    def __init__(self, *args: Any, **kwargs: Any) -> None:
        super().__init__()
        src: Dict[str, Any] = dict(*args, **kwargs)
        for k, v in src.items():
            self[k] = self._wrap(v)

    @classmethod
    def _wrap(cls, v: Any) -> Any:
        if isinstance(v, dotdict):
            return v
        if isinstance(v, Mapping):
            return cls(v)
        if isinstance(v, (list, tuple)):
            return type(v)(cls._wrap(x) for x in v)
        return v

    def __setitem__(self, key: str, value: Any) -> None:
        super().__setitem__(key, self._wrap(value))

    def __getattr__(self, name: str) -> Any:
        try:
            return self[name]
        except KeyError as e:
            raise AttributeError(name) from e

    def __setattr__(self, name: str, value: Any) -> None:
        self[name] = value

    def __delattr__(self, name: str) -> None:
        try:
            del self[name]
        except KeyError as e:
            raise AttributeError(name) from e

    def to_dict(self) -> Dict[str, Any]:
        def unwrap(v: Any) -> Any:
            if isinstance(v, dict):
                return {k: unwrap(x) for k, x in v.items()}
            if isinstance(v, (list, tuple)):
                return [unwrap(x) for x in v]
            return v

        return unwrap(self)

    def get_nested(self, dotted: str, default: Any = None) -> Any:
        node: Any = self
        for part in dotted.split("."):
            if not isinstance(node, Mapping) or part not in node:
                return default
            node = node[part]
        return node


def set_nested(d: dict, dotted: str, value: Any, create: bool = True) -> None:
    """Set a dotted key, creating missing intermediate dicts. An intermediate
    that exists but is NOT a dict is an error — silently clobbering a scalar
    with a dict would corrupt the config on a typo'd key."""
    parts = dotted.split(".")
    node = d
    for p in parts[:-1]:
        if p not in node:
            if not create:
                raise KeyError(f"missing intermediate key {p!r} in {dotted!r}")
            node[p] = {}
        elif not isinstance(node[p], dict):
            raise KeyError(
                f"cannot set {dotted!r}: intermediate key {p!r} holds a non-dict value ({node[p]!r})"
            )
        node = node[p]
    node[parts[-1]] = value


def del_nested(d: dict, dotted: str) -> None:
    parts = dotted.split(".")
    node = d
    for p in parts[:-1]:
        node = node[p]
    del node[parts[-1]]


def polynomial_decay(
    current_step: int,
    *,
    initial: float = 1.0,
    final: float = 0.0,
    max_decay_steps: int = 100,
    power: float = 1.0,
) -> float:
    """Reference ``utils/utils.py:133-145``."""
    if current_step > max_decay_steps or initial == final:
        return final
    return (initial - final) * ((1 - current_step / max_decay_steps) ** power) + final


class Ratio:
    """Replay-ratio controller (reference ``utils/utils.py:261-302``, Hafner's when.py).

    Converts the delta in policy steps since the last call into a number of
    gradient-step repeats so that ``gradient_steps / policy_steps ~= ratio``.
    The fractional residue is carried by keeping ``_prev`` as a float policy
    step. Stateful and checkpointable via ``state_dict``/``load_state_dict``
    (same keys as the reference so resumes are interchangeable).
    """

    def __init__(self, ratio: float, pretrain_steps: int = 0) -> None:
        if pretrain_steps < 0:
            raise ValueError(f"'pretrain_steps' must be non-negative, got {pretrain_steps}")
        if ratio < 0:
            raise ValueError(f"'ratio' must be non-negative, got {ratio}")
        self._pretrain_steps = pretrain_steps
        self._ratio = ratio
        self._prev: float | None = None

    def __call__(self, step: int) -> int:
        if self._ratio == 0:
            return 0
        if self._prev is None:
            self._prev = step
            if self._pretrain_steps > 0:
                if step < self._pretrain_steps:
                    warnings.warn(
                        "The number of pretrain steps is greater than the number of current steps: "
                        "capping 'pretrain_steps' to the current step to keep the requested ratio."
                    )
                    self._pretrain_steps = step
                return int(self._pretrain_steps * self._ratio)
            return 1
        repeats = int((step - self._prev) * self._ratio)
        self._prev += repeats / self._ratio
        return repeats

    def state_dict(self) -> Dict[str, Any]:
        return {"_ratio": self._ratio, "_prev": self._prev, "_pretrain_steps": self._pretrain_steps}

    def load_state_dict(self, state_dict: Mapping[str, Any]) -> "Ratio":
        self._ratio = state_dict["_ratio"]
        self._prev = state_dict["_prev"]
        self._pretrain_steps = state_dict["_pretrain_steps"]
        return self


def save_configs(cfg: Mapping[str, Any], log_dir: str) -> None:
    """Persist the resolved run config (reference ``utils/utils.py:257-259``)."""
    os.makedirs(log_dir, exist_ok=True)
    raw = cfg.to_dict() if isinstance(cfg, dotdict) else dict(cfg)
    with open(os.path.join(log_dir, "config.yaml"), "w") as f:
        yaml.safe_dump(raw, f, sort_keys=False)


def print_config(
    cfg: Mapping[str, Any],
    fields: Sequence[str] = ("algo", "buffer", "checkpoint", "env", "fabric", "metric"),
) -> None:
    """Pretty-print the config tree (reference ``utils/utils.py:208-237``)."""
    try:
        from rich.syntax import Syntax
        from rich.tree import Tree
        import rich

        tree = Tree("CONFIG")
        raw = cfg.to_dict() if isinstance(cfg, dotdict) else dict(cfg)
        for field in fields:
            if field in raw:
                branch = tree.add(field)
                branch.add(Syntax(yaml.safe_dump(raw[field], sort_keys=False), "yaml"))
        rest = {k: v for k, v in raw.items() if k not in fields and not isinstance(v, dict)}
        if rest:
            tree.add(Syntax(yaml.safe_dump(rest, sort_keys=False), "yaml"))
        rich.print(tree)
    except Exception:
        print(yaml.safe_dump(cfg.to_dict() if isinstance(cfg, dotdict) else dict(cfg), sort_keys=False))


def gradient_step_chunks(n_steps: int, algo_cfg: Mapping[str, Any]) -> list:
    """Split a variable gradient-step count into jit-shape-stable pieces.

    The SAC-family loops fuse all G gradient steps of an update into one
    scanned jit whose length is G — but ``Ratio`` varies G (most brutally on
    the first post-warmup update, which repays the whole warmup debt: G in
    the hundreds), and every distinct G compiles a fresh executable.
    Chunking caps the set of
    compiled lengths at {chunk} ∪ {possible remainders}: full chunks are
    shape-identical, the scan math is unchanged (scans compose), and only
    the remainder varies. The chunk size comes from
    ``algo.gradient_steps_chunk`` (the SAC-family yamls declare it)."""
    if n_steps <= 0:
        return []
    chunk = int(algo_cfg.get("gradient_steps_chunk", 16) or 16)
    out = [chunk] * (int(n_steps) // chunk)
    rem = int(n_steps) % chunk
    if rem:
        out.append(rem)
    return out


def weighted_chunk_metrics(chunk_metrics: list) -> Any:
    """Gradient-step-weighted mean over ``(chunk_steps, device_metrics)``
    pairs — fetched in ONE host round trip and identical to the
    pre-chunking all-G mean. Companion of :func:`gradient_step_chunks`."""
    import jax
    import numpy as np

    weights = np.array([w for w, _ in chunk_metrics], np.float64)
    stacked = np.asarray(jax.device_get([m for _, m in chunk_metrics]))
    return np.average(stacked, axis=0, weights=weights)
