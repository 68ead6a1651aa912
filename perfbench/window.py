"""The window's arithmetic, on env 0's timestamps alone.

A vector step is ``action_repeat`` consecutive ``step()`` calls of env 0 (the
recipe's episode lengths are multiples of it, so the grouping is exact). The
window opens when vector step ``open_index`` returns and holds every later
vector step that returned by the deadline: ``n`` cycles, each a wait (the
agent acts and trains while the environment waits) and a step.

A bulk-synchronous loop (a rollout of ``cycle`` vector steps, then an update in
which no step is taken) names its cycle: its configuration's
``algo.rollout_steps``. Vector step ``i`` ends a rollout where ``(i + 1) %
cycle == 0``; the window then opens on a rollout's end and closes on the last
one that returned by the deadline, so it holds whole (update, rollout) cycles
wherever in a cycle the deadline falls.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import numpy as np


def vector_steps(stamps: np.ndarray, action_repeat: int) -> Tuple[np.ndarray, np.ndarray]:
    """``(entry_ns, exit_ns)`` of every complete vector step."""
    done = int(stamps[0]) // action_repeat
    pairs = np.asarray(stamps[1 : 1 + 2 * done * action_repeat]).reshape(done, action_repeat, 2)
    return pairs[:, 0, 0].copy(), pairs[:, -1, 1].copy()


def quantile(values: np.ndarray, q: float) -> float:
    return float(np.quantile(values, q)) if len(values) else float("nan")


def rollout_end(index: int, cycle: int) -> int:
    """The first vector step at or after ``index`` that ends a rollout."""
    return -(-(index + 1) // cycle) * cycle - 1


def measure(entry: np.ndarray, exit_: np.ndarray, open_index: int, deadline_ns: int, num_envs: int,
            cycle: Optional[int] = None) -> Dict[str, Any]:  # fmt: skip
    """End-to-end numbers of the window ``(exit_[open_index], deadline_ns]``;
    with ``cycle`` (vector steps a rollout) of the whole cycles in it, from
    ``open_index``, which has to end a rollout, to the last rollout end that
    returned by the deadline."""
    last = int(np.searchsorted(exit_, deadline_ns, side="right")) - 1
    if cycle is not None:
        if rollout_end(open_index, cycle) != open_index:
            raise RuntimeError(f"perfbench: the window opens at vector step {open_index}, which ends no rollout of {cycle}")
        last = rollout_end(last + 1, cycle) - cycle
        if last <= open_index:
            raise RuntimeError(f"perfbench: the window holds no whole cycle of {cycle} vector steps")
    cycles = last - open_index
    if cycles < 1:
        raise RuntimeError(f"perfbench: the window holds {cycles} vector steps")
    seconds = (exit_[last] - exit_[open_index]) / 1e9
    waits_ms = (entry[open_index + 1 : last + 1] - exit_[open_index : last]) / 1e6
    in_step_s = float((exit_[open_index + 1 : last + 1] - entry[open_index + 1 : last + 1]).sum()) / 1e9
    return {
        "open_ns": int(exit_[open_index]),
        "close_ns": int(exit_[last]),
        "first": open_index + 1,
        "last": last,
        "vector_steps": cycles,
        "policy_steps": cycles * num_envs,
        "seconds": seconds,
        "env_steps_per_s": cycles * num_envs / seconds,
        "env_wait_ms_p95": quantile(waits_ms, 0.95),
        "env_wait_ms_p50": quantile(waits_ms, 0.50),
        "env_step_share": in_step_s / seconds,
        # the three longest waits as (ms, vector step): a stall of the host shows here and not in the tail
        "longest_waits_ms": [(float(waits_ms[i]), int(open_index + 1 + i)) for i in np.argsort(waits_ms)[::-1][:3]],
    }
