"""What the per-layer readers of the token policy's cell share. What its loop counts:
the ``counters`` events named ``seqpol/update`` in ``telemetry.jsonl`` (one an
update: gradient steps, routed pairs, pairs on held experts, the busiest held
expert's load over the mean, real and padded positions, rows prefilled, tokens
decoded, cache entries attended to), those of the window. A program without
them gives an empty list, and every reader then returns ``None``. And the
device's time in its train step, whole and under one ``jax.named_scope``.

The train step is read over the trace to its end, not over the stretch the
harness cuts at the window's last vector step: the window opens as a rollout
ends, so its deadline falls inside an update, the last step before it is the
rollout's last, and the cut stretch holds no train step at all. The profiler
runs until the program has left, and the program finishes the update it is in
before it leaves: those are the same ``seqpol_train_step`` executions on the
same minibatches as any other update's."""

import glob
import os
from typing import Any, Dict, List, Optional

import numpy as np

from perfbench import device_time, loader

NAME = "seqpol/update"


def updates(run: Any) -> List[Dict[str, Any]]:
    """The window's ``seqpol/update`` events, oldest first."""
    lo, hi = run.window["open_ns"], run.window["close_ns"] + int(5e6)
    found = [e for e in run.telemetry_events if e.get("event") == "counters" and e.get("name") == NAME and lo <= int(e.get("t_mono_ns", -1)) <= hi]
    return sorted(found, key=lambda e: e["t_mono_ns"])


def total(run: Any, field: str) -> Optional[float]:
    found = updates(run)
    return float(sum(e[field] for e in found)) if found else None


def per_gradient_step(run: Any, field: str) -> Optional[float]:
    steps = total(run, "gradient_steps")
    return total(run, field) / steps if steps else None


def train_steps(run: Any) -> Optional[Dict[str, Any]]:
    """``device_time.reduce`` from the traced stretch's start to the trace's
    end, made once per run: the train program's whole executions and their
    self time by scope. ``None`` where the run has no trace, no sync or no
    device plane."""
    if "_token_train_steps" in run.__dict__:
        return run.__dict__["_token_train_steps"]
    run.__dict__["_token_train_steps"] = None
    found = sorted(glob.glob(os.path.join(run.run_dir, "trace", "plugins", "profile", "*", "*.xplane.pb")))
    sync = getattr(getattr(run, "watcher", None), "sync", None)
    neutral = device_time.load(found[-1]) if found and sync is not None else None
    if neutral is None:
        return None
    tables = loader.algorithm(run.cell)
    none = np.zeros((0, 2), np.float64)
    run.__dict__["_token_train_steps"] = device_time.reduce(
        neutral,
        programs=tables.programs,
        train_program=tables.train_program,
        scopes=tables.scopes,
        sync_mono_ns=(sync["before_ns"] + sync["inside_ns"]) / 2.0,
        window_mono_ns=(float(sync["inside_ns"]), float(sync["inside_ns"]) + 3600e9),
        spans_mono_ns=none,
        env_steps_mono_ns=none,
    )
    return run.__dict__["_token_train_steps"]


def train_step_ms(run: Any) -> Optional[float]:
    """Device milliseconds per whole ``seqpol_train_step`` execution in the trace."""
    return device_time.program_ms(train_steps(run), loader.algorithm(run.cell).train_program)


def scope_ms(run: Any, scope: str) -> Optional[float]:
    """Device self time per train-step execution under ``scope``, forward and
    backward; ``None`` where the trace holds no whole step or knows no such scope."""
    reduced = train_steps(run)
    if not reduced or scope not in reduced["scopes"]:
        return None
    return device_time.scope_ms(reduced, (scope,))
