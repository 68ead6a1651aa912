"""What the per-layer readers of the token policy's cell share. What its loop counts:
the ``counters`` events named ``seqpol/update`` in ``telemetry.jsonl`` (one an
update: gradient steps, routed pairs, pairs on held experts, the busiest held
expert's load over the mean, real and padded positions, rows prefilled, tokens
decoded, cache entries attended to), those of the window. A program without
them gives an empty list, and every reader then returns ``None``. And the
device's time in its train step, whole and under one ``jax.named_scope``.

The train step is read over the traced stretch, which in a cell that names a
cycle (``algo.rollout_steps``) is one whole cycle: from a rollout's end, over
the update that follows it, to the next rollout's end (``run.trace_stretch``).
So the stretch holds every ``seqpol_train_step`` execution of one update,
whole, beside the rollout's decodes and prefills."""

from typing import Any, Dict, List, Optional

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


def train_step_ms(run: Any) -> Optional[float]:
    """Device milliseconds per whole ``seqpol_train_step`` execution in the traced stretch."""
    return device_time.program_ms(device_time.of_run(run), loader.algorithm(run.cell).train_program)


def scope_ms(run: Any, scope: str) -> Optional[float]:
    """Device self time per train-step execution under ``scope``, forward and
    backward; ``None`` where the trace holds no whole step or knows no such scope."""
    reduced = device_time.of_run(run)
    if not reduced or scope not in reduced["scopes"]:
        return None
    return device_time.scope_ms(reduced, (scope,))
