"""Shared per-log-interval throughput block for the training loops.

Every algorithm's loop used to hand-roll the same ``timer.compute()`` →
``Time/sps_*`` → ``timer.reset()`` dance; this helper centralizes it and, when
run telemetry is active, feeds the same window into
:meth:`RunTelemetry.heartbeat` so the JSONL stream, TensorBoard scalars and
``tools/report.py`` all report identical numbers.

Callers pass their own window deltas (the env-steps formula differs between
on-policy and off-policy loops) and reset their ``last_log``/``last_train``
bookkeeping themselves.
"""

from __future__ import annotations

from typing import Optional

from sheeprl_tpu.obs.span import span
from sheeprl_tpu.obs.telemetry import get_telemetry


def log_sps_and_heartbeat(
    logger,
    *,
    policy_step: int,
    env_steps: float,
    train_steps: float,
    train_invocations: Optional[float] = None,
) -> None:
    """Log ``Time/sps_train`` / ``Time/sps_env_interaction`` for the window
    since the last call, reset the span registry, and emit a telemetry
    heartbeat when the subsystem is active.

    ``env_steps``/``train_steps`` are the caller's window deltas;
    ``train_invocations`` is how many times the jitted train fn ran in the
    window (feeds MFU; None when the loop has no registered flops source)."""
    timer_window = {}
    if not span.disabled:
        timer_window = span.compute()
        sps = {}
        if timer_window.get("Time/train_time"):
            sps["Time/sps_train"] = train_steps / timer_window["Time/train_time"]
        if timer_window.get("Time/env_interaction_time"):
            sps["Time/sps_env_interaction"] = env_steps / timer_window["Time/env_interaction_time"]
        if sps:
            logger.log_metrics(sps, policy_step)
        span.reset()
    tel = get_telemetry()
    if tel is not None:
        tel.heartbeat(
            logger,
            step=policy_step,
            env_steps=env_steps,
            train_steps=train_steps,
            train_invocations=train_invocations,
            timer_window=timer_window,
        )
