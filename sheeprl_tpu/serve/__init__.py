"""Fault-tolerant policy-serving tier (``python -m sheeprl_tpu serve``).

Turns a committed training checkpoint into an inference service with the
robustness properties howto/serving.md documents: AOT-compiled batch ladder
(no request pays a JIT), SLO-bounded micro-batching, bounded queue with
typed load shedding, supervised replicas with budgeted restarts and
degraded N-1 mode, circuit breaking, and validated hot checkpoint swap
with rollback.

Import layering mirrors ``rollout``: this package root re-exports the
jax-free surface eagerly; :mod:`~sheeprl_tpu.serve.model` /
:mod:`~sheeprl_tpu.serve.server` (which import jax) are re-exported lazily
so parents that stay off jax can read configs and errors without touching
an accelerator runtime.
"""

from __future__ import annotations

from typing import Any

from sheeprl_tpu.serve.batching import MicroBatcher, Request
from sheeprl_tpu.serve.config import FleetConfig, LoadConfig, ServeConfig, serve_config_from_cfg
from sheeprl_tpu.serve.errors import (
    DeadlineExceeded,
    InferenceFailed,
    Overloaded,
    ServeError,
    ServerClosed,
    SwapRejected,
)
from sheeprl_tpu.serve.fault_injection import (
    ServeFaultSchedule,
    ServeFaultSpec,
    parse_serve_faults,
)

_LAZY = {
    "CompiledLadder": "sheeprl_tpu.serve.model",
    "ModelStore": "sheeprl_tpu.serve.model",
    "ModelVersion": "sheeprl_tpu.serve.model",
    "ServedPolicy": "sheeprl_tpu.serve.model",
    "newest_committed": "sheeprl_tpu.serve.model",
    "PolicyServer": "sheeprl_tpu.serve.server",
    "ServeStats": "sheeprl_tpu.serve.server",
    "Replica": "sheeprl_tpu.serve.replica",
    "ReplicaStats": "sheeprl_tpu.serve.replica",
    "ReplicaSet": "sheeprl_tpu.serve.supervisor",
    "ReplicaSlot": "sheeprl_tpu.serve.supervisor",
    "ServeClient": "sheeprl_tpu.serve.client",
    "run_load": "sheeprl_tpu.serve.loadgen",
    "run_ramp": "sheeprl_tpu.serve.loadgen",
    "ramp_rates": "sheeprl_tpu.serve.loadgen",
    "SlotPool": "sheeprl_tpu.serve.slots",
    "safe_complete": "sheeprl_tpu.serve.slots",
    "Router": "sheeprl_tpu.serve.router",
    "RoutedRequest": "sheeprl_tpu.serve.router",
    "RouteTarget": "sheeprl_tpu.serve.router",
    "FleetServer": "sheeprl_tpu.serve.fleet",
    "FleetReplica": "sheeprl_tpu.serve.fleet",
    "FleetSlot": "sheeprl_tpu.serve.fleet",
    "POLICY_BUILDERS": "sheeprl_tpu.serve.policy",
    "build_served_policy": "sheeprl_tpu.serve.policy",
    "make_linear_state": "sheeprl_tpu.serve.policy",
    "register_policy_builder": "sheeprl_tpu.serve.policy",
}


def __getattr__(name: str) -> Any:
    module = _LAZY.get(name)
    if module is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(module), name)


__all__ = [
    "DeadlineExceeded",
    "FleetConfig",
    "InferenceFailed",
    "LoadConfig",
    "MicroBatcher",
    "Overloaded",
    "Request",
    "ServeConfig",
    "ServeError",
    "ServeFaultSchedule",
    "ServeFaultSpec",
    "ServerClosed",
    "SwapRejected",
    "parse_serve_faults",
    "serve_config_from_cfg",
    *sorted(_LAZY),
]
