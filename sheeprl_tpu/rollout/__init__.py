"""Resilient rollout subsystem (host plane).

A supervised, process-based vector-env pool that is a drop-in replacement for
``gym.vector.SyncVectorEnv`` / ``AsyncVectorEnv`` across the algorithm mains
(selected behind ``env.backend=pool``; see :func:`sheeprl_tpu.envs.factory.
build_vector_env`):

- :class:`~sheeprl_tpu.rollout.pool.EnvPool` — workers step env *slots* in
  batches and write observations directly into preallocated shared-memory
  buffers (zero-copy numpy views on the host player path, one ``device_put``
  per step on the caller side), replicating gymnasium's ``SAME_STEP``
  autoreset semantics bit-for-bit.
- :class:`~sheeprl_tpu.rollout.supervisor.Supervisor` — per-worker
  heartbeats, step timeouts and crash detection; dead/hung workers are
  restarted with exponential backoff and capped retries (the in-flight
  episode is truncated, the in-flight reset replayed), and a slot whose
  worker exhausts its retries is masked dead instead of hanging the run.
- :mod:`~sheeprl_tpu.rollout.fault_injection` — a deterministic
  crash/hang/slow schedule (``rollout.fault_injection.*``) so the recovery
  paths above are exercised in CI, not discovered in production.

Telemetry: when ``metric.telemetry.enabled=True`` the pool emits
``rollout/env_step`` / ``rollout/env_reset`` spans, ``worker_restart`` and
``masked_slot`` events, and feeds the heartbeat's env step-latency p50/p95 and
queue-wait fields (``tools.report --env-stats`` summarizes the stream).

Workers never touch the TPU: the bootstrap pins ``JAX_PLATFORMS=cpu`` and
strips the distributed-coordinator environment before the child imports jax.
"""

from sheeprl_tpu.rollout.config import PoolConfig, pool_config_from_cfg
from sheeprl_tpu.rollout.fault_injection import FaultSchedule, FaultSpec, parse_fault_config
from sheeprl_tpu.rollout.pool import EnvPool
from sheeprl_tpu.rollout.supervisor import RestartBudget, WorkerDied, WorkerTimeout

__all__ = [
    "EnvPool",
    "FaultSchedule",
    "FaultSpec",
    "PoolConfig",
    "RestartBudget",
    "WorkerDied",
    "WorkerTimeout",
    "parse_fault_config",
    "pool_config_from_cfg",
]
