"""Per-transport counters for the multi-host data plane.

Every transport endpoint registers one :class:`NetStats` under a stable name
(``tcp.learner``, ``tcp.actor3``, ``remote.replica5``, ``agent``); the
counters accumulate for the life of the process and are rolled into the run
registry record at run end (``RunTelemetry.run_summary()['net']``), mirrored
by ``python -m tools.report --net-stats``. Mutation is plain ``+=`` on int fields — every
writer is a single thread per endpoint, and the read side (telemetry rollup)
only ever snapshots, so momentary torn reads cost nothing worse than an
off-by-one in a monitoring counter.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Dict


@dataclass
class NetStats:
    """Counters for one transport endpoint."""

    name: str
    frames_sent: int = 0
    frames_recv: int = 0
    bytes_sent: int = 0
    bytes_recv: int = 0
    reconnects: int = 0
    checksum_rejects: int = 0
    heartbeat_gaps: int = 0
    stale_slabs: int = 0
    torn_frames: int = 0  # mid-frame peer death: partial frame discarded

    def snapshot(self) -> Dict[str, int]:
        return {
            "frames_sent": self.frames_sent,
            "frames_recv": self.frames_recv,
            "bytes_sent": self.bytes_sent,
            "bytes_recv": self.bytes_recv,
            "reconnects": self.reconnects,
            "checksum_rejects": self.checksum_rejects,
            "heartbeat_gaps": self.heartbeat_gaps,
            "stale_slabs": self.stale_slabs,
            "torn_frames": self.torn_frames,
        }


_lock = threading.Lock()
_registry: Dict[str, NetStats] = {}


def net_stats(name: str) -> NetStats:
    """The process-wide counter block for ``name`` (created on first use)."""
    with _lock:
        stats = _registry.get(name)
        if stats is None:
            stats = _registry[name] = NetStats(name)
        return stats


def net_stats_snapshot() -> Dict[str, Dict[str, int]]:
    """All registered endpoints' counters, for the run-end rollup."""
    with _lock:
        endpoints = list(_registry.values())
    return {s.name: s.snapshot() for s in endpoints}


def reset_net_stats() -> None:
    """Drop every registered endpoint (tests isolate counters per case)."""
    with _lock:
        _registry.clear()
