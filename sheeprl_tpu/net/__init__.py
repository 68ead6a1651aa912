"""Multi-host data plane: transport-abstracted slab/param/inference traffic.

The package generalizes the PR 11 shared-memory data plane (trajectory ring +
param lane) and the PR 12 in-process replica fleet across a process/host
boundary:

- :mod:`sheeprl_tpu.net.frame` — length-prefixed frame codec. Every frame is
  CRC-guarded and carries a type tag; the decoder survives partial reads and
  rejects a corrupt frame without poisoning the rest of the stream.
- :mod:`sheeprl_tpu.net.transport` — the ``Transport`` seam between the
  learner and its actors. ``ShmTransport*`` wraps the existing
  :class:`~sheeprl_tpu.actor_learner.ring.TrajectoryRing` +
  :class:`~sheeprl_tpu.actor_learner.param_lane.ParamLane`;
  ``TcpTransport*`` ships the SAME ``SlabLayout`` wire bytes and the SAME
  10-word slab header (checksum included) over localhost/remote TCP, so the
  torn-write discipline and trace-id stamping survive the socket.
- :mod:`sheeprl_tpu.net.agent` — the per-host replica agent process serving
  ``INFER`` frames, adopted by the fleet as a remote replica.
- :mod:`sheeprl_tpu.net.remote` — the fleet-side ``RemoteReplica`` thread
  that bridges a :class:`~sheeprl_tpu.serve.slots.SlotPool` to one agent.
- :mod:`sheeprl_tpu.net.stats` — per-transport counters (frames, bytes,
  reconnects, checksum rejects, heartbeat gaps) surfaced through the
  ``net_event`` telemetry stream and ``python -m tools.report --net-stats``.
"""

from sheeprl_tpu.net.agent import ReplicaAgent, agent_child_main
from sheeprl_tpu.net.frame import (
    FrameDecoder,
    ProtocolError,
    encode_frame,
)
from sheeprl_tpu.net.remote import RemoteReplica
from sheeprl_tpu.net.stats import NetStats, net_stats, net_stats_snapshot, reset_net_stats
from sheeprl_tpu.net.transport import (
    ActorTransport,
    LearnerTransport,
    ShmActorTransport,
    ShmLearnerTransport,
    TcpActorTransport,
    TcpLearnerTransport,
    attach_actor_transport,
    build_learner_transport,
)

__all__ = [
    "ActorTransport",
    "FrameDecoder",
    "LearnerTransport",
    "NetStats",
    "ProtocolError",
    "RemoteReplica",
    "ReplicaAgent",
    "agent_child_main",
    "ShmActorTransport",
    "ShmLearnerTransport",
    "TcpActorTransport",
    "TcpLearnerTransport",
    "attach_actor_transport",
    "build_learner_transport",
    "encode_frame",
    "net_stats",
    "net_stats_snapshot",
    "reset_net_stats",
]
