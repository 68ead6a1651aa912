"""The ``Transport`` seam between the learner and its actors.

Two ends, two implementations each:

- **learner end** (:class:`LearnerTransport`): owns slab intake, torn
  accounting and the versioned param broadcast. ``poll()`` yields the next
  cleanly committed :class:`~sheeprl_tpu.actor_learner.ring.SlabMeta`;
  ``publish_params`` pushes a packed param vector to every attached actor.
- **actor end** (:class:`ActorTransport`): the staged slab write —
  ``try_begin_write → payload_view → write_meta → commit`` — plus the param
  subscription. The staging mirrors the ring's seqlock protocol exactly, so
  the crash drills (die between ``write_meta`` and ``commit``) mean the same
  thing on both transports.

``Shm*`` wraps the PR 11 shared-memory ring + lane unchanged. ``Tcp*`` ships
the SAME bytes over a socket: a ``SLAB`` frame's payload is the ring's
10-word int64 header (checksum word included, computed by the same
``_checksum`` mix) followed by the ``SlabLayout``-packed slab, so torn-write
detection and trace-id stamping survive the network. Commit discipline maps
onto framing: a slab is *committed* iff its frame arrived complete with both
checksums (frame CRC + header mix) intact — a mid-frame peer death or a
corrupt frame is *torn*, counted, and never admitted, exactly like a
``WRITING`` or checksum-mismatched ring slot.

Flow control replaces the ring's slot ownership: the learner grants each
actor ``slots_per_actor`` credits at HELLO; a ``SLAB`` spends one, a
``SLAB_ACK`` (sent when the learner releases the slab) returns it. An actor
with zero credits blocks in ``try_begin_write`` — the same backpressure as a
full ring.

Reconnects carry a **generation bump**: the supervisor respawns a dead actor
with ``generation + 1``, the new HELLO raises the learner's floor for that
actor id, and any slab arriving on an older-generation connection (a zombie
that was mid-``sendall`` when declared dead) is dropped as stale, never
admitted. Slabs that fully arrived before the death are kept — committed is
committed, the shm rule.

Threading: each endpoint object is single-threaded by design (the learner
loop owns the learner end; the actor loop owns the actor end). Sockets are
pumped inline from ``poll``/``try_begin_write``/``param_version`` with
zero-timeout selects, so no background thread ever touches shared state.
"""

from __future__ import annotations

import json
import select
import socket
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import numpy as np

from sheeprl_tpu.actor_learner.param_lane import ParamLane
from sheeprl_tpu.actor_learner.ring import (
    CHECKSUM,
    COMMITTED,
    COMMIT_T_US,
    HEADER_WORDS,
    SEQ,
    STATE,
    ACTOR_ID,
    COLLECT_US,
    ENV_STEPS,
    N_ROWS,
    PARAM_VERSION,
    TRACE_ID,
    SlabMeta,
    TrajectoryRing,
    _checksum,
)
from sheeprl_tpu.net.frame import (
    F_BYE,
    F_HEARTBEAT,
    F_HELLO,
    F_HELLO_ACK,
    F_PARAM,
    F_SLAB,
    F_SLAB_ACK,
    FrameDecoder,
    ProtocolError,
    encode_frame,
)
from sheeprl_tpu.net.stats import NetStats, net_stats
from sheeprl_tpu.obs.trace import trace_event

_HEADER_BYTES = HEADER_WORDS * 8
_RECV_CHUNK = 1 << 16
_SEND_TIMEOUT_S = 30.0
_HANDSHAKE_TIMEOUT_S = 30.0


class TransportError(RuntimeError):
    """The peer is gone or the stream is unrecoverable."""


# --------------------------------------------------------------------------
# learner end
# --------------------------------------------------------------------------


class LearnerTransport:
    """Abstract learner end: slab intake + torn accounting + param lane."""

    kind: str = "?"
    torn_detected: int = 0

    def actor_wire(self, actor_index: int) -> Dict[str, Any]:
        """Picklable attach handle for one actor's child process."""
        raise NotImplementedError

    def pump(self) -> None:
        """Service the transport without consuming a slab (accepts, HELLO/ACK
        handshakes, heartbeats). No-op on shm; the supervisor calls this from
        its blocking waits so a dialing actor is never starved."""

    def poll(self) -> Optional[SlabMeta]:
        """Next cleanly committed slab, or None (keep polling)."""
        raise NotImplementedError

    def payload(self, meta: SlabMeta) -> np.ndarray:
        """The polled slab's payload bytes (valid until :meth:`release`)."""
        raise NotImplementedError

    def release(self, meta: SlabMeta) -> None:
        raise NotImplementedError

    def occupancy(self) -> float:
        raise NotImplementedError

    def drain_torn_trace_ids(self) -> List[int]:
        raise NotImplementedError

    def reclaim_actor(self, actor_index: int, slots: Sequence[int]) -> int:
        """Reclaim a dead actor's in-flight capacity; returns newly counted
        torn writes (shm: WRITING slots freed; tcp: already counted at
        disconnect, so 0)."""
        raise NotImplementedError

    def publish_params(self, flat: np.ndarray, version: int) -> None:
        raise NotImplementedError

    def net_stats(self) -> Optional[NetStats]:
        return None

    def close(self) -> None:
        raise NotImplementedError


class ShmLearnerTransport(LearnerTransport):
    """Same-host transport: the PR 11 ring + lane, unchanged semantics."""

    kind = "shm"

    def __init__(self, *, payload_bytes: int, num_slots: int, param_nbytes: int) -> None:
        self.ring = TrajectoryRing(num_slots, payload_bytes)
        self.lane = ParamLane(param_nbytes)
        self._cursor = 0

    # the learner's telemetry reads these through the transport
    @property
    def torn_detected(self) -> int:  # type: ignore[override]
        return self.ring.torn_detected

    def actor_wire(self, actor_index: int) -> Dict[str, Any]:
        return {"kind": "shm", "ring": self.ring.spec(), "lane": self.lane.spec()}

    def poll(self) -> Optional[SlabMeta]:
        n = self.ring.num_slots
        for k in range(n):
            s = (self._cursor + k) % n
            meta = self.ring.poll(s)
            if meta is not None:
                self._cursor = (s + 1) % n
                return meta
        return None

    def payload(self, meta: SlabMeta) -> np.ndarray:
        return self.ring.payload_view(meta.slot)

    def release(self, meta: SlabMeta) -> None:
        self.ring.release(meta.slot)

    def occupancy(self) -> float:
        return self.ring.occupancy()

    def drain_torn_trace_ids(self) -> List[int]:
        return self.ring.drain_torn_trace_ids()

    def reclaim_actor(self, actor_index: int, slots: Sequence[int]) -> int:
        return self.ring.reclaim_actor_slots(slots)

    def publish_params(self, flat: np.ndarray, version: int) -> None:
        self.lane.publish(flat, version)

    def close(self) -> None:
        self.ring.close()
        self.lane.close()


class _ActorConn:
    """Learner-side state for one accepted actor connection."""

    __slots__ = ("sock", "decoder", "actor_id", "generation", "last_beat", "gap_flagged", "addr")

    def __init__(self, sock: socket.socket, addr: Any) -> None:
        self.sock = sock
        self.decoder = FrameDecoder()
        self.actor_id: Optional[int] = None
        self.generation = -1
        self.last_beat = time.monotonic()
        self.gap_flagged = False
        self.addr = addr


class TcpLearnerTransport(LearnerTransport):
    """Cross-host transport: the learner listens, actors dial in."""

    kind = "tcp"

    def __init__(
        self,
        *,
        payload_bytes: int,
        num_slots: int,
        slots_per_actor: int,
        param_nbytes: int,
        host: str = "127.0.0.1",
        port: int = 0,
        hb_timeout_s: float = 10.0,
    ) -> None:
        self.payload_bytes = int(payload_bytes)
        self.num_slots = int(num_slots)
        self.slots_per_actor = int(slots_per_actor)
        self.param_nbytes = int(param_nbytes)
        self.hb_timeout_s = float(hb_timeout_s)
        self.stats = net_stats("tcp.learner")
        self.torn_detected = 0
        self.torn_trace_ids: List[int] = []
        self._listen = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listen.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listen.bind((host, int(port)))
        self._listen.listen(64)
        self._listen.setblocking(False)
        self.host, self.port = self._listen.getsockname()[:2]
        self._conns: List[_ActorConn] = []
        # newest generation seen per actor id: the stale-slab floor
        self._generations: Dict[int, int] = {}
        # committed slabs awaiting poll: (meta, payload, arrival generation)
        self._pending: Deque[Tuple[SlabMeta, np.ndarray]] = deque()
        self._open: Dict[Tuple[int, int], np.ndarray] = {}  # (actor_id, seq) -> payload
        self._param_frame: Optional[bytes] = None  # latest PARAM, replayed to late joiners
        self._closed = False

    @property
    def address(self) -> Tuple[str, int]:
        return (self.host, self.port)

    def actor_wire(self, actor_index: int) -> Dict[str, Any]:
        return {
            "kind": "tcp",
            "host": self.host,
            "port": self.port,
            "payload_bytes": self.payload_bytes,
            "param_nbytes": self.param_nbytes,
        }

    # ------------------------------------------------------------------ pump
    def _pump(self) -> None:
        if self._closed:
            return
        while True:
            socks = [self._listen] + [c.sock for c in self._conns]
            try:
                readable, _, _ = select.select(socks, [], [], 0)
            except (OSError, ValueError):
                readable = []
            if not readable:
                break
            for sock in readable:
                if sock is self._listen:
                    self._accept()
                else:
                    conn = next((c for c in self._conns if c.sock is sock), None)
                    if conn is not None:
                        self._read(conn)
        now = time.monotonic()
        for conn in self._conns:
            if conn.actor_id is None:
                continue
            if now - conn.last_beat > self.hb_timeout_s:
                if not conn.gap_flagged:
                    conn.gap_flagged = True
                    self.stats.heartbeat_gaps += 1
                    _net_event("heartbeat_gap", transport="tcp.learner", actor=conn.actor_id)
            else:
                conn.gap_flagged = False

    def _accept(self) -> None:
        try:
            sock, addr = self._listen.accept()
        except OSError:
            return
        sock.setblocking(True)
        sock.settimeout(_SEND_TIMEOUT_S)
        self._conns.append(_ActorConn(sock, addr))

    def _read(self, conn: _ActorConn) -> None:
        try:
            data = conn.sock.recv(_RECV_CHUNK)
        except (BlockingIOError, InterruptedError):
            return
        except OSError:
            self._drop(conn, "recv error")
            return
        if not data:
            self._drop(conn, "peer closed")
            return
        self.stats.bytes_recv += len(data)
        before = conn.decoder.checksum_rejects
        try:
            frames = conn.decoder.feed(data)
        except ProtocolError:
            self._drop(conn, "protocol error")
            return
        rejected = conn.decoder.checksum_rejects - before
        if rejected:
            self.stats.checksum_rejects += rejected
            # a skipped frame on a slab link is a torn write: something was
            # committed by the peer and will never be admitted
            self.torn_detected += rejected
            _net_event("checksum_reject", transport="tcp.learner", count=rejected)
        for ftype, _flags, payload in frames:
            self.stats.frames_recv += 1
            conn.last_beat = time.monotonic()
            if ftype == F_HELLO:
                self._handle_hello(conn, payload)
            elif ftype == F_SLAB:
                self._handle_slab(conn, payload)
            elif ftype == F_HEARTBEAT:
                pass  # beat already recorded
            elif ftype == F_BYE:
                self._drop(conn, "bye", count_torn=False)
                return

    def _handle_hello(self, conn: _ActorConn, payload: bytes) -> None:
        try:
            hello = json.loads(payload.decode("utf-8"))
        except ValueError:
            self._drop(conn, "bad hello")
            return
        actor_id = int(hello.get("actor_id", -1))
        generation = int(hello.get("generation", 0))
        floor = self._generations.get(actor_id, -1)
        if generation >= floor:
            self._generations[actor_id] = generation
            # a newer incarnation supersedes any zombie connection still
            # holding this actor id — drop the zombie NOW so its in-flight
            # bytes can never race the successor's
            for other in list(self._conns):
                if other is not conn and other.actor_id == actor_id:
                    self._drop(other, "superseded by reconnect")
            if floor >= 0:
                self.stats.reconnects += 1
                _net_event("reconnect", transport="tcp.learner", actor=actor_id, generation=generation)
        conn.actor_id = actor_id
        conn.generation = generation
        now_wall = time.time()
        skew_s = now_wall - float(hello.get("t_wall", now_wall))
        trace_event(
            "net_handshake",
            peer=str(hello.get("role", f"actor{actor_id}")),
            actor=actor_id,
            generation=generation,
            skew_s=skew_s,
            transport="tcp",
        )
        ack = {
            "role": "learner",
            "credits": self.slots_per_actor,
            "payload_bytes": self.payload_bytes,
            "param_nbytes": self.param_nbytes,
            "t_wall": now_wall,
            "t_echo": hello.get("t_wall"),
        }
        self._send(conn, encode_frame(F_HELLO_ACK, json.dumps(ack).encode("utf-8")))
        if self._param_frame is not None:
            self._send(conn, self._param_frame)

    def _handle_slab(self, conn: _ActorConn, payload: bytes) -> None:
        if len(payload) != _HEADER_BYTES + self.payload_bytes:
            self._drop(conn, f"slab frame of {len(payload)} bytes (want {_HEADER_BYTES + self.payload_bytes})")
            return
        hdr = np.frombuffer(payload, dtype=np.int64, count=HEADER_WORDS)
        if int(hdr[CHECKSUM]) != _checksum(hdr[SEQ:CHECKSUM]):
            # frame CRC passed but the slab header mix did not: stale or
            # recycled meta — the ring's torn-slab classes, over the wire
            self.torn_detected += 1
            self.stats.checksum_rejects += 1
            tid = int(hdr[TRACE_ID])
            if tid:
                self.torn_trace_ids.append(tid)
            _net_event("checksum_reject", transport="tcp.learner", actor=conn.actor_id, layer="slab_header")
            return
        actor_id = int(hdr[ACTOR_ID])
        if conn.generation < self._generations.get(actor_id, conn.generation):
            # zombie connection of a superseded incarnation: the supervisor
            # already reclaimed this actor — never re-admit its slabs
            self.stats.stale_slabs += 1
            _net_event("stale_slab", transport="tcp.learner", actor=actor_id, generation=conn.generation)
            return
        meta = SlabMeta(
            slot=-1,
            seq=int(hdr[SEQ]),
            param_version=int(hdr[PARAM_VERSION]),
            actor_id=actor_id,
            n_rows=int(hdr[N_ROWS]),
            collect_us=int(hdr[COLLECT_US]),
            env_steps=int(hdr[ENV_STEPS]),
            trace_id=int(hdr[TRACE_ID]),
            commit_t_us=int(hdr[COMMIT_T_US]),
        )
        slab = np.frombuffer(payload, dtype=np.uint8, offset=_HEADER_BYTES).copy()
        self._pending.append((meta, slab))

    def _drop(self, conn: _ActorConn, reason: str, *, count_torn: bool = True) -> None:
        if count_torn:
            partial = conn.decoder.partial()
            if partial is not None:
                ftype, _length, got = partial
                if ftype in (F_SLAB, -1):
                    # mid-frame peer death: the canonical torn write of the
                    # TCP transport. If the slab header fully landed and its
                    # mix checks out, the trace id is trustworthy — attribute
                    # the victim, like reclaim_actor_slots does
                    self.torn_detected += 1
                    self.stats.torn_frames += 1
                    if len(got) >= _HEADER_BYTES:
                        hdr = np.frombuffer(got, dtype=np.int64, count=HEADER_WORDS)
                        tid = int(hdr[TRACE_ID])
                        if tid and int(hdr[CHECKSUM]) == _checksum(hdr[SEQ:CHECKSUM]):
                            self.torn_trace_ids.append(tid)
                    _net_event("torn_frame", transport="tcp.learner", actor=conn.actor_id)
        try:
            conn.sock.close()
        except OSError:
            pass
        if conn in self._conns:
            self._conns.remove(conn)
        _net_event("disconnect", transport="tcp.learner", actor=conn.actor_id, reason=reason)

    def _send(self, conn: _ActorConn, frame: bytes) -> None:
        try:
            conn.sock.sendall(frame)
        except OSError:
            self._drop(conn, "send error")
            return
        self.stats.frames_sent += 1
        self.stats.bytes_sent += len(frame)

    # ------------------------------------------------------------------- api
    def pump(self) -> None:
        self._pump()

    def poll(self) -> Optional[SlabMeta]:
        self._pump()
        if not self._pending:
            return None
        meta, slab = self._pending.popleft()
        self._open[(meta.actor_id, meta.seq)] = slab
        return meta

    def payload(self, meta: SlabMeta) -> np.ndarray:
        return self._open[(meta.actor_id, meta.seq)]

    def release(self, meta: SlabMeta) -> None:
        self._open.pop((meta.actor_id, meta.seq), None)
        conn = next((c for c in self._conns if c.actor_id == meta.actor_id), None)
        if conn is not None:
            ack = np.int64(meta.seq).tobytes()
            self._send(conn, encode_frame(F_SLAB_ACK, ack))

    def occupancy(self) -> float:
        return (len(self._pending) + len(self._open)) / max(1, self.num_slots)

    def drain_torn_trace_ids(self) -> List[int]:
        ids, self.torn_trace_ids = self.torn_trace_ids, []
        return ids

    def reclaim_actor(self, actor_index: int, slots: Sequence[int]) -> int:
        # raise the generation floor NOW (the respawn's HELLO will raise it
        # again) and sever any connection still claiming this actor id; torn
        # partial frames were counted at disconnect, so nothing new here
        self._generations[actor_index] = self._generations.get(actor_index, 0) + 1
        for conn in list(self._conns):
            if conn.actor_id == actor_index:
                self._drop(conn, "reclaimed")
        return 0

    def publish_params(self, flat: np.ndarray, version: int) -> None:
        flat = np.asarray(flat, dtype=np.uint8).reshape(-1)
        if flat.shape[0] != self.param_nbytes:
            raise ValueError(f"param lane expects {self.param_nbytes} bytes, got {flat.shape[0]}")
        self._pump()
        frame = encode_frame(F_PARAM, np.int64(version).tobytes() + flat.tobytes())
        self._param_frame = frame
        for conn in list(self._conns):
            if conn.actor_id is not None:
                self._send(conn, frame)

    def net_stats(self) -> Optional[NetStats]:
        return self.stats

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        for conn in list(self._conns):
            try:
                conn.sock.sendall(encode_frame(F_BYE))
            except OSError:
                pass
            try:
                conn.sock.close()
            except OSError:
                pass
        self._conns.clear()
        try:
            self._listen.close()
        except OSError:
            pass
        _net_event("transport_close", transport="tcp.learner", **self.stats.snapshot())


# --------------------------------------------------------------------------
# actor end
# --------------------------------------------------------------------------


class ActorTransport:
    """Abstract actor end: staged slab writes + param subscription."""

    kind: str = "?"

    def try_begin_write(self) -> bool:
        raise NotImplementedError

    def payload_view(self) -> np.ndarray:
        raise NotImplementedError

    def write_meta(self, **meta: int) -> None:
        raise NotImplementedError

    def commit(self) -> None:
        raise NotImplementedError

    def abort_torn(self) -> None:
        """Crash-drill hook: leave the staged write torn (shm: slot stays
        WRITING; tcp: half a frame on the wire) — the caller dies next."""
        raise NotImplementedError

    def param_version(self) -> int:
        raise NotImplementedError

    def poll_params(self) -> Optional[Tuple[int, np.ndarray]]:
        raise NotImplementedError

    def close(self) -> None:
        raise NotImplementedError


class ShmActorTransport(ActorTransport):
    kind = "shm"

    def __init__(self, ring: TrajectoryRing, lane: ParamLane, slots: Sequence[int]) -> None:
        self.ring = ring
        self.lane = lane
        self.slots = list(slots)
        self._cursor = 0
        self._cur: Optional[int] = None

    def try_begin_write(self) -> bool:
        for k in range(len(self.slots)):
            cand = self.slots[(self._cursor + k) % len(self.slots)]
            if self.ring.try_begin_write(cand):
                self._cursor = (self._cursor + k + 1) % len(self.slots)
                self._cur = cand
                return True
        return False

    def payload_view(self) -> np.ndarray:
        assert self._cur is not None, "payload_view before try_begin_write"
        return self.ring.payload_view(self._cur)

    def write_meta(self, **meta: int) -> None:
        assert self._cur is not None, "write_meta before try_begin_write"
        self.ring.write_meta(self._cur, **meta)

    def commit(self) -> None:
        assert self._cur is not None, "commit before try_begin_write"
        self.ring.commit(self._cur)
        self._cur = None

    def abort_torn(self) -> None:
        # nothing: the slot is left WRITING, which IS the shm torn state
        pass

    def param_version(self) -> int:
        return self.lane.version()

    def poll_params(self) -> Optional[Tuple[int, np.ndarray]]:
        return self.lane.poll()

    def close(self) -> None:
        self.ring.close()
        self.lane.close()


class TcpActorTransport(ActorTransport):
    kind = "tcp"

    def __init__(
        self,
        host: str,
        port: int,
        *,
        actor_id: int,
        generation: int,
        payload_bytes: int,
        param_nbytes: int,
        hb_interval_s: float = 0.5,
        connect_timeout_s: float = _HANDSHAKE_TIMEOUT_S,
    ) -> None:
        self.actor_id = int(actor_id)
        self.generation = int(generation)
        self.payload_bytes = int(payload_bytes)
        self.param_nbytes = int(param_nbytes)
        self.hb_interval_s = float(hb_interval_s)
        self.stats = net_stats(f"tcp.actor{self.actor_id}")
        self._scratch_hdr = np.zeros(HEADER_WORDS, dtype=np.int64)
        self._scratch_payload = np.zeros(self.payload_bytes, dtype=np.uint8)
        self._writing = False
        self._param: Optional[Tuple[int, np.ndarray]] = None
        self._last_hb = 0.0
        self._closed = False
        self.sock = socket.create_connection((host, int(port)), timeout=connect_timeout_s)
        self.sock.settimeout(_SEND_TIMEOUT_S)
        self._decoder = FrameDecoder()
        hello = {
            "role": f"actor{self.actor_id}",
            "actor_id": self.actor_id,
            "generation": self.generation,
            "t_wall": time.time(),
        }
        self._send(encode_frame(F_HELLO, json.dumps(hello).encode("utf-8")))
        ack = self._recv_frame_blocking(F_HELLO_ACK, connect_timeout_s)
        info = json.loads(ack.decode("utf-8"))
        self.credits = int(info.get("credits", 1))
        if int(info.get("payload_bytes", self.payload_bytes)) != self.payload_bytes:
            raise TransportError(
                f"slab layout disagreement: learner expects {info.get('payload_bytes')} "
                f"payload bytes, actor packed {self.payload_bytes}"
            )

    # ------------------------------------------------------------------ wire
    def _send(self, frame: bytes) -> None:
        try:
            self.sock.sendall(frame)
        except OSError as err:
            raise TransportError(f"learner link lost while sending: {err}") from err
        self.stats.frames_sent += 1
        self.stats.bytes_sent += len(frame)

    def _recv_frame_blocking(self, want_ftype: int, timeout_s: float) -> bytes:
        deadline = time.monotonic() + timeout_s
        while True:
            matched: Optional[bytes] = None
            for ftype, _flags, payload in self._drain(blocking=True, deadline=deadline):
                if ftype == want_ftype and matched is None:
                    matched = payload
                else:
                    # frames coalesced behind the match (e.g. the PARAM replay
                    # riding the HELLO_ACK) must not be dropped
                    self._handle(ftype, payload)
            if matched is not None:
                return matched
            if time.monotonic() >= deadline:
                raise TransportError(f"timed out waiting for frame type {want_ftype}")

    def _drain(self, *, blocking: bool = False, deadline: float = 0.0) -> List[Tuple[int, int, bytes]]:
        frames: List[Tuple[int, int, bytes]] = []
        while True:
            timeout = max(0.0, deadline - time.monotonic()) if blocking and not frames else 0.0
            try:
                readable, _, _ = select.select([self.sock], [], [], timeout)
            except (OSError, ValueError) as err:
                raise TransportError(f"learner link lost: {err}") from err
            if not readable:
                return frames
            try:
                data = self.sock.recv(_RECV_CHUNK)
            except (BlockingIOError, InterruptedError):
                return frames
            except OSError as err:
                raise TransportError(f"learner link lost: {err}") from err
            if not data:
                raise TransportError("learner closed the connection")
            self.stats.bytes_recv += len(data)
            try:
                frames += self._decoder.feed(data)
            except ProtocolError as err:
                raise TransportError(str(err)) from err
            if frames and blocking:
                return frames

    def _handle(self, ftype: int, payload: bytes) -> None:
        self.stats.frames_recv += 1
        if ftype == F_PARAM:
            version = int(np.frombuffer(payload, dtype=np.int64, count=1)[0])
            data = np.frombuffer(payload, dtype=np.uint8, offset=8)
            if data.shape[0] == self.param_nbytes and (
                self._param is None or version > self._param[0]
            ):
                self._param = (version, data.copy())
        elif ftype == F_SLAB_ACK:
            self.credits += 1
        elif ftype == F_BYE:
            raise TransportError("learner said bye")

    def _pump(self) -> None:
        for ftype, _flags, payload in self._drain():
            self._handle(ftype, payload)
        now = time.monotonic()
        if now - self._last_hb >= self.hb_interval_s:
            self._last_hb = now
            self._send(encode_frame(F_HEARTBEAT, np.int64(int(time.time() * 1e6)).tobytes()))

    # ------------------------------------------------------------------- api
    def try_begin_write(self) -> bool:
        self._pump()
        if self.credits <= 0:
            return False
        self._writing = True
        return True

    def payload_view(self) -> np.ndarray:
        assert self._writing, "payload_view before try_begin_write"
        return self._scratch_payload

    def write_meta(
        self,
        *,
        seq: int,
        param_version: int,
        actor_id: int,
        n_rows: int,
        collect_us: int,
        env_steps: int,
        trace_id: int = 0,
        commit_t_us: int = 0,
    ) -> None:
        assert self._writing, "write_meta before try_begin_write"
        hdr = self._scratch_hdr
        hdr[STATE] = COMMITTED  # the frame's arrival IS the commit word
        hdr[SEQ] = seq
        hdr[PARAM_VERSION] = param_version
        hdr[ACTOR_ID] = actor_id
        hdr[N_ROWS] = n_rows
        hdr[COLLECT_US] = collect_us
        hdr[ENV_STEPS] = env_steps
        hdr[TRACE_ID] = trace_id
        hdr[COMMIT_T_US] = commit_t_us
        hdr[CHECKSUM] = _checksum(hdr[SEQ:CHECKSUM])

    def _frame(self) -> bytes:
        return encode_frame(F_SLAB, self._scratch_hdr.tobytes() + self._scratch_payload.tobytes())

    def commit(self) -> None:
        assert self._writing, "commit before try_begin_write"
        self._send(self._frame())
        self.credits -= 1
        self._writing = False

    def abort_torn(self) -> None:
        """Ship HALF the slab frame and stop — the mid-frame peer death the
        learner must classify as torn. Only the crash drill calls this; the
        caller ``os._exit``\\ s immediately after."""
        frame = self._frame()
        try:
            self.sock.sendall(frame[: max(1, len(frame) // 2)])
        except OSError:
            pass

    def param_version(self) -> int:
        self._pump()
        return self._param[0] if self._param is not None else -1

    def poll_params(self) -> Optional[Tuple[int, np.ndarray]]:
        self._pump()
        return self._param

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        try:
            self.sock.sendall(encode_frame(F_BYE))
        except OSError:
            pass
        try:
            self.sock.close()
        except OSError:
            pass


# --------------------------------------------------------------------------
# factories
# --------------------------------------------------------------------------


def build_learner_transport(
    kind: str,
    *,
    payload_bytes: int,
    num_slots: int,
    slots_per_actor: int,
    param_nbytes: int,
    host: str = "127.0.0.1",
    port: int = 0,
) -> LearnerTransport:
    if kind == "shm":
        return ShmLearnerTransport(
            payload_bytes=payload_bytes, num_slots=num_slots, param_nbytes=param_nbytes
        )
    if kind == "tcp":
        return TcpLearnerTransport(
            payload_bytes=payload_bytes,
            num_slots=num_slots,
            slots_per_actor=slots_per_actor,
            param_nbytes=param_nbytes,
            host=host,
            port=port,
        )
    raise ValueError(f"unknown transport kind {kind!r} (want 'shm' or 'tcp')")


def attach_actor_transport(
    wire: Dict[str, Any], *, actor_id: int, generation: int, slots: Sequence[int]
) -> ActorTransport:
    """Actor-child factory from the blob's picklable wire dict."""
    kind = wire.get("kind", "shm")
    if kind == "shm":
        return ShmActorTransport(
            TrajectoryRing.attach(wire["ring"]), ParamLane.attach(wire["lane"]), slots
        )
    if kind == "tcp":
        return TcpActorTransport(
            wire["host"],
            wire["port"],
            actor_id=actor_id,
            generation=generation,
            payload_bytes=wire["payload_bytes"],
            param_nbytes=wire["param_nbytes"],
        )
    raise ValueError(f"unknown transport kind {kind!r} (want 'shm' or 'tcp')")


def _net_event(kind: str, **fields: Any) -> None:
    """Best-effort ``net_event`` telemetry emit (no-op untelemetered)."""
    try:
        from sheeprl_tpu.obs.telemetry import telemetry_net_event

        telemetry_net_event(kind, **fields)
    except Exception:
        pass
