"""Length-prefixed CRC-guarded frames: the byte-level contract of every TCP
link in the data plane.

Wire layout of one frame::

    offset  size  field
    ------  ----  -----------------------------------------------
    0       4     magic  b"SPNF"
    4       1     protocol version (PROTO_VERSION)
    5       1     frame type (one of the F_* constants)
    6       2     flags (reserved, little-endian u16)
    8       4     payload length, little-endian u32
    12      4     crc32 over version..length + payload
    16      N     payload

The decoder is an incremental state machine over a byte buffer, so it is
indifferent to how the kernel chops the stream (partial reads are the normal
case, not an error path). Failure classes:

- **short buffer** — not an error; bytes stay buffered until the rest lands.
- **corrupt payload** (magic + length intact, CRC mismatch) — the frame is
  *skipped in full* and counted; the declared length still frames the stream,
  so the next frame decodes cleanly. This is the frame-level analogue of the
  ring's "COMMITTED with a bad checksum → torn, never admitted".
- **corrupt preamble** (bad magic / absurd length / unknown version) — the
  stream has lost framing and cannot be resynchronized; :class:`ProtocolError`
  tells the endpoint to drop the connection (reconnect-with-generation-bump
  handles the rest).
- **EOF mid-frame** — :meth:`FrameDecoder.partial` names the half-received
  frame so slab transports can count it torn.
"""

from __future__ import annotations

import struct
import zlib
from typing import List, Optional, Tuple

MAGIC = b"SPNF"
PROTO_VERSION = 1

# frame types
F_HELLO = 1  # peer introduction: role, ids, generation, wall clock
F_HELLO_ACK = 2  # server reply: credits, clock echo for skew estimation
F_SLAB = 3  # 10-word slab header + SlabLayout payload (actor -> learner)
F_SLAB_ACK = 4  # credit return after the learner releases a slab
F_PARAM = 5  # u64 version + packed param bytes (learner -> actors)
F_HEARTBEAT = 6  # liveness beacon, u64 epoch-us payload
F_INFER = 7  # u64 batch id + pickled obs batch (fleet -> agent)
F_RESULT = 8  # u64 batch id + pickled outputs (agent -> fleet)
F_BYE = 9  # orderly close

_PREAMBLE = struct.Struct("<4sBBHII")
PREAMBLE_BYTES = _PREAMBLE.size  # 16
MAX_PAYLOAD_BYTES = 1 << 31  # anything larger is lost framing, not a frame


class ProtocolError(RuntimeError):
    """Unrecoverable stream corruption: drop the connection."""


def _crc(version: int, ftype: int, flags: int, length: int, payload: bytes) -> int:
    head = struct.pack("<BBHI", version, ftype, flags, length)
    return zlib.crc32(payload, zlib.crc32(head)) & 0xFFFFFFFF


def encode_frame(ftype: int, payload: bytes = b"", flags: int = 0) -> bytes:
    """One wire-ready frame."""
    length = len(payload)
    if length > MAX_PAYLOAD_BYTES:
        raise ValueError(f"frame payload of {length} bytes exceeds the {MAX_PAYLOAD_BYTES} cap")
    crc = _crc(PROTO_VERSION, ftype, flags, length, payload)
    return _PREAMBLE.pack(MAGIC, PROTO_VERSION, ftype, flags, length, crc) + payload


class FrameDecoder:
    """Incremental frame parser over an append-only byte buffer.

    ``feed(data)`` returns every complete frame newly decodable, in order, as
    ``(ftype, flags, payload)`` tuples. Corrupt-CRC frames are skipped (see
    module docstring) and tallied in :attr:`checksum_rejects`.
    """

    def __init__(self) -> None:
        self._buf = bytearray()
        self.checksum_rejects = 0

    def feed(self, data: bytes) -> List[Tuple[int, int, bytes]]:
        self._buf += data
        frames: List[Tuple[int, int, bytes]] = []
        while True:
            if len(self._buf) < PREAMBLE_BYTES:
                return frames
            magic, version, ftype, flags, length, crc = _PREAMBLE.unpack_from(self._buf)
            if magic != MAGIC:
                raise ProtocolError(f"bad frame magic {bytes(magic)!r}: stream lost framing")
            if version != PROTO_VERSION:
                raise ProtocolError(f"unknown frame protocol version {version}")
            if length > MAX_PAYLOAD_BYTES:
                raise ProtocolError(f"absurd frame length {length}: stream lost framing")
            end = PREAMBLE_BYTES + length
            if len(self._buf) < end:
                return frames
            payload = bytes(self._buf[PREAMBLE_BYTES:end])
            del self._buf[:end]
            if _crc(version, ftype, flags, length, payload) != crc:
                # the declared length still frames the stream: skip exactly
                # this frame, keep decoding the next one
                self.checksum_rejects += 1
                continue
            frames.append((ftype, flags, payload))

    def partial(self) -> Optional[Tuple[int, int, bytes]]:
        """The half-received frame left in the buffer at EOF, if any:
        ``(ftype, declared_length, payload_so_far)``. ``ftype`` is -1 when
        even the preamble is incomplete."""
        if not self._buf:
            return None
        if len(self._buf) < PREAMBLE_BYTES:
            return (-1, 0, b"")
        _, _, ftype, _, length, _ = _PREAMBLE.unpack_from(self._buf)
        return (ftype, length, bytes(self._buf[PREAMBLE_BYTES:]))

    @property
    def buffered(self) -> int:
        return len(self._buf)
