"""Preallocated rollout storage for the on-policy host loops.

The reference collection loop appends per-step numpy arrays to Python lists
and ``np.stack``s them at the end of the window — for small classic-control
obs the stack (one more full copy plus T*keys list traversals) is a visible
slice of the host loop's bookkeeping.  ``RolloutStore``
replaces it with arrays of shape ``[T, ...]`` allocated once on the first
window and written in place (``buf[k][t] = v`` — the write IS the copy, so
callers that used to ``.copy()`` values before appending can stop).

``slots=2`` double-buffers: with ``algo.overlap_collection`` the async train
dispatch may still be reading update N's arrays (jax can alias host numpy
zero-copy on the CPU backend) while the loop writes update N+1, so successive
updates alternate buffers.
"""

from __future__ import annotations

from typing import Dict, Mapping

import numpy as np


class RolloutBuffer:
    """One window's storage: per-key ``[length, ...]`` arrays, lazily
    allocated from the first written value's shape/dtype, then reused."""

    def __init__(self, length: int):
        self._length = int(length)
        self._arrays: Dict[str, np.ndarray] = {}

    def put(self, t: int, values: Mapping[str, np.ndarray]) -> None:
        """Write one step's values at index ``t`` (in-place copy)."""
        for k, v in values.items():
            arr = self._arrays.get(k)
            if arr is None:
                v = np.asarray(v)
                arr = np.empty((self._length,) + v.shape, dtype=v.dtype)
                self._arrays[k] = arr
            arr[t] = v

    def arrays(self) -> Dict[str, np.ndarray]:
        """The ``[T, ...]`` arrays (the live buffers, not copies)."""
        return dict(self._arrays)


class RolloutStore:
    """A rotating set of :class:`RolloutBuffer` slots, one window each."""

    def __init__(self, length: int, slots: int = 1):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        self._buffers = [RolloutBuffer(length) for _ in range(slots)]

    def begin(self, update: int) -> RolloutBuffer:
        """The buffer for this update's window (rotates across slots)."""
        return self._buffers[update % len(self._buffers)]
