"""Preemption watcher: turn SIGTERM/SIGINT into a graceful drain.

TPU maintenance events and spot evictions arrive as SIGTERM with a short
grace window. The signal handler does the minimum legal thing — set a flag
and note the time — and the training loop polls :meth:`should_preempt` at
its iteration boundary. On multi-host runs the poll is a host-object-plane
collective (any rank's signal preempts every rank), so all processes enter
the emergency-save collective together instead of deadlocking half-in.

A run that exits because of preemption uses :data:`PREEMPTED_EXIT_CODE` so
supervisors (k8s restart policies, bash drills) can tell "evicted after a
clean emergency checkpoint" from success (0) and from crashes (everything
else). A second SIGINT while draining restores the default KeyboardInterrupt
behaviour — Ctrl-C twice still means "stop NOW".
"""

from __future__ import annotations

import os
import signal
import threading
import time
from typing import Optional

# distinct from 0 (success), 1 (crash) and 130 (SIGINT default): preempted
# after a committed emergency checkpoint — safe to reschedule with
# checkpoint.resume_from=auto
PREEMPTED_EXIT_CODE = 77

_SIGNALS = (signal.SIGTERM, signal.SIGINT)


class PreemptionWatcher:
    def __init__(self) -> None:
        self._requested = False
        self.signum: Optional[int] = None
        self.signal_time: Optional[float] = None
        self._old_handlers: dict = {}
        self._owner_pid: Optional[int] = None
        self.installed = False

    def install(self) -> "PreemptionWatcher":
        """Install the handlers. A no-op off the main thread (Python only
        allows signal handlers there) so helper threads can share the code."""
        if self.installed or threading.current_thread() is not threading.main_thread():
            return self
        self._owner_pid = os.getpid()
        for sig in _SIGNALS:
            self._old_handlers[sig] = signal.signal(sig, self._handle)
        self.installed = True
        return self

    def uninstall(self) -> None:
        if not self.installed:
            return
        for sig, old in self._old_handlers.items():
            try:
                signal.signal(sig, old)
            except (ValueError, OSError):
                pass
        self._old_handlers.clear()
        self.installed = False

    def _handle(self, signum, frame) -> None:
        if os.getpid() != self._owner_pid:
            # a forked child (gymnasium's AsyncVectorEnv workers fork after
            # the train loop installed this) inherited the handler. Nothing
            # polls the flag there, so swallowing the signal would make the
            # parent's terminate()+join() of its workers wait forever — the
            # child dies as it would have without the watcher.
            signal.signal(signum, signal.SIG_DFL)
            os.kill(os.getpid(), signum)
            return
        if self._requested and signum == signal.SIGINT:
            # second Ctrl-C: the user wants out immediately
            self.uninstall()
            raise KeyboardInterrupt
        self._requested = True
        self.signum = signum
        self.signal_time = time.time()

    @property
    def requested(self) -> bool:
        return self._requested

    def should_preempt(self, num_processes: int = 1) -> bool:
        """Poll at the train-loop boundary. With multiple processes this is a
        COLLECTIVE — every rank must call it at the same point — so that one
        rank's SIGTERM sends all ranks into the emergency save together."""
        if num_processes > 1:
            from sheeprl_tpu.parallel.collectives import all_gather_object

            return any(all_gather_object(bool(self._requested)))
        return self._requested
