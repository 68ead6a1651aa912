"""A forked child must not inherit the preemption watcher's behaviour.

gymnasium's AsyncVectorEnv forks its workers after the train loop installed
the SIGTERM/SIGINT watcher. Nothing polls the flag in a worker, so an
inherited handler swallowed the SIGTERM that ``multiprocessing`` sends its
daemon children at interpreter exit — and the parent's ``join`` then waited
forever: any crash after the envs existed hung the process (found in PR 21's
bring-up; on the chip machine that is a hung chip).
"""

import os
import signal
import time

from sheeprl_tpu.resilience.preemption import PreemptionWatcher


def test_forked_child_dies_on_sigterm_and_the_parent_still_drains():
    watcher = PreemptionWatcher().install()
    try:
        pid = os.fork()
        if pid == 0:  # the worker: would sleep forever if the signal were swallowed
            time.sleep(60)
            os._exit(0)
        time.sleep(0.2)
        os.kill(pid, signal.SIGTERM)
        deadline = time.monotonic() + 10.0
        status = None
        while time.monotonic() < deadline:
            done, status = os.waitpid(pid, os.WNOHANG)
            if done:
                break
            time.sleep(0.05)
        else:
            os.kill(pid, signal.SIGKILL)
            os.waitpid(pid, 0)
            raise AssertionError("the forked child swallowed SIGTERM")
        assert os.WIFSIGNALED(status) and os.WTERMSIG(status) == signal.SIGTERM
        # in the process that installed it, the same signal is still a drain request
        assert not watcher.requested
        os.kill(os.getpid(), signal.SIGTERM)
        assert watcher.requested and watcher.signum == signal.SIGTERM
    finally:
        watcher.uninstall()
