"""Summed ``train/block`` spans of the window per loop turn: how long the host
waits in the ``block_until_ready`` that the timers impose."""

from perfbench import device_time


def read(run):
    found = device_time.spans(run, "train/block")
    if not found:
        return None
    return 1e3 * sum(d for _, d in found) / run.window["vector_steps"]
