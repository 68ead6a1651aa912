"""Summed ``loop/head`` and ``loop/tail`` spans of the window per loop
iteration (a turn, or an update where the configuration names a cycle,
``algo.rollout_steps``): the host's part of a turn before its first window
span and after its last, the finite check's fetch, the log block and the
checkpoint check among it. Nothing where the program has no such spans."""

from perfbench import device_time


def read(run):
    found = [d for name in ("loop/head", "loop/tail") for _, d in device_time.spans(run, name)]
    if not found:
        return None
    iterations = run.window["vector_steps"] // int(run.cell.config["algo"].get("rollout_steps", 1))
    return 1e3 * sum(found) / iterations if iterations else None
