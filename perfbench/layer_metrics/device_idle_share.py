"""1 - the union of device-op intervals over the traced stretch at the end of
the window, from the profiler's trace."""


def read(run):
    busy, window = run.trace.get("busy_s"), run.trace.get("window_s")
    if busy is None or not window:
        return None
    return 100.0 * (1.0 - busy / window)
