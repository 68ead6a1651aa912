"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip, read after
the window and before the reference runs."""


def read(run):
    if run.peak_bytes is None:
        return None
    return run.peak_bytes / 2**30
