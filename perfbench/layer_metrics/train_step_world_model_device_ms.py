"""Device self time per train step under ``dv3/wm/encode``, ``dv3/wm/rssm_scan``
and ``dv3/wm/decode``, forward and backward."""

from perfbench import device_time


def read(run):
    return device_time.scope_ms(device_time.of_run(run), device_time.WORLD_MODEL)
