"""Device self time per train step under ``dv3/wm/encode``, ``dv3/wm/rssm_scan``
and ``dv3/wm/decode``, forward and backward."""

from perfbench import device_time
from perfbench.algorithms import dreamer_v3


def read(run):
    return device_time.scope_ms(device_time.of_run(run), dreamer_v3.WORLD_MODEL)
