"""Device self time per train step under the three ``.../optimizer`` scopes."""

from perfbench import device_time
from perfbench.algorithms import dreamer_v3


def read(run):
    return device_time.scope_ms(device_time.of_run(run), dreamer_v3.OPTIMIZER)
