"""Device self time per train step under the three ``.../optimizer`` scopes."""

from perfbench import device_time


def read(run):
    return device_time.scope_ms(device_time.of_run(run), device_time.OPTIMIZER)
