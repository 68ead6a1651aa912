"""Mean ``ring/add`` span of the window: staging copy, ``device_put`` and the
dispatch of ``ring_write``, on the host."""

from perfbench import device_time


def read(run):
    return device_time.span_mean_ms(run, "ring/add")
