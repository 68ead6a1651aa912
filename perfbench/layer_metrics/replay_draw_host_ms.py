"""Mean ``replay/draw`` span of the window: index draw on the host,
``device_put`` and the dispatch of the gather, once per batch."""

from perfbench import device_time


def read(run):
    return device_time.span_mean_ms(run, "replay/draw")
