"""Mean ``train/dispatch`` span of the window: the host's time to enqueue one
train program, the runtime's allocation of its result buffers included."""

from perfbench import device_time


def read(run):
    return device_time.span_mean_ms(run, "train/dispatch")
