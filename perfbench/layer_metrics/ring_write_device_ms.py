"""Device time of one ``ring_write`` execution (the replay ring's write, one a
vector step), from the ``XLA Modules`` line of the traced stretch."""

from perfbench import device_time


def read(run):
    return device_time.program_ms(device_time.of_run(run), "ring_write")
