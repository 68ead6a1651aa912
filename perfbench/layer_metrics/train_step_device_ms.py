"""Device time of one execution of the algorithm's train program
(``dv3_train_step`` for Dreamer-V3): the train step alone, without the gather,
the ring write and the host (``train_step.ms_per_grad_step`` holds all of those)."""

from perfbench import device_time


def read(run):
    return device_time.train_ms(run)
