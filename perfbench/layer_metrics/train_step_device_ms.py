"""Device time of one ``dv3_train_step`` execution: the train step alone,
without the gather, the ring write and the host (``train_step.ms_per_grad_step``
holds all of those)."""

from perfbench import device_time


def read(run):
    return device_time.program_ms(device_time.of_run(run), device_time.TRAIN)
