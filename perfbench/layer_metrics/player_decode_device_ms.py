"""Device time of one ``seqpol_decode`` execution (one token for every row through
the latent cache, a vector step), from the ``XLA Modules`` line of the traced stretch."""

from perfbench import device_time


def read(run):
    return device_time.program_ms(device_time.of_run(run), "seqpol_decode")
