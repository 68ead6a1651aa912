"""Device time of one ``seqpol_prefill`` execution (the prompts of up to
``prefill_rows`` rows that reset), from the ``XLA Modules`` line of the traced stretch."""

from perfbench import device_time


def read(run):
    return device_time.program_ms(device_time.of_run(run), "seqpol_prefill")
