"""Device time of one ``dv3_player_step`` execution (the player's forward, one
a vector step), from the ``XLA Modules`` line of the traced stretch."""

from perfbench import device_time


def read(run):
    return device_time.program_ms(device_time.of_run(run), "dv3_player_step")
