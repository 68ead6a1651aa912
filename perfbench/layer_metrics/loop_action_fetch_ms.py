"""Mean ``player/get_actions`` span of the window: the player's dispatch and the
``device_get`` of the action, the turn's one sync."""

from perfbench import device_time


def read(run):
    return device_time.span_mean_ms(run, "player/get_actions")
