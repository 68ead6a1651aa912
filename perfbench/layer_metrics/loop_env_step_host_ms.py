"""Mean ``env/step`` span of the window: the vector step as the loop sees it,
pipes to the env workers included."""

from perfbench import device_time


def read(run):
    return device_time.span_mean_ms(run, "env/step")
