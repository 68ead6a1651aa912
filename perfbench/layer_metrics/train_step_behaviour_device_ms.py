"""Device self time per train step under ``dv3/behaviour/imagine``,
``dv3/behaviour/actor_loss`` and ``dv3/critic/loss``, forward and backward."""

from perfbench import device_time


def read(run):
    return device_time.scope_ms(device_time.of_run(run), device_time.BEHAVIOUR)
