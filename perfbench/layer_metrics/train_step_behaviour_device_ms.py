"""Device self time per train step under ``dv3/behaviour/imagine``,
``dv3/behaviour/actor_loss`` and ``dv3/critic/loss``, forward and backward."""

from perfbench import device_time
from perfbench.algorithms import dreamer_v3


def read(run):
    return device_time.scope_ms(device_time.of_run(run), dreamer_v3.BEHAVIOUR)
