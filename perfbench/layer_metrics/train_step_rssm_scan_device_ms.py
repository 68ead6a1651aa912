"""Device self time per train step under ``dv3/wm/rssm_scan`` alone, forward
and backward: the scan ROADMAP A3's levers are judged on."""

from perfbench import device_time


def read(run):
    return device_time.scope_ms(device_time.of_run(run), ("dv3/wm/rssm_scan",))
