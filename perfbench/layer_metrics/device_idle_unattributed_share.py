"""Share of the traced stretch's idle time that lies under none of the
algorithm's leaf spans (seven for Dreamer-V3) nor env 0's ``step()``."""

from perfbench import device_time


def read(run):
    reduced = device_time.of_run(run)
    if not reduced or not reduced["idle_s"] or not reduced["leaf_spans"]:
        return None
    return 100.0 * reduced["idle_unattributed_s"] / reduced["idle_s"]
