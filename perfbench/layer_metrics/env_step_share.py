"""Share of the window spent inside env 0's ``step()``, from the benchmark's
own timestamps."""


def read(run):
    return 100.0 * run.window["env_step_share"]
