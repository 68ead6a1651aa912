"""The stand-in's rule for ``tiny.make_root``: its configuration is tiny as it
stands, so only what a CPU test run needs is added to its overrides."""

import json
import os

from perfbench.loader import ROOT

#: exact, all three
LIMITS = {"rollout_rows": 0, "updates_missing": 0, "program_renamed": 0}
#: three rollouts of 8 steps: the update and what follows it have compiled by then
WARM_STEPS = 24


def tiny_config(name, precision="fp32", root=ROOT):
    with open(os.path.join(root, "perfbench", "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    cfg["algo"]["precision"] = precision
    cfg["overrides"] = [*cfg["overrides"], f"fabric.precision={precision}", "fabric.accelerator=cpu", "fabric.devices=1", "env.sync_env=True"]
    return cfg
