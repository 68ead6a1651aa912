"""Dreamer-V3, as ``"reference": "dreamer_v3"`` in a configuration's file
names it: everything the harness and its tests ask of an algorithm (README.md,
"What an algorithm module supplies"), taken from ``bridge.py``, ``correct.py``
and ``flops.py``, with the names the program gives its jitted functions, the
train step's scopes and the loop's leaf spans (howto/telemetry.md)."""

from perfbench import flops
from perfbench.bridge import Capture, check_stated, installed  # noqa: F401
from perfbench.correct import verify  # noqa: F401


def model_flops(config):
    """What ``model_flops_per_grad_step`` in a configuration's file is held against."""
    return flops.per_gradient_step(config)["total"]


#: the programs the loop dispatches
programs = ("ring_write", "ring_amend", "ring_gather_sequences", "dv3_train_step", "dv3_player_step", "dv3_player_reset", "dv3_target_ema")
train_program = "dv3_train_step"
#: the scopes inside the train step, and the groups the per-scope readers sum
scopes = ("dv3/wm/encode", "dv3/wm/rssm_scan", "dv3/wm/decode", "dv3/wm/optimizer", "dv3/behaviour/imagine",
          "dv3/behaviour/actor_loss", "dv3/behaviour/optimizer", "dv3/critic/loss", "dv3/critic/optimizer")  # fmt: skip
WORLD_MODEL = ("dv3/wm/encode", "dv3/wm/rssm_scan", "dv3/wm/decode")
BEHAVIOUR = ("dv3/behaviour/imagine", "dv3/behaviour/actor_loss", "dv3/critic/loss")
OPTIMIZER = ("dv3/wm/optimizer", "dv3/behaviour/optimizer", "dv3/critic/optimizer")
#: the host's leaf spans, nested in the loop's two window spans
leaf_spans = ("player/get_actions", "ring/add", "env/step", "loop/store_step", "replay/draw", "train/dispatch", "train/block")
