"""The window-and-full-attention token policy's rule for ``tiny.make_root``: a
configuration of ``"reference": "token_ppo_mellum2"`` at widths a CPU test can
hold (the same ratios: two sliding-window layers and a full-attention layer
under the published YaRN table, 2 query heads a key-value head, every layer
over 8 routed experts of which 2 a token by a softmax and 4 held, none shared,
embedding and head untied; a window of 8 and prompts of 6 to 12 tokens, so
that half of the prompts wrap the ring inside the prefill), and the limits a
sound float32 run keeps there."""

from __future__ import annotations

import copy
import json
import os
from typing import Any, Dict

from perfbench.algorithms import token_ppo_mellum2
from perfbench.loader import ROOT

TINY_MODEL = {"hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8, "sliding_window": 8,
              "layer_types": ["sliding_attention", "sliding_attention", "full_attention"], "moe_intermediate_size": 16, "n_routed_experts": 8,
              "held_experts": [0, 1, 2, 3], "num_experts_per_tok": 2, "num_hidden_layers": 3, "vocab_rows": 24, "context": 24, "prompt_max": 12}  # fmt: skip
#: rollouts of 12 steps (the player's 12 recorded forwards lie before the first update), sequences of 12 + 12 slots
TINY_ALGO = {"num_envs": 4, "rollout_steps": 12, "sequence_length": 24, "batch_size": 4, "prefill_rows": 2}
TINY_ENV = {"vocab_rows": 24, "prompt": {"low": 6, "high": 12}, "response": {"median": 5, "sigma": 0.6, "low": 2, "high": 8, "first": 3},
            "action": {"type": "discrete", "dim": 24}}  # fmt: skip
#: three rollouts: reset, prefill and two updates have compiled by then
WARM_STEPS = 36


def tiny_config(name: str, precision: str = "fp32", root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "perfbench", "configs", f"{name}.json")) as f:
        cfg = copy.deepcopy(json.load(f))
    cfg["model"].update(TINY_MODEL)
    cfg["algo"].update(TINY_ALGO)
    cfg["env"].update(TINY_ENV)
    # float32 on the CPU: a sound run then agrees with the reference to 1e-5,
    # and every fault and every lower precision stands far above that
    cfg["algo"]["precision"] = precision
    keys = cfg["program_keys"]
    as_word = lambda v: "[" + ",".join(str(x) for x in v) + "]" if isinstance(v, list) else v  # noqa: E731
    cfg["overrides"] = [
        *cfg["overrides"],
        *[f"{keys['model.' + k]}={as_word(v)}" for k, v in TINY_MODEL.items()],
        *[f"{keys['algo.' + k]}={v}" for k, v in TINY_ALGO.items()],
        f"fabric.precision={precision}",
        "fabric.accelerator=cpu",
        "fabric.devices=1",  # the test session has eight virtual CPU devices; a one-chip cell sees one
    ]
    cfg["model_flops_per_grad_step"] = token_ppo_mellum2.model_flops(cfg)
    return cfg


#: a sound float32 run at these widths reads 1e-5 or under in every number; ``change`` magnifies the gradient's
#: round-off by 1 / (|g| + eps) and reads 1e-4; the rollout's rows and the counts are compared exactly
LIMITS = {**{name: 1e-3 for name in ("policy_loss", "value_loss", "entropy_loss", "first_grad", "grad_direction", "gae", "player_logits",
                                     "player_values", "player_reset_logits", "player_window_logits")},
          "change": 1e-2, "rollout_rows": 0, "program_renamed": 0, "steps_missing": 0, "forwards_missing": 0, "resets_missing": 0,
          "wraps_missing": 0}  # fmt: skip
