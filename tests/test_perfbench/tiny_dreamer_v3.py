"""Dreamer-V3's rule for ``tiny.make_root``: a configuration of
``"reference": "dreamer_v3"`` at widths a CPU test can hold, the limits a sound
float32 run keeps there, and the program's own train step on seeded batches."""

from __future__ import annotations

import copy
import json
import os
from typing import Any, Dict

from perfbench import flops
from perfbench.loader import ROOT

TINY_MODEL = {"cnn_multiplier": 2, "dense_units": 16, "mlp_layers": 1, "recurrent_state_size": 16, "hidden_size": 16,
              "stochastic_size": 4, "discrete_size": 4}  # fmt: skip
TINY_ALGO = {"batch_size": 2, "sequence_length": 8, "horizon": 3, "learning_starts": 64}
TINY_BUFFER = 512


def tiny_config(name: str, precision: str = "fp32", root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "perfbench", "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    cfg = copy.deepcopy(cfg)
    cfg["model"].update(TINY_MODEL)
    cfg["algo"].update(TINY_ALGO)
    # float32 on the CPU: a sound run then agrees with the reference to 1e-5,
    # and every fault and every lower precision stands far above that
    cfg["algo"]["precision"] = precision
    cfg["buffer_size"] = TINY_BUFFER
    # as in the real files, the first episode ends just after the player has begun to act
    first = (TINY_ALGO["learning_starts"] // cfg["algo"]["num_envs"] + 3) * cfg["algo"]["action_repeat"]
    cfg["env"]["episode_frames"].update({"low": 6, "high": 12, "count": 3, "first": first})
    keys = cfg["program_keys"]
    cfg["overrides"] = [
        *cfg["overrides"],
        *[f"{keys['model.' + k]}={v}" for k, v in TINY_MODEL.items()],
        f"algo.world_model.representation_model.hidden_size={TINY_MODEL['hidden_size']}",
        *[f"{keys['algo.' + k]}={v}" for k, v in TINY_ALGO.items()],
        f"buffer.size={TINY_BUFFER}",
        f"fabric.precision={precision}",
        "fabric.accelerator=cpu",
        "fabric.devices=1",  # the test session has eight virtual CPU devices; a one-chip cell sees one
        "env.sync_env=True",
    ]
    cfg["model_flops_per_grad_step"] = flops.per_gradient_step(cfg)["total"]
    return cfg


#: a sound float32 run at these widths reads about 1e-5 in every number of the train step
TRAIN_LIMITS = {name: 1e-3 for name in ("wm_loss", "policy_loss", "value_loss", "first_grad", "first_grad_wm", "first_grad_actor",
                                        "first_grad_critic", "grad_direction", "change")}  # fmt: skip
#: and in the player's; what the ring gives back is compared exactly
TINY_LIMITS = {**TRAIN_LIMITS, "player_h": 1e-3, "player_z": 1e-3, "player_action": 1e-3, "ring_rows": 0}
#: what ``tiny.make_root`` writes into a cell of this algorithm, and the names a cell's own limits may use
LIMITS = TINY_LIMITS


def seeded_batch(cfg: Dict[str, Any], rng) -> Dict[str, Any]:
    """One ``[T, B]`` batch in the replay's layout, rows all different."""
    import numpy as np

    from perfbench.references.dreamer_v3 import sizes

    s, a = sizes(cfg), cfg["algo"]
    T, B = a["sequence_length"], a["batch_size"]
    if s["continuous"]:
        actions = rng.normal(size=(T, B, s["act_dim"])).astype(np.float32)
    else:
        actions = np.eye(s["act_dim"], dtype=np.float32)[rng.integers(0, s["act_dim"], (T, B))]
    batch = {
        "rgb": rng.integers(0, 256, (T, B, *cfg["env"]["frame"]), dtype=np.uint8),
        "actions": actions,
        "rewards": rng.choice([0.0, 1.0, -0.1], (T, B, 1)).astype(np.float32),
        "terminated": (rng.random((T, B, 1)) < 0.05).astype(np.float32),
        "truncated": np.zeros((T, B, 1), np.float32),
        "is_first": (rng.random((T, B, 1)) < 0.1).astype(np.float32),
    }
    for key in cfg["model"]["mlp_inputs"]:
        batch[key] = batch["rewards"].copy()
    return batch


def program_steps(cfg: Dict[str, Any], precision: str, seed: int = 3, steps: int = 3):
    """The program's own jitted train step, built as its ``main`` builds it,
    from the benchmark's weights, on seeded batches; returns the capture."""
    import gymnasium as gym
    import jax
    import numpy as np

    from perfbench import bridge
    from perfbench.references.dreamer_v3 import sizes
    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as program
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.ops.math import init_moments
    from sheeprl_tpu.ops.optim import build_tx
    from sheeprl_tpu.parallel.fabric import Fabric
    from sheeprl_tpu.utils.utils import dotdict

    composed = dotdict(
        compose(
            "config",
            [*cfg["overrides"], "env=dummy", "env.id=dummy_continuous",
             *[f"env.{k}={v}" for k, v in cfg["env_overrides"].items()], f"fabric.precision={precision}"],
        )  # fmt: skip
    )
    fabric = Fabric(devices=1, precision=precision, accelerator="cpu")
    s = sizes(cfg)
    spaces = {"rgb": gym.spaces.Box(0, 255, tuple(cfg["env"]["frame"]), np.uint8)}
    for key, dim in cfg["model"]["mlp_inputs"].items():
        spaces[key] = gym.spaces.Box(-np.inf, np.inf, (dim,), np.float32)
    capture = bridge.Capture(cfg, seed)
    rng = np.random.default_rng(seed)
    actions_dim = (s["act_dim"],)
    with bridge.installed(capture):
        wm, wm_p, actor, actor_p, critic, critic_p, target_p, _ = program.build_agent(
            fabric, actions_dim, s["continuous"], composed, gym.spaces.Dict(spaces)
        )
        txs = [build_tx(composed.algo[n].optimizer, composed.algo[n].clip_gradients) for n in ("world_model", "actor", "critic")]
        opts = [tx.init(p) for tx, p in zip(txs, (wm_p, actor_p, critic_p))]
        train = program.make_train_fn(fabric, wm, actor, critic, *txs, composed, s["continuous"], actions_dim)
        moments, key = init_moments(), jax.random.PRNGKey(seed)
        for i in range(steps):
            tau = 1.0 if i == 0 else float(composed.algo.critic.tau)
            target_p = jax.tree.map(lambda c, t: tau * c + (1 - tau) * t, critic_p, target_p)
            key, step_key = jax.random.split(key)
            wm_p, actor_p, critic_p, *opts, moments, _ = train(
                wm_p, actor_p, critic_p, target_p, *opts, moments, jax.device_put(seeded_batch(cfg, rng)), step_key
            )
    return capture
