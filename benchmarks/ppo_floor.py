"""Measure the irreducible env-stepping floor of the PPO bench workload:
what does bare ``gym.vector`` CartPole stepping cost on this host, with zero
learning on top?

Stages, each timed over ``--steps`` env steps (env-steps/s):

1. ``random``: SyncVectorEnv.step with ``action_space.sample()`` — the pure
   gym floor, no policy at all.
2. ``noop-policy``: adds the host-side numpy work PPO's player cannot avoid
   (obs dict assembly + a trivially cheap deterministic action) — isolates
   vector-env cost from policy cost.
3. ``policy``: the real PPOPlayer forward (jitted MLP on the player device)
   — the full interaction path minus buffers and training.
4. ``bookkeeping``: stage 3 plus everything the collection window does
   except the train dispatch — preallocated rollout-array writes, the
   per-window GAE pass — so the stage-3→4 drop IS the host-loop
   bookkeeping cost that ``algo.fused_rollout`` removes.

The gap between stage 4 and the full bench number is the train dispatch
plus loop glue.

Usage: python benchmarks/ppo_floor.py [--steps 32768] [--envs 64]
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


def make_envs(n):
    import gymnasium as gym

    return gym.vector.SyncVectorEnv([lambda: gym.make("CartPole-v1") for _ in range(n)])


def stage_random(envs, steps):
    n = envs.num_envs
    envs.reset(seed=0)
    # deterministic action stream: repeated floor runs measure the same
    # episode-length mix, so run-to-run deltas are timing, not luck
    envs.action_space.seed(0)
    t0 = time.perf_counter()
    for _ in range(steps // n):
        envs.step(envs.action_space.sample())
    return steps / (time.perf_counter() - t0)


def stage_noop_policy(envs, steps):
    n = envs.num_envs
    obs, _ = envs.reset(seed=0)
    actions = np.zeros((n,), np.int64)
    t0 = time.perf_counter()
    for _ in range(steps // n):
        # the cheapest possible "policy": a numpy reduction over the obs
        actions[:] = (np.asarray(obs).sum(-1) > 0).astype(np.int64)
        obs, *_ = envs.step(actions)
    return steps / (time.perf_counter() - t0)


def _build_player(envs):
    import gymnasium as gym

    from sheeprl_tpu.algos.ppo.agent import PPOPlayer, build_agent
    from sheeprl_tpu.config.compose import compose
    from sheeprl_tpu.parallel.fabric import Fabric, resolve_player_device

    cfg = compose("config", ["exp=ppo", "env.num_envs=64", "algo.mlp_keys.encoder=[state]"])
    fabric = Fabric(devices=1, precision=str(cfg.fabric.get("precision", "fp32")))
    obs_space = gym.spaces.Dict({"state": envs.single_observation_space})
    agent, params = build_agent(fabric, (int(envs.single_action_space.n),), False, cfg, obs_space)
    player = PPOPlayer(agent, params, device=resolve_player_device(cfg.algo.get("player_device", "auto")))
    return player


def stage_player(envs, steps):
    import jax

    from sheeprl_tpu.parallel.fabric import put_tree

    player = _build_player(envs)
    n = envs.num_envs
    obs, _ = envs.reset(seed=0)
    # the key lives on the player's device and steps fold a counter in-graph
    # — the exact per-step pattern of the training loop (ppo.py rollout)
    key = put_tree(jax.random.PRNGKey(0), player.device)
    player.rollout_actions({"state": np.asarray(obs, np.float32)}, key, 0)  # warm the jit
    t0 = time.perf_counter()
    for c in range(steps // n):
        out = player.rollout_actions({"state": np.asarray(obs, np.float32)}, key, c)
        _actions, real_actions, _lp, _v = jax.device_get(out)
        obs, *_ = envs.step(real_actions[..., 0].reshape(-1))
    return steps / (time.perf_counter() - t0)


def stage_bookkeeping(envs, steps, rollout_steps=128):
    import functools

    import jax

    from sheeprl_tpu.ops.math import gae
    from sheeprl_tpu.parallel.fabric import put_tree
    from sheeprl_tpu.utils.prealloc import RolloutStore

    player = _build_player(envs)
    n = envs.num_envs
    obs, _ = envs.reset(seed=0)
    key = put_tree(jax.random.PRNGKey(0), player.device)
    gae_fn = jax.jit(functools.partial(gae, gamma=0.99, gae_lambda=0.95))
    store = RolloutStore(rollout_steps)
    player.rollout_actions({"state": np.asarray(obs, np.float32)}, key, 0)  # warm the jit
    windows = max(1, steps // (n * rollout_steps))
    c = 0
    t0 = time.perf_counter()
    for w in range(windows):
        buf = store.begin(w)
        for t in range(rollout_steps):
            c += 1
            state = np.asarray(obs, np.float32)
            out = player.rollout_actions({"state": state}, key, c)
            actions, real_actions, logprobs, values = jax.device_get(out)
            obs, rewards, terminated, truncated, _ = envs.step(real_actions[..., 0].reshape(-1))
            buf.put(
                t,
                {
                    "state": state,
                    "dones": np.logical_or(terminated, truncated).reshape(n, 1).astype(np.float32),
                    "values": values,
                    "actions": actions,
                    "logprobs": logprobs,
                    "rewards": np.asarray(rewards, np.float32).reshape(n, 1),
                },
            )
        data = buf.arrays()
        next_values = np.asarray(player.get_values({"state": np.asarray(obs, np.float32)}))
        returns, advantages = gae_fn(
            put_tree(data["rewards"], player.device),
            put_tree(data["values"], player.device),
            put_tree(data["dones"], player.device),
            put_tree(next_values, player.device),
        )
        data["returns"] = np.asarray(returns)
        data["advantages"] = np.asarray(advantages)
        # the minibatch views the train path would slice from
        _ = {k: v.reshape(v.shape[0] * v.shape[1], *v.shape[2:]) for k, v in data.items()}
    return windows * rollout_steps * n / (time.perf_counter() - t0)


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=32768)
    p.add_argument("--envs", type=int, default=64)
    args = p.parse_args()

    envs = make_envs(args.envs)
    rec = {"envs": args.envs, "steps": args.steps}
    rec["random_sps"] = round(stage_random(envs, args.steps), 1)
    rec["noop_policy_sps"] = round(stage_noop_policy(envs, args.steps), 1)
    try:
        rec["player_sps"] = round(stage_player(envs, args.steps), 1)
    except Exception as e:  # the player stage needs the full package import
        rec["player_error"] = repr(e)
    try:
        rec["bookkeeping_sps"] = round(stage_bookkeeping(envs, args.steps), 1)
    except Exception as e:
        rec["bookkeeping_error"] = repr(e)
    envs.close()
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
