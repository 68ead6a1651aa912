"""Measured torch baseline for the PPO benchmark workload.

The reference framework cannot run in this image (lightning/hydra are not
installed), so this standalone torch script reproduces the COMPUTE of the
reference's PPO benchmark (benchmarks/benchmark.py:11-18 +
configs/exp/ppo_benchmarks.yaml: CartPole-v1, vector obs, CPU) at the same
workload shape bench.py drives through the CLI: 64 sync envs, rollout 128,
10 update epochs over 512-sample minibatches, the default 2x64 MLP encoder
with actor/critic heads, GAE(0.99, 0.95), clip 0.2, vf 1.0.

Run: ``python benchmarks/ppo_torch_baseline.py [total_steps]`` — prints
env-steps/sec. bench.py holds the number measured with it (``_PPO_TORCH_CPU_SPS``,
with the command and date) and uses it as the PPO ``vs_baseline``.
"""

from __future__ import annotations

import sys
import time

import gymnasium as gym
import numpy as np
import torch
import torch.nn as nn

NUM_ENVS = 64
ROLLOUT = 128
BATCH = 512
EPOCHS = 10
DENSE = 64
FEATURES = 64
GAMMA, LAMBDA = 0.99, 0.95
CLIP, VF = 0.2, 1.0
LR = 1e-3


class Agent(nn.Module):
    def __init__(self, obs_dim: int, n_act: int) -> None:
        super().__init__()
        self.encoder = nn.Sequential(
            nn.Linear(obs_dim, DENSE), nn.Tanh(), nn.Linear(DENSE, FEATURES), nn.Tanh()
        )
        self.pi = nn.Linear(FEATURES, n_act)
        self.v = nn.Linear(FEATURES, 1)

    def forward(self, obs: torch.Tensor):
        feat = self.encoder(obs)
        return self.pi(feat), self.v(feat)


def main(total_steps: int) -> None:
    torch.manual_seed(0)
    envs = gym.vector.SyncVectorEnv(
        [lambda: gym.make("CartPole-v1") for _ in range(NUM_ENVS)]
    )
    obs_dim = int(np.prod(envs.single_observation_space.shape))
    n_act = int(envs.single_action_space.n)
    agent = Agent(obs_dim, n_act)
    opt = torch.optim.Adam(agent.parameters(), lr=LR)

    obs, _ = envs.reset(seed=0)
    steps = 0
    start = time.perf_counter()
    while steps < total_steps:
        rollout = {k: [] for k in ("obs", "act", "logp", "val", "rew", "done")}
        for _ in range(ROLLOUT):
            with torch.no_grad():
                logits, value = agent(torch.as_tensor(obs, dtype=torch.float32))
                dist = torch.distributions.Categorical(logits=logits)
                action = dist.sample()
                logp = dist.log_prob(action)
            nxt, rew, term, trunc, _ = envs.step(action.numpy())
            rollout["obs"].append(obs.astype(np.float32))
            rollout["act"].append(action.numpy())
            rollout["logp"].append(logp.numpy())
            rollout["val"].append(value[:, 0].numpy())
            rollout["rew"].append(np.asarray(rew, np.float32))
            rollout["done"].append(np.logical_or(term, trunc).astype(np.float32))
            obs = nxt
            steps += NUM_ENVS

        with torch.no_grad():
            _, last_v = agent(torch.as_tensor(obs, dtype=torch.float32))
        vals = np.stack(rollout["val"] + [last_v[:, 0].numpy()])
        rews, dones = np.stack(rollout["rew"]), np.stack(rollout["done"])
        adv = np.zeros_like(rews)
        carry = 0.0
        for t in reversed(range(ROLLOUT)):
            mask = 1.0 - dones[t]
            delta = rews[t] + GAMMA * vals[t + 1] * mask - vals[t]
            carry = delta + GAMMA * LAMBDA * mask * carry
            adv[t] = carry
        ret = adv + vals[:-1]

        flat = {
            "obs": torch.as_tensor(np.concatenate(rollout["obs"])),
            "act": torch.as_tensor(np.concatenate(rollout["act"])),
            "logp": torch.as_tensor(np.concatenate(rollout["logp"])),
            "adv": torch.as_tensor(adv.reshape(-1)),
            "ret": torch.as_tensor(ret.reshape(-1)),
        }
        n = flat["obs"].shape[0]
        for _ in range(EPOCHS):
            perm = torch.randperm(n)
            for i in range(0, n, BATCH):
                rows = perm[i : i + BATCH]
                logits, value = agent(flat["obs"][rows])
                dist = torch.distributions.Categorical(logits=logits)
                ratio = torch.exp(dist.log_prob(flat["act"][rows]) - flat["logp"][rows])
                a = flat["adv"][rows]
                pg = -torch.min(
                    ratio * a, torch.clamp(ratio, 1 - CLIP, 1 + CLIP) * a
                ).mean()
                vloss = ((value[:, 0] - flat["ret"][rows]) ** 2).mean()
                loss = pg + VF * vloss - 0.0 * dist.entropy().mean()
                opt.zero_grad()
                loss.backward()
                opt.step()

    sps = steps / (time.perf_counter() - start)
    print(f"{sps:.2f} env-steps/sec over {steps} steps")


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 32768)
