"""Measured torch baseline for the Dreamer-V3 benchmark workload.

The reference framework cannot run in this image (lightning/hydra are not
installed), so this standalone torch script reproduces the COMPUTE of the
reference's benchmark recipe (configs/exp/dreamer_v3_benchmarks.yaml:27-45 —
tiny nets: dense_units=8, discrete=4x4, cnn_channels_multiplier=2, 64x64
pixels, 1 env, replay_ratio 0.0625) with the same loop structure as
reference dreamer_v3.py: per-step player forward (encoder -> GRU ->
representation -> actor), buffer add, and a full train() gradient step
(Python RSSM loop over seq_len=64, imagination horizon 15, three optimizers)
every 16 policy steps. The env is a synthetic 64x64x3 pixel source so both
sides of the comparison step identical data.

Run: ``python benchmarks/dv3_torch_baseline.py [total_steps]`` — prints
env-steps/sec. bench.py holds the number measured with it (``_DV3_TORCH_CPU_SPS``,
with the command and date) and uses it as ``vs_baseline``.
"""

from __future__ import annotations

import sys
import time

import numpy as np
import torch
import torch.nn as nn
import torch.nn.functional as F

torch.set_num_threads(max(1, torch.get_num_threads()))

# tiny-net benchmark sizes (reference dreamer_v3_benchmarks.yaml)
DENSE = 8
STOCH, DISCRETE = 4, 4
RECURRENT = 8
CNN_MULT = 2
SEQ_LEN = 64
BATCH = 16
HORIZON = 15
REPLAY_RATIO = 0.5  # north-star walker-walk recipe (BASELINE.json)
ACTIONS = 6


class Encoder(nn.Module):
    def __init__(self):
        super().__init__()
        chans = [CNN_MULT, 2 * CNN_MULT, 4 * CNN_MULT, 8 * CNN_MULT]
        layers, in_ch = [], 3
        for c in chans:
            layers += [nn.Conv2d(in_ch, c, 4, 2, 1, bias=False), nn.SiLU()]
            in_ch = c
        self.conv = nn.Sequential(*layers)

    def forward(self, x):  # [B, 3, 64, 64]
        return self.conv(x).flatten(1)


class Decoder(nn.Module):
    def __init__(self, latent):
        super().__init__()
        self.fc = nn.Linear(latent, 8 * CNN_MULT * 4 * 4)
        chans = [4 * CNN_MULT, 2 * CNN_MULT, CNN_MULT]
        layers, in_ch = [], 8 * CNN_MULT
        for c in chans:
            layers += [nn.ConvTranspose2d(in_ch, c, 4, 2, 1, bias=False), nn.SiLU()]
            in_ch = c
        layers += [nn.ConvTranspose2d(in_ch, 3, 4, 2, 1)]
        self.deconv = nn.Sequential(*layers)

    def forward(self, z):
        x = self.fc(z).view(-1, 8 * CNN_MULT, 4, 4)
        return self.deconv(x)


class WorldModel(nn.Module):
    def __init__(self):
        super().__init__()
        stoch = STOCH * DISCRETE
        self.encoder = Encoder()
        emb = 8 * CNN_MULT * 4 * 4
        self.gru_in = nn.Linear(stoch + ACTIONS, DENSE)
        self.gru = nn.GRUCell(DENSE, RECURRENT)
        self.transition = nn.Sequential(nn.Linear(RECURRENT, DENSE), nn.SiLU(), nn.Linear(DENSE, stoch))
        self.representation = nn.Sequential(
            nn.Linear(RECURRENT + emb, DENSE), nn.SiLU(), nn.Linear(DENSE, stoch)
        )
        self.decoder = Decoder(stoch + RECURRENT)
        self.reward = nn.Sequential(nn.Linear(stoch + RECURRENT, DENSE), nn.SiLU(), nn.Linear(DENSE, 255))
        self.cont = nn.Sequential(nn.Linear(stoch + RECURRENT, DENSE), nn.SiLU(), nn.Linear(DENSE, 1))

    def sample_stoch(self, logits):
        logits = logits.view(*logits.shape[:-1], STOCH, DISCRETE)
        dist = torch.distributions.OneHotCategoricalStraightThrough(logits=logits)
        return dist.rsample().flatten(-2), logits

    def dynamic(self, z, h, a, emb):
        h = self.gru(F.silu(self.gru_in(torch.cat([z, a], -1))), h)
        prior_logits = self.transition(h)
        post, post_logits = self.sample_stoch(self.representation(torch.cat([h, emb], -1)))
        return h, post, post_logits, prior_logits.view(*prior_logits.shape[:-1], STOCH, DISCRETE)

    def imagine(self, z, h, a):
        h = self.gru(F.silu(self.gru_in(torch.cat([z, a], -1))), h)
        z, _ = self.sample_stoch(self.transition(h))
        return z, h


class Actor(nn.Module):
    def __init__(self):
        super().__init__()
        self.net = nn.Sequential(nn.Linear(STOCH * DISCRETE + RECURRENT, DENSE), nn.SiLU(), nn.Linear(DENSE, ACTIONS))

    def forward(self, latent):
        return self.net(latent)


def train_step(wm, actor, critic, opts, obs_seq, act_seq, rew_seq, cont_seq):
    B = obs_seq.shape[1]
    emb = wm.encoder(obs_seq.flatten(0, 1)).view(SEQ_LEN, B, -1)
    h = torch.zeros(B, RECURRENT)
    z = torch.zeros(B, STOCH * DISCRETE)
    hs, zs, post_l, prior_l = [], [], [], []
    for t in range(SEQ_LEN):  # the reference's Python RSSM loop
        h, z, pl, prl = wm.dynamic(z, h, act_seq[t], emb[t])
        hs.append(h), zs.append(z), post_l.append(pl), prior_l.append(prl)
    hs, zs = torch.stack(hs), torch.stack(zs)
    latents = torch.cat([zs, hs], -1)
    recon = wm.decoder(latents.flatten(0, 1)).view(SEQ_LEN, B, 3, 64, 64)
    rec_loss = F.mse_loss(recon, obs_seq)
    rew_loss = F.cross_entropy(wm.reward(latents).flatten(0, 1), torch.zeros(SEQ_LEN * B, dtype=torch.long))
    cont_loss = F.binary_cross_entropy_with_logits(wm.cont(latents), cont_seq)
    post_d = torch.distributions.OneHotCategorical(logits=torch.stack(post_l).view(SEQ_LEN, B, STOCH, DISCRETE))
    prior_d = torch.distributions.OneHotCategorical(logits=torch.stack(prior_l))
    kl = torch.distributions.kl_divergence(post_d, prior_d).mean()
    wm_loss = rec_loss + rew_loss + cont_loss + kl
    opts[0].zero_grad(set_to_none=True)
    wm_loss.backward()
    opts[0].step()

    # imagination (the reference's second Python loop)
    z = zs.detach().flatten(0, 1)
    h = hs.detach().flatten(0, 1)
    lats = []
    for _ in range(HORIZON):
        logits = actor(torch.cat([z, h], -1).detach())
        a = torch.distributions.OneHotCategoricalStraightThrough(logits=logits).rsample()
        z, h = wm.imagine(z, h, a)
        lats.append(torch.cat([z, h], -1))
    lats = torch.stack(lats)
    values = critic(lats)
    actor_loss = -values.mean()
    opts[1].zero_grad(set_to_none=True)
    actor_loss.backward(retain_graph=True)
    opts[1].step()
    critic_loss = F.mse_loss(critic(lats.detach()), values.detach())
    opts[2].zero_grad(set_to_none=True)
    critic_loss.backward()
    opts[2].step()


NUM_ENVS = 4  # north-star walker-walk recipe


def main(total_steps: int = 4096) -> float:
    torch.manual_seed(0)
    wm, actor = WorldModel(), Actor()
    critic = nn.Sequential(nn.Linear(STOCH * DISCRETE + RECURRENT, DENSE), nn.SiLU(), nn.Linear(DENSE, 1))
    opts = [
        torch.optim.Adam(wm.parameters(), 1e-4),
        torch.optim.Adam(actor.parameters(), 8e-5),
        torch.optim.Adam(critic.parameters(), 8e-5),
    ]
    rng = np.random.default_rng(0)
    buffer = np.zeros((16384, 3, 64, 64), np.uint8)
    pos = 0
    h = torch.zeros(NUM_ENVS, RECURRENT)
    z = torch.zeros(NUM_ENVS, STOCH * DISCRETE)
    prev_a = torch.zeros(NUM_ENVS, ACTIONS)

    start = time.perf_counter()
    grad_budget = 0.0
    for step in range(total_steps // NUM_ENVS):
        obs = rng.integers(0, 256, (NUM_ENVS, 3, 64, 64), dtype=np.uint8)  # synthetic env frames
        with torch.inference_mode():
            emb = wm.encoder(torch.as_tensor(obs, dtype=torch.float32) / 255.0 - 0.5)
            h2 = wm.gru(F.silu(wm.gru_in(torch.cat([z, prev_a], -1))), h)
            zl = wm.representation(torch.cat([h2, emb], -1)).view(-1, STOCH, DISCRETE)
            z2 = F.one_hot(zl.argmax(-1), DISCRETE).float().flatten(1)
            logits = actor(torch.cat([z2, h2], -1))
            a = torch.distributions.OneHotCategorical(logits=logits).sample()
        h, z, prev_a = h2.clone(), z2.clone(), a.clone()
        buffer[pos % len(buffer)] = obs[0]
        pos += 1

        grad_budget += REPLAY_RATIO * NUM_ENVS
        if grad_budget >= 1.0 and pos > SEQ_LEN + 1:
            grad_budget -= 1.0
            idx = rng.integers(0, max(1, min(pos, len(buffer)) - SEQ_LEN), BATCH)
            obs_seq = np.stack([buffer[i : i + SEQ_LEN] for i in idx], axis=1)
            obs_t = torch.as_tensor(obs_seq, dtype=torch.float32) / 255.0 - 0.5
            act_seq = torch.zeros(SEQ_LEN, BATCH, ACTIONS)
            rew_seq = torch.zeros(SEQ_LEN, BATCH, 1)
            cont_seq = torch.ones(SEQ_LEN, BATCH, 1)
            train_step(wm, actor, critic, opts, obs_t, act_seq, rew_seq, cont_seq)
    elapsed = time.perf_counter() - start
    sps = total_steps / elapsed
    print(f"torch DV3 benchmark baseline: {sps:.2f} env-steps/sec ({total_steps} steps, {elapsed:.1f}s)")
    return sps


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 4096)
