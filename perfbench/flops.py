"""Model FLOPs per gradient step, from the configuration's shapes alone.

Multiply-adds of the matrix products and convolutions that the algorithm
needs, forward and backward, counted as 2 FLOPs each; nothing is read from a
compiled program and recomputation does not count. Elementwise work
(LayerNorm, activations, losses, the optimizer) is left out: against the
chip's matrix peak only matrix work is meaningful.

- World model, on ``T x B`` rows: encoder, RSSM step (input projection, GRU,
  prior, posterior), decoder, reward and continue heads: forward, and
  backward for inputs and for weights: 3 x forward.
- Imagination, on ``T x B`` start rows over ``horizon`` steps: actor forward
  on ``horizon + 1`` latents, ``horizon`` prior steps, then reward, critic
  and continue heads on ``horizon + 1`` latents. Backward: continuous
  actions carry the gradient through the dynamics and the reward and critic
  heads (inputs only: 1 x forward of those) into the actor (2 x its
  forward); discrete actions use the score function, so only the actor is
  differentiated (2 x its forward).
- Critic, on ``horizon`` latents: slow critic forward, critic forward and
  backward (3 x).
"""

from __future__ import annotations

from typing import Any, Dict

from perfbench.references.dreamer_v3 import sizes


def dense(rows: int, fan_in: int, fan_out: int) -> int:
    """Forward FLOPs of ``[rows, fan_in] @ [fan_in, fan_out]``."""
    return 2 * rows * fan_in * fan_out


def conv(rows: int, out_hw: int, kernel: int, cin: int, cout: int) -> int:
    """Forward FLOPs of a convolution producing ``out_hw x out_hw x cout``
    from ``kernel x kernel x cin`` patches (a transposed convolution of
    stride 2 reads a quarter of its taps as zeros: those are not work the
    algorithm needs, so it counts ``kernel**2 / 4`` taps per output)."""
    return 2 * rows * out_hw * out_hw * kernel * kernel * cin * cout


def mlp(rows: int, fan_in: int, units: int, layers: int, out: int = 0) -> int:
    total = sum(dense(rows, fan_in if i == 0 else units, units) for i in range(layers))
    return total + (dense(rows, units, out) if out else 0)


def parts(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Forward FLOPs per row of each network (rows = 1)."""
    m, s = cfg["model"], sizes(cfg)
    units, layers, mult, rec, hidden = (
        m["dense_units"], m["mlp_layers"], m["cnn_multiplier"], m["recurrent_state_size"], m["hidden_size"],
    )  # fmt: skip
    encoder, cin, hw = 0, m["image_channels"], m["image_size"]
    for i in range(s["stages"]):
        hw //= 2
        encoder += conv(1, hw, 4, cin, 2**i * mult)
        cin = 2**i * mult
    if s["mlp_in"]:
        encoder += mlp(1, s["mlp_in"], units, layers)
    decoder = dense(1, s["latent"], hw * hw * cin)
    for i in range(s["stages"]):
        cout = m["image_channels"] if i == s["stages"] - 1 else 2 ** (s["stages"] - 2 - i) * mult
        hw *= 2
        decoder += conv(1, hw, 4, cin, cout) // 4
        cin = cout
    recurrent = dense(1, s["stoch"] + s["act_dim"], units) + dense(1, rec + units, 3 * rec)
    prior = mlp(1, rec, hidden, 1, s["stoch"])
    posterior = mlp(1, rec + s["embed"], hidden, 1, s["stoch"])
    actor_out = 2 * s["act_dim"] if s["continuous"] else s["act_dim"]
    return {
        "encoder": encoder,
        "decoder": decoder,
        "recurrent": recurrent,
        "prior": prior,
        "posterior": posterior,
        "reward": mlp(1, s["latent"], units, layers, m["bins"]),
        "continue": mlp(1, s["latent"], units, layers, 1),
        "actor": mlp(1, s["latent"], units, layers, actor_out),
        "critic": mlp(1, s["latent"], units, layers, m["bins"]),
    }


def per_gradient_step(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Model FLOPs of one gradient step, split by phase."""
    p, s, a = parts(cfg), sizes(cfg), cfg["algo"]
    rows = a["sequence_length"] * a["batch_size"]
    horizon = a["horizon"]
    world = 3 * rows * sum(p[k] for k in ("encoder", "recurrent", "prior", "posterior", "decoder", "reward", "continue"))
    dynamics = horizon * (p["recurrent"] + p["prior"])
    heads = (horizon + 1) * (p["reward"] + p["critic"] + p["continue"])
    actor = (horizon + 1) * p["actor"]
    forward = rows * (dynamics + heads + actor)
    if s["continuous"]:
        backward = rows * (dynamics + (horizon + 1) * (p["reward"] + p["critic"]) + 2 * actor)
    else:
        backward = rows * 2 * actor
    critic = rows * horizon * (p["critic"] + 3 * p["critic"])
    return {"world_model": world, "imagination": forward + backward, "critic": critic, "total": world + forward + backward + critic}
