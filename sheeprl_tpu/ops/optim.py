"""Optimizer factories (reference: torch.optim via hydra, configs/optim/*).

Thin optax builders so configs can say ``_target_: sheeprl_tpu.ops.optim.adam``
with torch-style arguments. Gradient clipping composes in front (the
reference's ``fabric.clip_gradients`` becomes part of the update chain), and
``schedule`` may replace the scalar lr (anneal_lr).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import optax


def _lr(lr: float, schedule: Optional[Any]) -> Any:
    return schedule if schedule is not None else lr


def build_tx(opt_cfg: Any, clip: Optional[float] = None) -> optax.GradientTransformation:
    """Optimizer from its config group (``_target_`` instantiate), with the
    algo's ``clip_gradients`` folded into the update chain — the one
    construction every training loop (and the standalone MFU probe) shares."""
    from sheeprl_tpu.config.compose import instantiate

    opt_cfg = dict(opt_cfg.to_dict() if hasattr(opt_cfg, "to_dict") else opt_cfg)
    if clip and float(clip) > 0:
        opt_cfg["max_grad_norm"] = float(clip)
    return instantiate(opt_cfg)


def adam(
    lr: float = 1e-3,
    betas: Sequence[float] = (0.9, 0.999),
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    max_grad_norm: float = 0.0,
    schedule: Optional[Any] = None,
) -> optax.GradientTransformation:
    b1, b2 = betas
    opt = (
        optax.adamw(_lr(lr, schedule), b1=b1, b2=b2, eps=eps, weight_decay=weight_decay)
        if weight_decay
        else optax.adam(_lr(lr, schedule), b1=b1, b2=b2, eps=eps)
    )
    if max_grad_norm and max_grad_norm > 0:
        return optax.chain(optax.clip_by_global_norm(max_grad_norm), opt)
    return opt


def sgd(
    lr: float = 1e-2,
    momentum: float = 0.0,
    weight_decay: float = 0.0,
    nesterov: bool = False,
    max_grad_norm: float = 0.0,
    schedule: Optional[Any] = None,
) -> optax.GradientTransformation:
    opt = optax.sgd(_lr(lr, schedule), momentum=momentum or None, nesterov=nesterov)
    if weight_decay:
        opt = optax.chain(optax.add_decayed_weights(weight_decay), opt)
    if max_grad_norm and max_grad_norm > 0:
        return optax.chain(optax.clip_by_global_norm(max_grad_norm), opt)
    return opt


def rmsprop_tf(
    lr: float = 1e-3,
    alpha: float = 0.9,
    eps: float = 1e-8,
    momentum: float = 0.0,
    centered: bool = False,
    weight_decay: float = 0.0,
    max_grad_norm: float = 0.0,
    schedule: Optional[Any] = None,
) -> optax.GradientTransformation:
    """TF-style RMSProp with epsilon inside the sqrt (reference
    optim/rmsprop_tf.py:14-156) — optax's rmsprop already follows the TF
    convention (eps_in_sqrt=True default in optax.scale_by_rms)."""
    opt = optax.rmsprop(
        _lr(lr, schedule), decay=alpha, eps=eps, centered=centered, momentum=momentum or None
    )
    if weight_decay:
        opt = optax.chain(optax.add_decayed_weights(weight_decay), opt)
    if max_grad_norm and max_grad_norm > 0:
        return optax.chain(optax.clip_by_global_norm(max_grad_norm), opt)
    return opt


def rmsprop(
    lr: float = 1e-3,
    alpha: float = 0.99,
    eps: float = 1e-8,
    momentum: float = 0.0,
    centered: bool = False,
    weight_decay: float = 0.0,
    max_grad_norm: float = 0.0,
    schedule: Optional[Any] = None,
) -> optax.GradientTransformation:
    """torch.optim.RMSprop-style (epsilon outside the sqrt)."""
    opt = optax.rmsprop(
        _lr(lr, schedule), decay=alpha, eps=eps, centered=centered, momentum=momentum or None,
        eps_in_sqrt=False,
    )
    if weight_decay:
        opt = optax.chain(optax.add_decayed_weights(weight_decay), opt)
    if max_grad_norm and max_grad_norm > 0:
        return optax.chain(optax.clip_by_global_norm(max_grad_norm), opt)
    return opt
