"""Where the benchmark touches the program: the weights go in through
``build_agent``'s own state arguments, and the first gradient steps are read
at the train function that the loop calls.

The benchmark makes the weights from ``--seed`` (``references/``) and hands
them to the program in the program's tree, so both sides start from the same
numbers without the reference taking anything the program made. The object
that ``make_train_fn`` returns is wrapped once; the loop gets the wrapper, and
the same compiled step serves the first three calls, which are recorded, and
every later one, which are timed.

This file is the only place that knows the program's parameter tree, the
order of its train function's arguments and the names of its losses.
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict, List, Tuple

import numpy as np

Path = Tuple[Any, ...]
TREES = ("wm", "actor", "critic")
#: steps of the program that the reference follows
FOLLOWED = 3
#: forwards of the player, from its first on, that are made again on the seeded weights
PLAYER_FORWARDS = 8
ADAM_B1 = 0.9


# --------------------------------------------------------------------------- #
# the reference's tree <-> the program's tree
# --------------------------------------------------------------------------- #


def _ln(i: int) -> Tuple[str, str]:
    return (f"LayerNorm_{i}", "LayerNorm_0")


def _trunk_pairs(ref: Path, prog: Path, layers: int) -> List[Tuple[Path, Path]]:
    out = []
    for i in range(layers):
        out += [
            ((*ref, i, "w"), (*prog, f"Dense_{i}", "kernel")),
            ((*ref, i, "b"), (*prog, f"Dense_{i}", "bias")),
            ((*ref, i, "ln_s"), (*prog, *_ln(i), "scale")),
            ((*ref, i, "ln_b"), (*prog, *_ln(i), "bias")),
        ]
    return out


def _head_pairs(ref: Path, prog: Path, layers: int, trunk: str, out: str) -> List[Tuple[Path, Path]]:
    return _trunk_pairs((*ref, "trunk"), (*prog, trunk), layers) + [
        ((*ref, "out", "w"), (*prog, out, "kernel")),
        ((*ref, "out", "b"), (*prog, out, "bias")),
    ]


def pairs(cfg: Dict[str, Any]) -> Dict[str, List[Tuple[Path, Path]]]:
    """For each of the three trees, ``(path in the reference, path in the
    program)`` of every leaf."""
    from perfbench.references.dreamer_v3 import sizes

    m, s = cfg["model"], sizes(cfg)
    layers, stages = m["mlp_layers"], s["stages"]
    wm: List[Tuple[Path, Path]] = []
    for i in range(stages):
        wm += [
            (("enc_cnn", i, "w"), ("cnn_encoder", f"Conv_{i}", "kernel")),
            (("enc_cnn", i, "ln_s"), ("cnn_encoder", *_ln(i), "scale")),
            (("enc_cnn", i, "ln_b"), ("cnn_encoder", *_ln(i), "bias")),
        ]
    if s["mlp_in"]:
        wm += _trunk_pairs(("enc_mlp",), ("mlp_encoder", "_LNMLP_0"), layers)
        wm += _trunk_pairs(("dec_mlp_trunk",), ("mlp_decoder", "_LNMLP_0"), layers)
    wm += [
        (("dec", "fc", "w"), ("cnn_decoder", "Dense_0", "kernel")),
        (("dec", "fc", "b"), ("cnn_decoder", "Dense_0", "bias")),
    ]
    for i in range(stages - 1):
        wm += [
            (("dec", "convs", i, "w"), ("cnn_decoder", f"ConvTranspose_{i}", "kernel")),
            (("dec", "convs", i, "ln_s"), ("cnn_decoder", *_ln(i), "scale")),
            (("dec", "convs", i, "ln_b"), ("cnn_decoder", *_ln(i), "bias")),
        ]
    wm += [
        (("dec", "out", "w"), ("cnn_decoder", f"ConvTranspose_{stages - 1}", "kernel")),
        (("dec", "out", "b"), ("cnn_decoder", f"ConvTranspose_{stages - 1}", "bias")),
        (("rec", "fc", "w"), ("recurrent_model", "Dense_0", "kernel")),
        (("rec", "fc", "b"), ("recurrent_model", "Dense_0", "bias")),
        (("rec", "fc", "ln_s"), ("recurrent_model", *_ln(0), "scale")),
        (("rec", "fc", "ln_b"), ("recurrent_model", *_ln(0), "bias")),
        (("rec", "gru", "w"), ("recurrent_model", "LayerNormGRUCell_0", "Dense_0", "kernel")),
        (("rec", "gru", "ln_s"), ("recurrent_model", "LayerNormGRUCell_0", *_ln(0), "scale")),
        (("rec", "gru", "ln_b"), ("recurrent_model", "LayerNormGRUCell_0", *_ln(0), "bias")),
        (("h0",), ("initial_recurrent_state",)),
    ]
    wm += _head_pairs(("trans",), ("transition_model",), 1, "layers_0", "layers_1")
    wm += _head_pairs(("repr",), ("representation_model",), 1, "layers_0", "layers_1")
    wm += _head_pairs(("reward",), ("reward_model",), layers, "layers_0", "layers_1")
    wm += _head_pairs(("cont",), ("continue_model",), layers, "layers_0", "layers_1")
    return {
        "wm": wm,
        "actor": _head_pairs((), (), layers, "_LNMLP_0", "head_0"),
        "critic": _head_pairs((), (), layers, "_LNMLP_0", "Dense_0"),
    }


def _get(tree: Any, path: Path) -> Any:
    for key in path:
        tree = tree[key]
    return tree


def to_program(ref_tree: Any, leaf_pairs: List[Tuple[Path, Path]]) -> Dict[str, Any]:
    """The reference's weights in the program's (flax) tree."""
    out: Dict[str, Any] = {}
    for ref_path, prog_path in leaf_pairs:
        node = out
        for key in prog_path[:-1]:
            node = node.setdefault(key, {})
        node[prog_path[-1]] = _get(ref_tree, ref_path)
    return {"params": out}


def name_of(path: Path) -> str:
    return "/".join(str(k) for k in path)


def from_program(prog_tree: Any, leaf_pairs: List[Tuple[Path, Path]]) -> Dict[str, Any]:
    """``{reference leaf name: the program's leaf}``; a leaf the program
    lacks is a ``KeyError``, one it has besides is reported by the caller."""
    return {name_of(ref_path): _get(prog_tree["params"], prog_path) for ref_path, prog_path in leaf_pairs}


def flat(ref_tree: Any, leaf_pairs: List[Tuple[Path, Path]]) -> Dict[str, Any]:
    return {name_of(ref_path): _get(ref_tree, ref_path) for ref_path, _ in leaf_pairs}


def adam_mu(opt_state: Any) -> Any:
    """The first-moment tree inside an optax chain's state."""
    if hasattr(opt_state, "mu"):
        return opt_state.mu
    if isinstance(opt_state, (tuple, list)):
        for part in opt_state:
            found = adam_mu(part)
            if found is not None:
                return found
    return None


# --------------------------------------------------------------------------- #
# reading the program's first steps
# --------------------------------------------------------------------------- #


class Capture:
    """What the program's first :data:`FOLLOWED` gradient steps were fed and
    what they gave. Everything is brought to the host at once, so nothing of
    it stays on the device through the window."""

    def __init__(self, cfg: Dict[str, Any], seed: int) -> None:
        self.cfg, self.seed = cfg, seed
        self.pairs = pairs(cfg)
        self.batches: List[Dict[str, np.ndarray]] = []
        self.keys: List[np.ndarray] = []
        self.losses: List[Dict[str, float]] = []
        self.first_grad_norms: Dict[str, Dict[str, float]] = {}
        self.first_grad_samples: Dict[str, Dict[str, np.ndarray]] = {}
        self.params_after: Dict[str, Dict[str, np.ndarray]] = {}
        #: the seeded weights in the reference's trees, on the host: what the program was handed
        self.seeded: Any = None
        #: observation and key of the player's first forwards, with its compiled step and the state before the first
        self.player_calls: List[Dict[str, Any]] = []
        self.player_step: Any = None
        #: what that step gives on the seeded weights (``replay_player``)
        self.player: List[Dict[str, Any]] = []
        self.calls = 0
        #: where the program put its replay and its player: read, never set
        self.placement: Dict[str, Any] = {}

    @property
    def complete(self) -> bool:
        return len(self.losses) == FOLLOWED and bool(self.params_after)

    def replay_player(self) -> None:
        """Call the player's compiled step once more for each recorded forward:
        on the seeded weights, with that forward's observation and key and the
        initial state. Both sides then hold the same weights, which in the loop,
        after a gradient step, they no longer do. Made after the window, so the
        window and the peak of memory hold nothing of it."""
        if self.player or not self.player_calls:
            return
        import jax

        step, state, device = self.player_step
        weights = jax.device_put([to_program(self.seeded[i], self.pairs[n]) for i, n in ((0, "wm"), (1, "actor"))], device)
        for call in self.player_calls:
            action, h, z = jax.device_get(step(*weights, call["obs"], *state, call["key"], False))
            # ``h``, ``z``: the latent the action was sampled at
            self.player.append({**call, "action": np.asarray(action), "h": np.asarray(h), "z": np.asarray(z)})
        self.player_step = None


class _TrainFn:
    """The loop's train function: the program's own jitted step, with the
    first calls recorded around it."""

    def __init__(self, fn: Any, capture: Capture) -> None:
        self._fn, self._capture = fn, capture

    def __getattr__(self, name: str) -> Any:
        return getattr(self._fn, name)

    def __call__(self, *args: Any) -> Any:
        cap = self._capture
        cap.calls += 1
        if len(cap.losses) >= FOLLOWED:
            return self._fn(*args)
        import jax

        from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import METRIC_ORDER

        cap.batches.append({k: np.asarray(v) for k, v in jax.device_get(args[8]).items()})
        cap.keys.append(np.asarray(jax.device_get(args[9])))
        out = self._fn(*args)
        metrics = np.asarray(jax.device_get(out[7]), np.float64)
        cap.losses.append(
            {
                "world_model": float(metrics[METRIC_ORDER.index("Loss/world_model_loss")]),
                "policy": float(metrics[METRIC_ORDER.index("Loss/policy_loss")]),
                "value": float(metrics[METRIC_ORDER.index("Loss/value_loss")]),
            }
        )
        if len(cap.losses) == 1:
            for name, opt in zip(TREES, out[3:6]):
                mu = from_program(adam_mu(opt), cap.pairs[name])
                norms, samples = jax.device_get(jax.jit(lambda t: (_leaf_norms(t), _leaf_samples(t)))(mu))
                cap.first_grad_norms[name] = {k: float(v) / (1.0 - ADAM_B1) for k, v in norms.items()}
                cap.first_grad_samples[name] = {k: np.asarray(v) for k, v in samples.items()}
        if len(cap.losses) == FOLLOWED:
            for name, params in zip(TREES, out[0:3]):
                leaves = from_program(params, cap.pairs[name])
                cap.params_after[name] = {k: np.asarray(v) for k, v in jax.device_get(leaves).items()}
        return out


def _leaf_norms(leaves: Dict[str, Any]) -> Dict[str, Any]:
    import jax.numpy as jnp

    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) for k, v in leaves.items()}


#: entries of each leaf that the direction of the first gradient is read on
SAMPLE = 1 << 16


def _leaf_samples(leaves: Dict[str, Any]) -> Dict[str, Any]:
    """At most :data:`SAMPLE` entries of each leaf, evenly spaced, in float32."""
    import jax.numpy as jnp

    return {k: v.reshape(-1)[:: max(1, v.size // SAMPLE)][:SAMPLE].astype(jnp.float32) for k, v in leaves.items()}


def check_stated(stated: Dict[str, Any], composed: Any) -> None:
    """The program's composed configuration has to say what the
    configuration's file says, key by key."""
    wrong = []
    for ours, theirs in stated["program_keys"].items():
        want: Any = stated
        for part in ours.split("."):
            want = want[part]
        got: Any = composed
        for part in theirs.split("."):
            got = got[part]
        same = abs(float(got) - float(want)) <= 1e-12 * max(1.0, abs(float(want))) if isinstance(want, (int, float)) else got == want
        if not same:
            wrong.append(f"{theirs}={got!r} but {ours}={want!r}")
    if wrong:
        raise SystemExit("perfbench: the program's configuration departs from the configuration's file: " + "; ".join(wrong))


@contextlib.contextmanager
def installed(capture: Capture):
    """While open, the Dreamer-V3 entry point builds its agent from the
    benchmark's weights and trains through the recording wrapper."""
    import jax

    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as program

    from perfbench.references import dreamer_v3 as reference

    real_build, real_make, real_replay = program.build_agent, program.make_train_fn, program.make_sequential_replay

    def build_agent(fabric, actions_dim, is_continuous, cfg, obs_space, *states):
        if any(s is not None for s in states):
            raise RuntimeError("perfbench: the benchmark does not resume a checkpoint")
        check_stated(capture.cfg, cfg)
        seeded = reference.seeded_params(capture.cfg, capture.seed)
        capture.seeded = jax.device_get(seeded)
        trees = [to_program(t, capture.pairs[n]) for n, t in zip(TREES, seeded)]
        target = jax.tree.map(lambda x: x.copy(), trees[2])
        built = real_build(fabric, actions_dim, is_continuous, cfg, obs_space, *trees, target)
        player = built[-1]
        # ``None`` is the program's word for "with the learner, on the default device"
        where = player.device if player.device is not None else jax.devices()[0]
        capture.placement["player_device"] = where.platform
        _watch_player(player, capture)
        return built

    def make_sequential_replay(*args, **kwargs):
        rb = real_replay(*args, **kwargs)
        on_device = isinstance(rb, program.DeviceReplayBuffer)
        capture.placement["buffer_device"] = "device" if on_device else "host"
        capture.placement["replay"] = rb.devices() if on_device else ["host"]
        return rb

    def make_train_fn(*args, **kwargs):
        return _TrainFn(real_make(*args, **kwargs), capture)

    program.build_agent, program.make_train_fn = build_agent, make_train_fn
    program.make_sequential_replay = make_sequential_replay
    try:
        yield capture
    finally:
        program.build_agent, program.make_train_fn = real_build, real_make
        program.make_sequential_replay = real_replay


def _watch_player(player: Any, capture: Capture) -> None:
    """Record the observation and the key of the player's first
    :data:`PLAYER_FORWARDS` forwards, and keep its compiled step with the
    state it starts from, for :meth:`Capture.replay_player`."""
    import jax

    real = player.get_actions

    def get_actions(obs, key, *args, **kwargs):
        if len(capture.player_calls) < PLAYER_FORWARDS:
            if not capture.player_calls:
                capture.player_step = (player._step, (player.h, player.z, player.actions), player.device)
            capture.player_calls.append({"obs": {k: np.asarray(v) for k, v in obs.items()}, "key": np.asarray(jax.device_get(key))})
        return real(obs, key, *args, **kwargs)

    player.get_actions = get_actions
