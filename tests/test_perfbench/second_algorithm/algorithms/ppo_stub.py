"""The repository's own PPO (``exp=ppo``), as ``"reference": "ppo_stub"`` names
it: a stand-in that the benchmark's tests add to a copy, as new files only, to
prove that the harness takes an algorithm that is not Dreamer-V3. No cell of
``BENCHMARK.json`` names it, and it holds the program to less than a cell has
to: no seeded weights and no plain reference of the update, only that the rows
the first update trains on are what the environments produced and were handed.

Supplies every name README.md asks of an algorithm module."""

import contextlib

import numpy as np

from perfbench.bridge import check_stated  # noqa: F401  (the same rule: ``program_keys``, key by key)
from perfbench.correct import judge

#: the one program of the loop that the program names (the player's rollout step is a lambda)
programs = ("local_train",)
train_program = "local_train"
#: the update carries no ``jax.named_scope`` and the loop no leaf span: the two ``Time/*`` spans are all there is
scopes = ()
leaf_spans = ()


def model_flops(config):
    """One update by hand: every row of the rollout through encoder, actor and
    critic in each epoch, forward and backward (three times the forward's
    multiply-adds, two FLOPs each)."""
    m, a = config["model"], config["algo"]
    widths = [m["dense_units"]] * m["mlp_layers"]

    def mlp(fan_in, out):
        sizes = [fan_in, *widths, out]
        return sum(i * o for i, o in zip(sizes, sizes[1:]))

    per_row = mlp(m["state_dim"], m["features_dim"]) + mlp(m["features_dim"], config["env"]["action"]["dim"]) + mlp(m["features_dim"], 1)
    return 3 * 2 * a["rollout_steps"] * a["num_envs"] * a["update_epochs"] * per_row


class Capture:
    """What is kept of the program's run: how often it trained (``calls``,
    read by the harness), where (``placement``), the name of the jitted update
    and the first update's rows as the train function got them."""

    def __init__(self, cfg, seed):
        self.cfg, self.seed = cfg, seed
        self.calls = 0
        self.placement = {}
        self.program = None
        self.rollout = None


class _TrainFn:
    def __init__(self, fn, capture):
        self._fn, self._capture = fn, capture

    def __getattr__(self, name):
        return getattr(self._fn, name)

    def __call__(self, params, opt_state, data, *rest):
        import jax

        cap = self._capture
        cap.calls += 1
        if cap.rollout is None:
            cap.rollout = {k: np.array(v) for k, v in data.items()}  # a copy: the loop writes the next rollout into the same arrays
            cap.placement["train_device"] = sorted(d.platform for d in jax.tree.leaves(params)[0].devices())[0]
        return self._fn(params, opt_state, data, *rest)


@contextlib.contextmanager
def installed(capture):
    """While open, the loop of ``algos.ppo.ppo`` trains through the recording
    wrapper, and its composed configuration is held against the file's."""
    from sheeprl_tpu.algos.ppo import ppo as program

    real = program.make_train_fn

    def make_train_fn(fabric, agent, tx, cfg, *args, **kwargs):
        check_stated(capture.cfg, cfg)
        fn = real(fabric, agent, tx, cfg, *args, **kwargs)
        capture.program = getattr(fn, "__name__", None)
        return _TrainFn(fn, capture)

    program.make_train_fn = make_train_fn
    try:
        yield capture
    finally:
        program.make_train_fn = real


def produced(cfg, seed, index, handed):
    """What env ``index`` gave the loop, step by step, on the actions it was
    ``handed``: the observation each action was chosen on, the reward, the
    episode end. Made again from the seed, through the env's own factory."""
    import importlib

    module, _, name = cfg["env"]["make"].rpartition(".")
    env = getattr(importlib.import_module(module), name)(cfg["name"], cfg["env"], seed + index, index)
    obs, _ = env.reset()
    rows = []
    for action in handed:
        now, reward, terminated, truncated, _ = env.step(int(action))
        rows.append((obs["state"], reward, float(terminated or truncated)))
        obs = env.reset()[0] if terminated or truncated else now  # the vector env resets in the same step
    return rows


def rollout_rows(cfg, seed, rollout, stamps):
    """How many of the first update's ``rollout_steps x num_envs`` rows are not
    what the environments produced there (state, reward, end) and were handed
    (the action, as each env logged it)."""
    from perfbench.env import read_action_log

    steps, envs = cfg["algo"]["rollout_steps"], cfg["algo"]["num_envs"]
    got = {k: rollout[k].reshape(steps, envs, -1) for k in ("state", "rewards", "dones", "actions")}
    bad = 0
    for e in range(envs):
        handed = read_action_log(stamps, e, 1)[:steps, 0]
        for t, (state, reward, done) in enumerate(produced(cfg, seed, e, handed)):
            same = (np.array_equal(got["state"][t, e], state) and got["rewards"][t, e, 0] == np.float32(reward)
                    and got["dones"][t, e, 0] == done and int(np.argmax(got["actions"][t, e])) == int(handed[t]))  # fmt: skip
            bad += not same
    return bad


def verify(cfg, seed, capture, limits, stamps=None):
    """``(correct, compared, not_compared)``: the update ran as the program named
    in the tables, and every row it first trained on is the environments'."""
    numbers = {"updates_missing": float(capture.calls < 1), "program_renamed": float(capture.program != train_program)}
    if capture.rollout is not None and stamps is not None:
        numbers["rollout_rows"] = float(rollout_rows(cfg, seed, capture.rollout, stamps))
    compared = judge(numbers, limits)
    return all(v["ok"] for v in compared.values()), compared, {k: v for k, v in numbers.items() if k not in limits}
