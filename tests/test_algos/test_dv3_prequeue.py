"""The Dreamer-V3 loop queues the next turn's player forward behind the train
steps before it waits for them (``dreamer_v3.main``, ``PlayerDV3.get_actions``
``fetch=False``). One tiny run with a checkpoint every second update and a NaN
injected at update 5 is recorded call by call; the tests read the record."""

import os

import gymnasium as gym
import jax
import numpy as np
import pytest

from tests.test_algos.test_dreamer_v3 import dv3_args

UPDATES = 8
LEARNING_STARTS = 2  # updates
FAULT_AT = 5
ENVS = 2


def tiny_player():
    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.parallel.fabric import Fabric
    from sheeprl_tpu.utils.utils import dotdict
    from tests.test_algos.test_dv3_trace_names import TINY

    cfg = dotdict(compose("config", TINY))
    obs_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (16, 16, 3), np.uint8)})
    fabric = Fabric(devices=1, precision="fp32", accelerator="cpu")
    return build_agent(fabric, (3,), True, cfg, obs_space)[-1]


def test_an_unfetched_forward_is_the_fetched_one():
    player = tiny_player()
    obs = {"rgb": np.full((ENVS, 16, 16, 3), 7, np.uint8)}
    key = jax.random.PRNGKey(3)
    player.init_states()
    start = (player.h, player.z, player.actions)
    fetched = player.get_actions(obs, key)
    after = jax.device_get((player.h, player.z, player.actions))
    assert isinstance(fetched, np.ndarray)

    player.h, player.z, player.actions = start
    queued = player.get_actions(obs, key, fetch=False)
    assert isinstance(queued, jax.Array)
    np.testing.assert_array_equal(np.asarray(queued), fetched)
    for got, want in zip(jax.device_get((player.h, player.z, player.actions)), after):
        np.testing.assert_array_equal(got, want)
    assert player.actions is queued  # the next forward's previous action stays on the device


@pytest.fixture(scope="module")
def record(tmp_path_factory):
    """The run's calls in order, its forwards, train keys, env actions,
    counters and checkpoints."""
    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as program
    from sheeprl_tpu.cli import run
    from sheeprl_tpu.resilience import RunResilience, committed_checkpoints
    from sheeprl_tpu.utils.checkpoint import load_checkpoint

    tmp_path = tmp_path_factory.mktemp("prequeue")
    rec = {"calls": [], "forwards": [], "train_keys": {}, "env_actions": {}, "counters": [], "step": None}
    turn = {"update": 0, "stepped": False}
    real = {n: getattr(program, n) for n in ("build_agent", "make_train_fn", "build_vector_env", "telemetry_advance", "telemetry_counters")}

    def next_acting_turn():
        return turn["update"] + turn["stepped"]

    def build_agent(*args, **kwargs):
        built = real["build_agent"](*args, **kwargs)
        player = built[-1]
        rec["step"] = step = player._step
        get_actions = player.get_actions

        def _step(*a, **k):
            rec["calls"].append(("dv3_player_step", next_acting_turn()))
            return step(*a, **k)

        # on the instance and with (obs, key) first, as the benchmark's bridge watches it
        def watched(obs, key, *a, **k):
            before = jax.device_get((player.h, player.z, player.actions))
            action = get_actions(obs, key, *a, **k)
            rec["forwards"].append(
                {
                    "turn": next_acting_turn(),
                    "obs": {n: np.asarray(v) for n, v in obs.items()},
                    "key": np.asarray(jax.device_get(key)),
                    "before": before,
                    "kwargs": k,
                    "action": np.asarray(action),
                }
            )
            return action

        player._step, player.get_actions = _step, watched
        return built

    def make_train_fn(*args, **kwargs):
        fn = real["make_train_fn"](*args, **kwargs)

        def train(*a):
            assert len(a) == 10
            rec["calls"].append(("train", turn["update"]))
            rec["train_keys"].setdefault(turn["update"], []).append(np.asarray(jax.device_get(a[9])))
            out = fn(*a)
            assert len(out) == 8
            return out

        train.lower = fn.lower  # ``telemetry_register_flops`` reads the shapes through it
        return train

    def build_vector_env(*args, **kwargs):
        envs = real["build_vector_env"](*args, **kwargs)
        step = envs.step

        def watched(actions):
            rec["env_actions"][turn["update"]] = np.array(actions)
            turn["stepped"] = True
            return step(actions)

        envs.step = watched
        return envs

    def telemetry_advance(policy_step):
        turn["update"] += 1
        turn["stepped"] = False
        return real["telemetry_advance"](policy_step)

    def telemetry_counters(name, **fields):
        rec["counters"].append((name, fields))
        return real["telemetry_counters"](name, **fields)

    block, check_finite = jax.block_until_ready, RunResilience.check_finite

    def block_until_ready(x):
        rec["calls"].append(("train/block", turn["update"]))
        return block(x)

    def watched_check(self, metrics, update):
        rec["calls"].append(("finite_check", turn["update"]))
        return check_finite(self, metrics, update)

    args = [a for a in dv3_args(tmp_path, "dummy_continuous") if not a.startswith(("dry_run", "algo.learning_starts", "buffer.size", "algo.run_test"))]
    args += [
        "dry_run=False",
        f"algo.total_steps={UPDATES * ENVS}",
        f"algo.learning_starts={LEARNING_STARTS * ENVS}",
        "algo.run_test=False",
        "buffer.size=64",
        "env.sync_env=True",
        f"checkpoint.every={2 * ENVS}",
        "checkpoint.keep_last=10",
        "checkpoint.async_save=False",
        "fabric.devices=1",
        "fabric.accelerator=cpu",
        "resilience.fault_injection.enabled=True",
        f"resilience.fault_injection.faults=[{{kind: nan, at_update: {FAULT_AT}}}]",
        "run_name=prequeue",
    ]
    with pytest.MonkeyPatch.context() as patch:
        patch.chdir(tmp_path)
        for name, fn in (("build_agent", build_agent), ("make_train_fn", make_train_fn), ("build_vector_env", build_vector_env), ("telemetry_advance", telemetry_advance), ("telemetry_counters", telemetry_counters)):
            patch.setattr(program, name, fn)
        patch.setattr(jax, "block_until_ready", block_until_ready)
        patch.setattr(RunResilience, "check_finite", watched_check)
        run(args)

    ckpt_dirs = [os.path.join(root, d) for root, dirs, _ in os.walk(tmp_path) for d in dirs if d == "checkpoint"]
    rec["checkpoints"] = {}
    for ckpt in committed_checkpoints(ckpt_dirs[0]):
        state = load_checkpoint(ckpt.path)
        rec["checkpoints"][int(state["update"])] = state
    return rec


def trained_turns(rec):
    return sorted({turn for name, turn in rec["calls"] if name == "train"})


def test_the_host_waits_only_after_the_next_forward_is_queued(record):
    calls = record["calls"]
    turns = trained_turns(record)
    assert turns == list(range(LEARNING_STARTS, UPDATES + 1))
    for turn in turns[:-1]:  # the last turn has no next one to queue for
        last_train = max(i for i, c in enumerate(calls) if c == ("train", turn))
        queued = calls.index(("dv3_player_step", turn + 1))
        assert last_train < queued < calls.index(("train/block", turn)) < calls.index(("finite_check", turn))
    # once a turn, through the instance, observation and key first; the turn
    # after the injected fault has its forward made twice
    per_turn = [f["turn"] for f in record["forwards"]]
    acting = list(range(LEARNING_STARTS + 1, UPDATES + 1))
    assert sorted(per_turn) == sorted(acting + [FAULT_AT + 1])
    assert all(f["kwargs"] == {"mask": None, "fetch": False} for f in record["forwards"])
    # and what the env was handed is what that forward gave
    for forward in record["forwards"]:
        if forward["turn"] != FAULT_AT + 1:
            np.testing.assert_array_equal(record["env_actions"][forward["turn"]].reshape(ENVS, -1), forward["action"])


def test_a_rollback_makes_the_queued_forward_again_on_the_restored_weights(record):
    first, again = [f for f in record["forwards"] if f["turn"] == FAULT_AT + 1]
    np.testing.assert_array_equal(first["key"], again["key"])
    for a, b in zip(first["before"], again["before"]):
        np.testing.assert_array_equal(a, b)
    restored = record["checkpoints"][FAULT_AT - 1]
    action, _, _ = record["step"](restored["world_model"], restored["actor"], again["obs"], *again["before"], again["key"], False)
    handed = record["env_actions"][FAULT_AT + 1].reshape(ENVS, -1)
    np.testing.assert_array_equal(handed, np.asarray(action))
    assert not np.array_equal(handed, first["action"])  # the poisoned weights' action went nowhere
    totals = {k: sum(fields[k] for name, fields in record["counters"] if name == "dv3/prequeue") for k in ("turns", "forwards_queued", "forwards_landed", "forwards_redone")}
    assert totals["forwards_redone"] == 1
    assert totals["turns"] == UPDATES - LEARNING_STARTS
    assert totals["forwards_queued"] == totals["turns"]  # the first acting turn's too: queued at ``learning_starts``


@pytest.mark.parametrize("update", [2, 6])
def test_a_checkpoint_holds_the_keys_of_before_the_queued_splits(record, update):
    """The forward of turn ``update + 1`` and the next window's key split were
    issued before this checkpoint was written: a resumed run still splits the
    saved keys into the same action key and the same first train key."""
    state = record["checkpoints"][update]
    (forward,) = [f for f in record["forwards"] if f["turn"] == update + 1]
    np.testing.assert_array_equal(np.asarray(jax.random.split(np.asarray(state["player_rng_key"]))[1]), forward["key"])
    np.testing.assert_array_equal(np.asarray(jax.random.split(np.asarray(state["rng_key"]))[1]), record["train_keys"][update + 1][0])
