"""The per-step Dreamer-V3 train program donates its three parameter trees
(``dreamer_v3.make_train_fn``, ``TRAIN_STEP_DONATED``), and the loop holds to
the rule that makes that sound: no handle on a parameter tree outlives the next
train dispatch. Each placement runs ``dreamer_v3.main`` twice at a tiny size,
as it is and with the parent's donation put back through ``jax.jit``'s own
argument, and the tests read the two records."""

import os
import signal

import jax
import numpy as np
import pytest

from tests.test_algos.test_dreamer_v3 import dv3_args

ENVS = 2
UPDATES = 8
LEARNING_STARTS = 2  # updates
FAULT_AT = 5
PREEMPT_AT = 8  # the poll at the top of this update finds the signal
PARENT_DONATED = (4, 5, 6, 7)

#: what each placement adds to the tiny recipe
PLACEMENTS = {
    # two gradient steps a window; a checkpoint every second update, a NaN and a preemption between windows
    "device_ring": ["buffer.device=True", "algo.replay_ratio=1", f"checkpoint.every={2 * ENVS}",
                    "resilience.fault_injection.enabled=True", f"resilience.fault_injection.faults=[{{kind: nan, at_update: {FAULT_AT}}}]"],
    "host_ring_prefetch": ["buffer.device=False", "buffer.prefetch=2", "algo.replay_ratio=0.5"],
    "fused_supersteps": ["buffer.device=True", "algo.replay_ratio=1", "algo.fused_gradient_steps=2"],
    "host_player": ["buffer.device=True", "algo.replay_ratio=1"],
}  # fmt: skip


def tiny_args(tmp_path, *more):
    """The tiny recipe over ``UPDATES`` updates on one CPU device, training from ``LEARNING_STARTS`` on."""
    replaced = ("dry_run", "algo.learning_starts", "buffer.size", "algo.run_test", "algo.replay_ratio")
    return [a for a in dv3_args(tmp_path, "dummy_continuous") if not a.startswith(replaced)] + [
        "dry_run=False",
        f"algo.total_steps={UPDATES * ENVS}",
        f"algo.learning_starts={LEARNING_STARTS * ENVS}",
        "algo.run_test=False",
        "buffer.size=64",
        "env.sync_env=True",
        "checkpoint.async_save=False",
        "fabric.devices=1",
        "fabric.accelerator=cpu",
        *more,
    ]


def leaves_deleted(tree):
    return [leaf.is_deleted() for leaf in jax.tree.leaves(tree)]


def run_main(tmp_path, placement, donate):
    """One run of ``dreamer_v3.main``; returns the env's actions by update,
    the checkpoints by update, the train step's counters, what held of the
    rule at each window's end, and the player."""
    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as program
    from sheeprl_tpu.cli import run
    from sheeprl_tpu.parallel import fabric as fabric_mod
    from sheeprl_tpu.resilience import PREEMPTED_EXIT_CODE, RunResilience, committed_checkpoints
    from sheeprl_tpu.utils.checkpoint import load_checkpoint

    rec = {"env_actions": {}, "counters": [], "windows": [], "first_step": None, "player": None, "exit": None, "polls": 0, "stream": {"landed": 0, "waited": 0}}
    turn = {"update": 0}
    window = []  # this window's train calls: (the three parameter trees handed in, those given back)
    real = {n: getattr(program, n) for n in ("build_agent", "make_train_fn", "build_vector_env", "telemetry_advance", "telemetry_counters", "telemetry_train_window")}

    def build_agent(*args, **kwargs):
        built = real["build_agent"](*args, **kwargs)
        rec["player"] = built[-1]
        return built

    def make_train_fn(*args, **kwargs):
        fn = real["make_train_fn"](*args, **kwargs)
        if not donate:
            # the parent's program: the same function under the parent's ``donate_argnums``
            fn = jax.jit(fn.__wrapped__, donate_argnums=PARENT_DONATED)

        def train(*a):
            if rec["first_step"] is None:
                pointers = [{leaf.unsafe_buffer_pointer() for leaf in jax.tree.leaves(a[i])} for i in (2, 3)]
                rec["first_step"] = {"critic_and_target_share": len(pointers[0] & pointers[1]), "critic_leaves": len(pointers[0])}
            out = fn(*a)
            window.append((a[:3], out[:3]))
            return out

        train.lower = fn.lower  # ``telemetry_register_flops`` reads the shapes through it
        return train

    def build_vector_env(*args, **kwargs):
        envs = real["build_vector_env"](*args, **kwargs)
        step = envs.step

        def watched(actions):
            rec["env_actions"][turn["update"]] = np.array(actions)
            return step(actions)

        envs.step = watched
        return envs

    def telemetry_advance(policy_step):
        turn["update"] += 1
        return real["telemetry_advance"](policy_step)

    def telemetry_counters(name, **fields):
        rec["counters"].append((name, fields))
        return real["telemetry_counters"](name, **fields)

    def telemetry_train_window(*args, **kwargs):
        # the loop calls this at a window's end, behind ``player.update_params`` and the queued forward
        if window:
            player = rec["player"]
            handed, given = zip(*window)
            rec["windows"].append(
                {
                    "update": turn["update"],
                    "steps": len(window),
                    "handed_deleted": [all(leaves_deleted(tree)) for trees in handed for tree in trees],
                    "newest_deleted": [any(leaves_deleted(tree)) for tree in given[-1]],
                    "player_deleted": any(leaves_deleted((player.wm_params, player.actor_params))),
                    "player_holds_newest": player.wm_params is given[-1][0] and player.actor_params is given[-1][1],
                }
            )
            window.clear()
        return real["telemetry_train_window"](*args, **kwargs)

    preempt_requested = RunResilience.preempt_requested

    def polled(self):
        rec["polls"] += 1
        if placement == "device_ring" and rec["polls"] == PREEMPT_AT:
            os.kill(os.getpid(), signal.SIGTERM)
        return preempt_requested(self)

    # a gate on the stream that does not depend on the clock: every third poll of a pipe lands what is in flight,
    # so a tree offered meanwhile waits as the candidate across a train window
    pipe_poll = fabric_mod._StreamPipe.poll
    pipe_polls = {}

    def gated_poll(self):
        pipe_polls[id(self)] = pipe_polls.get(id(self), 0) + 1
        if pipe_polls[id(self)] % 3:
            return None
        rec["stream"]["waited"] += self._candidate is not None
        landed = pipe_poll(self)
        rec["stream"]["landed"] += landed is not None
        return landed

    args = tiny_args(tmp_path, "checkpoint.keep_last=10", f"run_name={placement}", *PLACEMENTS[placement])
    with pytest.MonkeyPatch.context() as patch:
        patch.chdir(tmp_path)
        for name, fn in (("build_agent", build_agent), ("make_train_fn", make_train_fn), ("build_vector_env", build_vector_env),
                         ("telemetry_advance", telemetry_advance), ("telemetry_counters", telemetry_counters), ("telemetry_train_window", telemetry_train_window)):  # fmt: skip
            patch.setattr(program, name, fn)
        patch.setattr(RunResilience, "preempt_requested", polled)
        if placement == "host_player":
            # on the CPU the learner sits on the first device; the player on another one has its weights streamed
            patch.setattr(fabric_mod, "resolve_player_device", lambda spec="auto": jax.devices()[1])
            patch.setattr(fabric_mod._StreamPipe, "_age_threshold", lambda self: 0.0)
            patch.setattr(fabric_mod._StreamPipe, "poll", gated_poll)
        try:
            run(args)
        except SystemExit as left:
            rec["exit"] = left.code
        assert rec["exit"] == (PREEMPTED_EXIT_CODE if placement == "device_ring" else None)

    ckpt_dirs = [os.path.join(root, d) for root, dirs, _ in os.walk(tmp_path) for d in dirs if d == "checkpoint"]
    rec["checkpoints"] = {int(state["update"]): state for state in (load_checkpoint(c.path) for c in committed_checkpoints(ckpt_dirs[0]))}
    rec["player_weights"] = jax.device_get((rec["player"].wm_params, rec["player"].actor_params))
    return rec


PER_STEP = [p for p in PLACEMENTS if p != "fused_supersteps"]  # the placements that run the per-step program


@pytest.fixture(scope="module")
def pair(tmp_path_factory):
    """``pair(placement)``: the run as it is and the run with the parent's donation, each made once."""
    made = {}

    def of(placement):
        if placement not in made:
            made[placement] = [run_main(tmp_path_factory.mktemp(f"{placement}_{side}"), placement, donate) for side, donate in (("change", True), ("parent", False))]
        return made[placement]

    return of


def assert_trees_equal(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("placement", list(PLACEMENTS))
def test_every_action_and_every_checkpoint_is_the_parents_bit_for_bit(pair, placement):
    change, parent = pair(placement)
    assert sorted(change["env_actions"]) == sorted(parent["env_actions"]) and len(change["env_actions"]) >= UPDATES - 1
    for update, actions in change["env_actions"].items():
        np.testing.assert_array_equal(actions, parent["env_actions"][update])
    assert sorted(change["checkpoints"]) == sorted(parent["checkpoints"])
    for update, state in change["checkpoints"].items():
        assert_trees_equal({k: v for k, v in state.items() if k != "rb"}, {k: v for k, v in parent["checkpoints"][update].items() if k != "rb"})
    if placement == "device_ring":
        # the periodic ones, the rollback's among them, and the preemption's between two windows
        assert sorted(change["checkpoints"]) == [2, 4, 6, PREEMPT_AT - 1]
    else:
        assert sorted(change["checkpoints"]) == [UPDATES]


@pytest.mark.parametrize("placement", PER_STEP)
def test_no_parameter_tree_outlives_the_next_dispatch_and_the_player_holds_the_newest(pair, placement):
    change, parent = pair(placement)
    windows = change["windows"]
    last = PREEMPT_AT - 1 if placement == "device_ring" else UPDATES
    assert [w["update"] for w in windows] == list(range(LEARNING_STARTS, last + 1))
    if placement != "host_ring_prefetch":  # at replay ratio 1 a window has steps that hand their results to the next
        assert max(w["steps"] for w in windows) > 1
    for w in windows:
        assert all(w["handed_deleted"]), w  # each step's three trees went into its results
        assert not any(w["newest_deleted"]) and not w["player_deleted"], w
        # the player beside the learner reads the step's own arrays; the other one its streamed copy
        assert w["player_holds_newest"] == (placement != "host_player"), w
    # the parent's program gave them back in fresh buffers and let the old ones live on
    assert parent["windows"] and not any(any(w["handed_deleted"]) for w in parent["windows"])


def test_the_fused_superstep_keeps_its_parameters(pair):
    change, parent = pair("fused_supersteps")
    # the per-step program never runs there, and the superstep's parameters stay un-donated (ROADMAP B4)
    assert change["windows"] == [] and parent["windows"] == [] and change["first_step"] is None
    assert [name for name, _ in change["counters"] if name == "dv3/train_step_buffers"] == []


@pytest.mark.parametrize("placement", PER_STEP)
def test_the_first_steps_target_critic_shares_no_buffer_with_the_critic(pair, placement):
    change, _ = pair(placement)
    assert change["first_step"]["critic_leaves"] > 0 and change["first_step"]["critic_and_target_share"] == 0


def test_the_counter_reads_one_fresh_result_leaf(pair):
    change, parent = pair("device_ring")
    events = [fields for name, fields in change["counters"] if name == "dv3/train_step_buffers"]
    assert events and all(e == events[0] for e in events)
    assert events[0]["fresh_result_leaves"] == 1  # the metrics vector
    assert events[0]["aliased_result_leaves"] == events[0]["result_leaves"] - 1 > 100


def test_a_nan_in_a_window_rolls_back_and_trains_on(pair):
    change, parent = pair("device_ring")
    redone = [sum(fields["forwards_redone"] for name, fields in side["counters"] if name == "dv3/prequeue") for side in (change, parent)]
    assert redone == [1, 1]
    # update 6's checkpoint is of weights trained on from update 4's, not of the poisoned ones
    leaves = jax.tree.leaves(change["checkpoints"][FAULT_AT + 1]["world_model"])
    assert all(np.isfinite(np.asarray(leaf)).all() for leaf in leaves)
    assert any(not np.array_equal(a, b) for a, b in zip(leaves, jax.tree.leaves(change["checkpoints"][FAULT_AT - 1]["world_model"])))
    assert [w["update"] for w in change["windows"]][-2:] == [FAULT_AT + 1, FAULT_AT + 2]


def test_the_host_player_ends_with_the_last_windows_weights(pair):
    change, _ = pair("host_player")
    assert len(change["windows"]) >= 3
    last = change["checkpoints"][UPDATES]
    assert_trees_equal(change["player_weights"], (last["world_model"], last["actor"]))
    player = change["player"]
    assert {d.id for leaf in jax.tree.leaves((player.wm_params, player.actor_params)) for d in leaf.devices()} == {jax.devices()[1].id}
    # what the gate made of the stream: trees that waited as the candidate across a train window landed too
    assert change["stream"]["landed"] >= 2 and change["stream"]["waited"] >= 1


def test_result_buffers_counts_what_the_donated_arguments_can_hold():
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import result_buffers

    shape = jax.ShapeDtypeStruct
    params = {"w": shape((4, 3), np.float32), "b": shape((3,), np.float32)}
    opt = {"mu": params, "nu": params, "count": shape((), np.int32)}
    args = (params, opt, shape((2,), np.float32))
    out = (params, opt, shape((13,), np.float32))
    assert result_buffers(args, out, (0, 1)) == {"result_leaves": 8, "aliased_result_leaves": 7, "fresh_result_leaves": 1}
    assert result_buffers(args, out, (1,)) == {"result_leaves": 8, "aliased_result_leaves": 5, "fresh_result_leaves": 3}
    # a donated leaf holds one result, not every result of its shape
    assert result_buffers((shape((3,), np.float32),), (shape((3,), np.float32),) * 2, (0,))["fresh_result_leaves"] == 1


def test_a_step_that_raises_after_its_dispatch_fails_the_crash_checkpoint_and_not_the_error(tmp_path):
    """The loop's bindings are the trees the failed dispatch took: the crash
    guard's checkpoint cannot be written, says so, and the step's own error is
    the one that leaves ``cli.run``."""
    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as program
    from sheeprl_tpu.cli import run
    from sheeprl_tpu.resilience import committed_checkpoints

    calls = []
    real = program.make_train_fn

    def make_train_fn(*args, **kwargs):
        fn = real(*args, **kwargs)

        def train(*a):
            calls.append(a[:3])
            out = fn(*a)
            if len(calls) == 2:
                raise RuntimeError("the step failed after its dispatch")
            return out

        train.lower = fn.lower
        return train

    with pytest.MonkeyPatch.context() as patch:
        patch.chdir(tmp_path)
        patch.setattr(program, "make_train_fn", make_train_fn)
        with pytest.warns(UserWarning, match="crash guard: emergency checkpoint failed .*deleted"), pytest.raises(RuntimeError, match="the step failed after its dispatch"):
            run(tiny_args(tmp_path, "buffer.device=True", "algo.replay_ratio=1"))
    assert len(calls) == 2 and all(leaf.is_deleted() for leaf in jax.tree.leaves(calls[1]))
    ckpt_dirs = [os.path.join(root, d) for root, dirs, _ in os.walk(tmp_path) for d in dirs if d == "checkpoint"]
    assert not any(committed_checkpoints(d) for d in ckpt_dirs)


def test_a_rollback_places_the_restored_trees_like_donated_ones_without_reading_them():
    from sheeprl_tpu.resilience import RunResilience

    live = {"w": jax.device_put(np.ones((2, 3), np.float32), jax.devices()[0]), "key": jax.random.PRNGKey(0)}
    jax.jit(lambda t: jax.tree.map(lambda x: x + 1, t), donate_argnums=0)(live)
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(live))
    placed = RunResilience.place_like({"w": np.full((2, 3), 7, np.float32), "key": np.asarray([3, 4], np.uint32)}, live)
    np.testing.assert_array_equal(np.asarray(placed["w"]), 7)
    assert placed["w"].committed and placed["w"].sharding == live["w"].sharding and not placed["key"].committed
