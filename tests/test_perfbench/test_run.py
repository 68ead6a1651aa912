"""The harness end to end, tiny on the CPU: everything of a run except the
look for a chip. The last-line contract for ``--trace 0`` and ``--trace 1``,
and the faults a training cell can have, planted under the timed path."""

import contextlib
import json

import pytest

from perfbench import run
from tests.test_perfbench import tiny, tiny_dreamer_v3


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("perfbench_root")))


KEPT = {}


def _verify_and_keep(cfg, seed, capture, limits, stamps):
    """The algorithm's own ``verify``, with the run's capture kept for the test of the player's control."""
    from perfbench import correct

    KEPT[cfg["name"]] = (cfg, capture, stamps)
    return correct.verify(cfg, seed, capture, limits, stamps)


def _line(result):
    # what the driver reads: the dict survives JSON, keys in the contract's order
    return json.loads(json.dumps(result))


@pytest.mark.parametrize("cell,trace", [("dv3_S_walker.train", 0), ("dv3_XL_crafter.train", 1)])
def test_last_line_contract(root, cell, trace):
    line = _line(run.run_cell(cell, 2**31 + 17, 1.5, bool(trace), root=root, require_tpu=False, verify=_verify_and_keep))
    assert list(line)[:3] == ["correct", "attempted", "failed"] and list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    with open(f"{root}/BENCHMARK.json") as f:
        bench = json.load(f)
    if trace:
        # the device metrics need a chip; the rest are read on any machine
        assert {"compile.in_window", "env.step_share", "loop.env_interaction_ms", "train_step.ms_per_grad_step"} <= set(line["metrics"])
        assert set(line["metrics"]) <= {m["name"] for m in bench["per_layer"]}
        assert line["metrics"]["compile.in_window"]["value"] == 0.0
        assert {"busy_s", "window_s"} <= set(line["device"]) and "breakdown" in line
    else:
        assert set(line["metrics"]) == {m["name"] for m in bench["end_to_end"]}
        assert all(v["value"] > 0 for v in line["metrics"].values())
    for number in line["compared"].values():
        assert set(number) == {"value", "limit"}


def _faulty(fault):
    """A context manager that breaks the program's train function, or what
    its ring gives back, underneath the harness: the recording wrapper and
    the window see only the result."""

    @contextlib.contextmanager
    def patch():
        from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as program

        real = program.make_train_fn

        def make_train_fn(*args, **kwargs):
            fn = real(*args, **kwargs)

            def unchanged(*a):
                out = fn(*a)
                return (a[0], a[1], a[2], *out[3:])

            def half_batch(*a):
                batch = {k: v[:, : v.shape[1] // 2] for k, v in a[8].items()}
                return fn(*a[:8], batch, a[9])

            return {"unchanged": unchanged, "half_batch": half_batch, "ring_mixes_rows": fn}[fault]

        def sampled_batches(*args, **kwargs):
            for batch in real_batches(*args, **kwargs):
                yield {**batch, "rewards": batch["rewards"][:, ::-1]} if fault == "ring_mixes_rows" else batch

        real_batches = program.sampled_batches
        program.make_train_fn, program.sampled_batches = make_train_fn, sampled_batches
        try:
            yield
        finally:
            program.make_train_fn, program.sampled_batches = real, real_batches

    return patch


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "ring_mixes_rows"])
def test_a_broken_train_step_is_not_correct(root, fault):
    line = run.run_cell("dv3_S_walker.train", 5, 1.0, False, root=root, require_tpu=False, program_patch=_faulty(fault))
    assert line["correct"] is False, line["compared"]
    if fault == "unchanged":
        assert line["compared"]["change"]["value"] == pytest.approx(1.0, abs=1e-6)
    if fault == "ring_mixes_rows":
        # the step itself is sound on what it was fed: only the look at the ring fails
        assert [k for k, v in line["compared"].items() if not v["value"] <= v["limit"]] == ["ring_rows"]


def test_the_players_control_and_a_wrong_observation_fail(root):
    """The reference's player in float8, put in the program's place on the
    forwards a run recorded, and the program's player judged on another
    frame than it saw."""
    import jax

    from perfbench import bridge, correct
    from perfbench.references import dreamer_v3 as reference
    if "dv3_S_walker" not in KEPT:
        run.run_cell("dv3_S_walker.train", 2**31 + 17, 1.0, False, root=root, require_tpu=False, verify=_verify_and_keep)
    cfg, capture, _ = KEPT["dv3_S_walker"]
    assert len(capture.player) == bridge.PLAYER_FORWARDS
    wm, actor, _ = jax.device_put(capture.seeded)
    ref = correct.player_side(reference.Model(cfg, "float32"), wm, actor, capture.player)
    sound = correct.player_gaps(correct._stacked(capture.player), ref)
    assert all(sound[k] <= tiny_dreamer_v3.LIMITS[k] for k in ("player_h", "player_z", "player_action")), sound
    control = correct.player_gaps(correct.player_side(reference.Model(cfg, "float8"), wm, actor, capture.player), ref)
    assert control["player_h"] > 10 * tiny_dreamer_v3.LIMITS["player_h"], control
    shifted = [{**call, "obs": {k: v[::-1] for k, v in call["obs"].items()}} for call in capture.player]
    wrong = correct.player_gaps(correct._stacked(capture.player), correct.player_side(reference.Model(cfg, "float32"), wm, actor, shifted))
    assert wrong["player_z"] > 10 * tiny_dreamer_v3.LIMITS["player_z"], wrong
