"""The plain reference against the program's own train step, tiny on the CPU:
it agrees in float32, a lower precision on either side does not pass, and the
controls (the reference with its weights in bfloat16, and in float8, put in the
program's place) fail."""

import pytest

from perfbench import correct
from tests.test_perfbench import tiny_dreamer_v3 as tiny

CONFIGS = ("dv3_S_walker", "dv3_XL_crafter")


def _followed(name):
    cfg = tiny.tiny_config(name)
    capture = tiny.program_steps(cfg, "fp32")
    return cfg, capture, correct.follow(cfg, capture, "float32")


@pytest.fixture(scope="module")
def followed():
    return _followed(CONFIGS[0])


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_agrees_with_the_programs_step_in_float32(name, followed):
    # continuous actions and pixels alone; discrete actions with a vector input beside the pixels
    cfg, capture, ref = followed if name == CONFIGS[0] else _followed(name)
    numbers = correct.compare(correct.program_side(capture, ref), ref)
    compared = correct.judge(numbers, tiny.TRAIN_LIMITS)
    assert all(v["ok"] for v in compared.values()), compared
    assert max(numbers.values()) < 1e-4, numbers


def test_program_in_a_lower_precision_is_not_correct(followed):
    cfg, _, _ = followed
    stated_lower = {**cfg, "algo": {**cfg["algo"], "precision": "bf16-mixed"}}
    capture = tiny.program_steps(stated_lower, "bf16-mixed")
    ref = correct.follow(cfg, capture, "float32")
    compared = correct.judge(correct.compare(correct.program_side(capture, ref), ref), tiny.TRAIN_LIMITS)
    assert not all(v["ok"] for v in compared.values()), compared


def test_a_program_that_departs_from_the_stated_precision_is_refused(followed):
    cfg, _, _ = followed
    with pytest.raises(SystemExit, match="fabric.precision='bf16-mixed' but algo.precision='fp32'"):
        tiny.program_steps(cfg, "bf16-mixed")


@pytest.fixture(scope="module")
def controls(followed):
    cfg, capture, ref = followed
    numbers = {}
    for policy in ("bfloat16_weights", "float8"):
        control = correct.follow(cfg, capture, policy)
        side = {k: control[k] for k in ("losses", "first_grads", "first_grad_samples", "change")} | {"player": None}
        numbers[policy] = correct.compare(side, ref)
    return numbers


@pytest.mark.parametrize("policy,number", [("bfloat16_weights", "change"), ("bfloat16_weights", "policy_loss"), ("bfloat16_weights", "value_loss"),
                                           ("float8", "grad_direction"), ("float8", "first_grad_actor"), ("float8", "first_grad_critic")])  # fmt: skip
def test_control_in_the_programs_place_fails(controls, policy, number):
    assert controls[policy][number] > 10 * tiny.TINY_LIMITS[number], controls[policy]


def test_half_of_the_batch_left_out_reads_far_off(followed):
    cfg, capture, ref = followed
    fault = correct.follow(cfg, capture, "float32", fault="half_batch")
    side = {k: fault[k] for k in ("losses", "first_grads", "first_grad_samples", "change")} | {"player": None}
    numbers = correct.compare(side, ref)
    assert numbers["wm_loss"] > 1e-3 or numbers["first_grad"] > 1e-2, numbers
