"""``rssm_scan``'s backward pass (``sheeprl_tpu/ops/hoisted_scan.py``): the
gradient of every dense kernel of the step is one contraction after the
backward loop. Held here: the gradients are those of plain reverse mode through
an unhoisted ``lax.scan`` of ``WorldModel.dynamic``, the backward loop no longer
carries an accumulator of any such kernel's shape, the Pallas step's kernels
stay in the loop, and a run's ``telemetry.jsonl`` says so (howto/telemetry.md,
``dv3/rssm_scan``)."""

import functools
import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.algos.dreamer_v3 import agent
from sheeprl_tpu.algos.dreamer_v3.agent import WorldModel, rssm_scan, rssm_scan_kernels

T, B, E = 5, 3, 6  # no kernel below has B rows, so a carry of a kernel's shape is an accumulator
HIDDEN, DENSE, STOCH, DISCRETE = 10, 7, 4, 4
ACTIONS = {"discrete": (3,), "continuous": (2,)}
#: the benchmark's limit on ``grad_direction`` (perfbench/correct.py) at bf16-mixed
GRAD_DIRECTION = 0.004


@functools.lru_cache(maxsize=None)
def model(head="discrete", learnable=True, dtype=jnp.float32, fused="flax"):
    """A world model of distinct tiny widths and its seeded parameters (only the RSSM's are built)."""
    wm = WorldModel(
        cnn_keys=(), mlp_keys=("state",), cnn_output_channels=(), mlp_output_dims=(E,), image_size=(16, 16),
        actions_dim=ACTIONS[head], stochastic_size=STOCH, discrete_size=DISCRETE, recurrent_state_size=HIDDEN,
        recurrent_dense_units=DENSE, representation_hidden_size=9, transition_hidden_size=11,
        learnable_initial_recurrent_state=learnable, fused_recurrent=fused, dtype=dtype,
    )  # fmt: skip

    def init(mod):
        zeros = lambda *shape: jnp.zeros(shape, jnp.float32)  # noqa: E731
        mod.dynamic(zeros(1, STOCH * DISCRETE), zeros(1, HIDDEN), zeros(1, sum(ACTIONS[head])), zeros(1, E), zeros(1, 1) + 1, jax.random.PRNGKey(0))
        return ()

    params = nn.init(init, wm)(jax.random.PRNGKey(1))
    if learnable:  # zeros at init: off zero, so tanh' is not 1 and the leaf's gradient is a real test
        params = jax.tree.map(lambda x: x, params)
        params["params"]["initial_recurrent_state"] = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (HIDDEN,))
    return wm, params


def plain_scan(wm, params, embedded, actions, is_first, key):
    """``rssm_scan`` as it was: reverse mode accumulates every parameter's gradient in the loop."""
    h = jnp.zeros((embedded.shape[1], wm.recurrent_state_size), jnp.float32)
    z = jnp.zeros((embedded.shape[1], wm.stoch_state_size), jnp.float32)

    def step(carry, xs):
        h, z, key = carry
        emb_t, act_t, first_t = xs
        key, sub = jax.random.split(key)
        h, z, post, prior = wm.apply(params, z, h, act_t, emb_t, first_t, sub, method=WorldModel.dynamic)
        return (h, z, key), (h, z, post, prior)

    return jax.lax.scan(step, (h, z, key), (embedded, actions, is_first))[1]


def inputs(head):
    k = jax.random.split(jax.random.PRNGKey(5), 6)
    embedded = jax.random.normal(k[0], (T, B, E))
    if head == "discrete":
        actions = jax.nn.one_hot(jax.random.randint(k[1], (T, B), 0, 3), 3)
    else:
        actions = jnp.tanh(jax.random.normal(k[1], (T, B, 2)))
    weights = [jax.random.normal(kk, s) for kk, s in zip(k[2:], [(T, B, HIDDEN), (T, B, STOCH * DISCRETE), (T, B, STOCH, DISCRETE), (T, B, STOCH, DISCRETE)])]  # fmt: skip
    return embedded, actions, weights


IS_FIRST = {
    "none": np.zeros((T, B, 1), np.float32),
    "some_rows_mid_sequence": np.zeros((T, B, 1), np.float32),
    "all_of_row_0": np.zeros((T, B, 1), np.float32),
}
IS_FIRST["some_rows_mid_sequence"][2, 1] = IS_FIRST["some_rows_mid_sequence"][3, 0] = 1.0
IS_FIRST["all_of_row_0"][0] = 1.0


def loss_of(scan, wm, actions, weights):
    """A loss over the scan's four outputs, each weighed entry by entry."""

    def loss(params, embedded, is_first):
        outs = scan(wm, params, embedded, actions, jnp.asarray(is_first), jax.random.PRNGKey(9))
        return sum((o * w).sum() for o, w in zip(outs, weights))

    return loss


@functools.lru_cache(maxsize=None)
def both_grads(head, learnable, dtype=jnp.float32, fused="flax"):
    """``(hoisted, plain)``: jitted gradients with respect to ``params`` and ``embedded``."""
    wm, _ = model(head, learnable, dtype, fused)
    _, actions, weights = inputs(head)
    return tuple(jax.jit(jax.grad(loss_of(scan, wm, actions, weights), argnums=(0, 1))) for scan in (rssm_scan, plain_scan))


def leaves_by_name(tree):
    return {jax.tree_util.keystr(path): np.asarray(leaf) for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("head", ["discrete", "continuous"])
@pytest.mark.parametrize("learnable", [True, False], ids=["learnable_h0", "zero_h0"])
@pytest.mark.parametrize("first", list(IS_FIRST))
def test_the_hoisted_backward_gives_plain_reverse_modes_gradients(first, learnable, head):
    _, params = model(head, learnable)
    embedded = inputs(head)[0]
    hoisted, plain = (leaves_by_name(g(params, embedded, IS_FIRST[first])) for g in both_grads(head, learnable))
    assert hoisted.keys() == plain.keys() and len(hoisted) == len(jax.tree.leaves(params)) + 1
    for name, want in plain.items():
        np.testing.assert_allclose(hoisted[name], want, rtol=1e-5, atol=1e-5, err_msg=name)
    moved = [name for name, want in plain.items() if np.abs(want).max() > 0]
    assert len(moved) == len(plain) - (0 if first != "none" or not learnable else 1), "a leaf the loss does not reach"


def test_under_bf16_mixed_the_directions_agree_as_the_benchmark_asks():
    _, params = model("discrete", True, jnp.bfloat16)
    embedded = inputs("discrete")[0]
    hoisted, plain = (leaves_by_name(g(params, embedded, IS_FIRST["some_rows_mid_sequence"])) for g in both_grads("discrete", True, jnp.bfloat16))
    for name, want in plain.items():
        got = hoisted[name].astype(np.float64).ravel()
        want = want.astype(np.float64).ravel()
        assert want.any(), name
        gap = 1.0 - got @ want / (np.linalg.norm(got) * np.linalg.norm(want))
        assert gap < GRAD_DIRECTION, (name, gap)
        assert hoisted[name].dtype == np.float32


def test_the_pallas_steps_kernels_stay_in_the_loop(monkeypatch):
    """The Pallas step takes its two kernels as arrays: they keep their own
    ``custom_vjp`` and per-step accumulation, the four MLP kernels leave."""
    monkeypatch.setattr(agent, "FusedRecurrentModel", functools.partial(agent.FusedRecurrentModel, interpret=True))
    wm, params = model("discrete", True, jnp.float32, "pallas")
    embedded, actions, _ = inputs("discrete")
    assert rssm_scan_kernels(wm, params, embedded, actions, IS_FIRST["none"], jax.random.PRNGKey(0)) == {
        "hoisted_kernels": 4,
        "hoisted_bytes": 4 * (HIDDEN * 11 + 11 * 16 + (HIDDEN + E) * 9 + 9 * 16),
        "in_loop_kernels": 2,
    }
    hoisted, plain = (leaves_by_name(g(params, embedded, IS_FIRST["some_rows_mid_sequence"])) for g in both_grads("discrete", True, jnp.float32, "pallas"))
    for name, want in plain.items():
        np.testing.assert_allclose(hoisted[name], want, rtol=1e-5, atol=1e-5, err_msg=name)


def test_the_counters_name_the_six_kernels_of_the_flax_step():
    wm, params = model()
    embedded, actions, _ = inputs("discrete")
    kernels = [(sum(ACTIONS["discrete"]) + 16, DENSE), (HIDDEN + DENSE, 3 * HIDDEN), (HIDDEN, 11), (11, 16), (HIDDEN + E, 9), (9, 16)]
    assert rssm_scan_kernels(wm, params, embedded, actions, IS_FIRST["none"], jax.random.PRNGKey(0)) == {
        "hoisted_kernels": 6,
        "hoisted_bytes": 4 * sum(rows * cols for rows, cols in kernels),
        "in_loop_kernels": 0,
    }


def backward_scan_carries(grad_fn, *args):
    """Shapes of what the reverse ``scan``s of ``grad_fn``'s jaxpr carry from step to step."""
    carries = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan" and eqn.params["reverse"]:
                first = eqn.params["num_consts"]
                carries.extend(tuple(v.aval.shape) for v in eqn.invars[first : first + eqn.params["num_carry"]])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(grad_fn)(*args).jaxpr)
    return carries


def test_the_backward_loop_carries_no_kernels_accumulator():
    wm, params = model()
    embedded, actions, weights = inputs("discrete")
    kernels = {leaf.shape for leaf in jax.tree.leaves(params) if leaf.ndim == 2}
    assert len(kernels) == 6
    args = (params, embedded, IS_FIRST["all_of_row_0"])
    plain = backward_scan_carries(jax.grad(loss_of(plain_scan, wm, actions, weights)), *args)
    assert kernels <= set(plain), "the plain scan's backward accumulates every kernel: the check below can fail"
    hoisted = backward_scan_carries(jax.grad(loss_of(rssm_scan, wm, actions, weights)), *args)
    assert hoisted and not kernels & set(hoisted), hoisted
    # what is sequential, and the vectors, stay: the carry's gradient and the small leaves' accumulators
    assert {(B, HIDDEN), (B, STOCH * DISCRETE), (HIDDEN,)} <= set(hoisted)


def test_a_tiny_run_reports_what_left_the_loop(tmp_path, monkeypatch):
    from sheeprl_tpu.cli import run
    from tests.test_algos.test_dreamer_v3 import dv3_args

    monkeypatch.chdir(tmp_path)
    run(dv3_args(tmp_path) + ["fabric.devices=1", "metric.telemetry.enabled=True", "metric.telemetry.poll_interval=0.0"])
    (path,) = [os.path.join(root, f) for root, _, files in os.walk(tmp_path) for f in files if f == "telemetry.jsonl"]
    events = [json.loads(line) for line in open(path) if line.strip()]
    reports = [e for e in events if e["event"] == "counters" and e["name"] == "dv3/rssm_scan"]
    assert reports, sorted({e["event"] for e in events})
    # dv3_args: recurrent 8, dense 8, 4 x 4 latents, two actions' one-hots, an 8 + 2 x 2 x 16 embedding
    report = reports[0]
    assert (report["hoisted_kernels"], report["in_loop_kernels"]) == (6, 0)
    assert report["hoisted_bytes"] % 4 == 0 and report["hoisted_bytes"] > 4 * (16 * 24 + 8 * 8 + 8 * 16)
