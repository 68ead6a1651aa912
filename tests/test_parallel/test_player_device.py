"""Host-player placement specs (learner-on-chip / actor-on-host split).

No reference counterpart — the torch player always shares the trainer's
device; this framework adds ``algo.player_device`` for backends whose dispatch
round trip measures above 5 ms (parallel/fabric.py ``resolve_player_device`` /
``HostPlayerParams``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.parallel.fabric import (
    HostPlayerParams,
    _ParamStreamer,
    dispatch_roundtrip_seconds,
    put_tree,
    resolve_player_device,
    resolve_train_device,
)


def test_param_streamer_roundtrip_exact():
    """Mixed-dtype tree survives the flat byte-vector transfer bit-exact."""
    dev = jax.devices("cpu")[0]
    tree = {
        "a": jnp.ones((3, 5), jnp.float32) * 1.5,
        "b": {"c": jnp.arange(7, dtype=jnp.int32), "d": jnp.full((2, 2, 2), 0.25, jnp.bfloat16)},
        "e": jnp.float32(3.25),
    }
    s = _ParamStreamer(tree, dev)
    out = s(tree)
    for l1, l2 in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        assert l1.shape == l2.shape and l1.dtype == l2.dtype
        assert np.array_equal(np.asarray(l1, np.float32), np.asarray(l2, np.float32))
    assert s.matches(tree)
    assert not s.matches({"a": tree["a"]})


def test_resolve_accelerator_is_none():
    assert resolve_player_device("accelerator") is None
    assert resolve_player_device(None) is None


def test_resolve_cpu_on_cpu_backend_is_none():
    # the test session runs on the CPU backend: "cpu" means "already there"
    assert resolve_player_device("cpu") is None


def test_resolve_auto_on_cpu_backend_is_none():
    assert resolve_player_device("auto") is None
    # conv policies too: auto depends only on the measured round trip
    assert resolve_player_device("auto") is None


def test_resolve_train_device_rules():
    tiny = {"w": np.zeros((8, 8), np.float32)}
    # default-backend spellings are always None
    assert resolve_train_device("accelerator", tiny, 1) is None
    assert resolve_train_device(None, tiny, 1) is None
    # auto on a cpu default backend: already the host, nothing to pin
    assert resolve_train_device("auto", tiny, 1) is None
    # explicit cpu pin commits to the host backend device
    dev = resolve_train_device("cpu", tiny, 1)
    assert dev is not None and dev.platform == "cpu"
    # multi-device: mesh training only — explicit cpu is a config error,
    # auto silently stays on the mesh
    with pytest.raises(ValueError, match="single-device"):
        resolve_train_device("cpu", tiny, 2)
    assert resolve_train_device("auto", tiny, 8) is None


def test_param_streamer_single_byte_dtypes_roundtrip():
    """int8/bool/uint8 leaves survive packing next to wider leaves (the
    round-2 advisor finding: concatenating raw int8 with uint8 segments
    type-promoted and broke the byte layout)."""
    dev = jax.devices("cpu")[0]
    tree = {
        "i8": jnp.array([-3, 0, 127, -128], jnp.int8),
        "u8": jnp.array([0, 255, 7], jnp.uint8),
        "b": jnp.array([True, False, True]),
        "f": jnp.ones((4,), jnp.float32) * 2.5,
    }
    s = _ParamStreamer(tree, dev)
    out = s(tree)
    for k in tree:
        assert out[k].dtype == tree[k].dtype, k
        assert np.array_equal(np.asarray(out[k]), np.asarray(tree[k])), k


def test_param_streamer_begin_finish_deferred():
    dev = jax.devices("cpu")[0]
    tree = {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4), "b": jnp.ones(3, jnp.bfloat16)}
    s = _ParamStreamer(tree, dev)
    handle = s.begin(tree)
    out = s.finish(handle)
    for k in tree:
        assert np.array_equal(np.asarray(out[k], np.float32), np.asarray(tree[k], np.float32))


def test_stream_pipe_applies_newest_after_age_gate(monkeypatch):
    from sheeprl_tpu.parallel import fabric as fabric_mod
    from sheeprl_tpu.parallel.fabric import _StreamPipe

    dev = jax.devices("cpu")[0]
    tree1 = {"w": jnp.zeros((4,), jnp.float32)}
    tree2 = {"w": jnp.ones((4,), jnp.float32)}
    s = _ParamStreamer(tree1, dev)
    pipe = _StreamPipe(s)
    monkeypatch.setitem(fabric_mod._rtt_cache, "rtt", 0.0)  # age gate -> 20 ms floor

    import time

    pipe.offer(tree1)
    time.sleep(0.05)
    assert pipe.poll() is not None  # tree1 lands once past the age gate
    pipe.offer(tree2)
    time.sleep(0.05)
    out = pipe.poll()
    assert out is not None and np.asarray(out["w"]).sum() == 4.0


def test_dispatch_fence_bounds_inflight_markers():
    from sheeprl_tpu.parallel.fabric import DispatchFence

    fence = DispatchFence(depth=2)
    for i in range(6):
        fence.push(jnp.full((3, 3), i, jnp.float32))
        assert len(fence._pending) <= 2
    fence.drain()
    assert len(fence._pending) == 0


def test_resolve_unknown_spec_raises():
    with pytest.raises(ValueError):
        resolve_player_device("gpu0")


def test_dispatch_roundtrip_is_fast_locally():
    # virtual CPU devices are in-process: far below the 5 ms threshold
    assert dispatch_roundtrip_seconds() < 0.005


def test_put_tree_identity_without_device():
    tree = {"a": np.ones((2,), np.float32)}
    assert put_tree(tree, None) is tree


def test_put_tree_places_on_device():
    dev = jax.devices("cpu")[0]
    out = put_tree({"a": np.ones((2,), np.float32)}, dev)
    assert out["a"].devices() == {dev}


class _Player(HostPlayerParams):
    _placed_attrs = ("params",)

    def __init__(self, params, device=None):
        self.device = device
        self.params = params


def test_mixin_passthrough_without_device():
    p = _Player({"w": np.zeros((2,), np.float32)})
    assert isinstance(p.params["w"], np.ndarray)


def test_mixin_places_assignments():
    dev = jax.devices("cpu")[0]
    p = _Player({"w": np.zeros((2,), np.float32)}, device=dev)
    assert p.params["w"].devices() == {dev}
    # every later assignment is placed too — the loops' `player.params = ...`
    # sync sites rely on this
    p.params = {"w": np.ones((2,), np.float32)}
    assert p.params["w"].devices() == {dev}
    assert float(p.params["w"][0]) == 1.0


def test_mixin_ignores_other_attrs():
    dev = jax.devices("cpu")[0]
    p = _Player({"w": np.zeros((2,), np.float32)}, device=dev)
    p.note = np.ones((1,), np.float32)
    assert isinstance(p.note, np.ndarray)


def test_player_on_explicit_device_end_to_end():
    """A PPOPlayer pinned to an explicit device samples actions correctly and
    keeps its params there after an update_params refresh."""
    import gymnasium as gym

    from sheeprl_tpu.algos.ppo.agent import PPOPlayer, build_agent
    from sheeprl_tpu.parallel import Fabric

    cfg = {
        "algo": {
            "cnn_keys": {"encoder": []},
            "mlp_keys": {"encoder": ["state"]},
            "encoder": {"cnn_features_dim": 64, "mlp_features_dim": 16, "dense_units": 8, "mlp_layers": 1},
            "actor": {"dense_units": 8, "mlp_layers": 1},
            "critic": {"dense_units": 8, "mlp_layers": 1},
            "dense_act": "tanh",
            "layer_norm": False,
        },
        "seed": 0,
    }
    obs_space = gym.spaces.Dict({"state": gym.spaces.Box(-1, 1, (3,), np.float32)})
    fabric = Fabric(devices=1)
    agent, params = build_agent(fabric, (2,), False, cfg, obs_space)
    dev = jax.devices("cpu")[0]
    player = PPOPlayer(agent, params, device=dev)

    obs = {"state": np.zeros((4, 3), np.float32)}
    actions, logprobs, values = player.get_actions(obs, jax.random.PRNGKey(0))
    assert np.asarray(actions).shape == (4, 2)
    # refresh params through the sync path used by the train loop
    player.update_params(params)
    leaf = jax.tree.leaves(player.params)[0]
    assert leaf.devices() == {dev}


def test_age_threshold_scales_with_pack_size_above_the_rtt_threshold(monkeypatch):
    """The stream gate waits for the landing estimate (bytes/bandwidth + RTT)
    when the dispatch round trip is above the 5 ms threshold, and keeps the
    cheap RTT-only gate below it — polling a large pack early turns the
    'free' finish into a blocking partial-transfer wait."""
    import jax.numpy as jnp

    from sheeprl_tpu.parallel import fabric as fabric_mod
    from sheeprl_tpu.parallel.fabric import _ParamStreamer, _StreamPipe

    monkeypatch.delenv("SHEEPRL_TPU_LINK_BYTES_PER_S", raising=False)
    dev = jax.devices()[0]
    big = {"w": jnp.zeros((1_000_000,), jnp.float32)}  # 4 MB pack
    pipe = _StreamPipe(_ParamStreamer(big, dev))

    # sub-threshold RTT: the cheap gate, bytes ignored
    monkeypatch.setitem(fabric_mod._rtt_cache, "rtt", 0.0001)
    assert pipe._age_threshold() == pytest.approx(0.02)

    # above the threshold: the 4 MB pack cannot land before bytes/bandwidth + RTT
    monkeypatch.setitem(fabric_mod._rtt_cache, "rtt", 0.1)
    expected = 4_000_000 / _StreamPipe._link_bytes_per_s() + 0.1
    assert pipe._age_threshold() == pytest.approx(expected)

    # a tiny pack above the threshold keeps the RTT-dominated gate
    small = _StreamPipe(_ParamStreamer({"w": jnp.zeros((4,), jnp.float32)}, dev))
    assert small._age_threshold() == pytest.approx(0.15)


def test_link_bytes_per_s_env_validation(monkeypatch):
    from sheeprl_tpu.parallel.fabric import _StreamPipe

    monkeypatch.setenv("SHEEPRL_TPU_LINK_BYTES_PER_S", "0")
    assert _StreamPipe._link_bytes_per_s() == 1e3  # floored, no ZeroDivision
    monkeypatch.setenv("SHEEPRL_TPU_LINK_BYTES_PER_S", "14MB")
    assert _StreamPipe._link_bytes_per_s() == 10e6  # malformed -> default
    monkeypatch.setenv("SHEEPRL_TPU_LINK_BYTES_PER_S", "5e7")
    assert _StreamPipe._link_bytes_per_s() == 5e7
    monkeypatch.setenv("SHEEPRL_TPU_LINK_BYTES_PER_S", "nan")
    assert _StreamPipe._link_bytes_per_s() == 1e3  # nan must not disable the gate


def test_resolvers_record_what_auto_became(monkeypatch):
    """Each placement the program picks from what it observes lands in the
    run record's ``resolved`` section through the active telemetry."""
    from sheeprl_tpu.obs import telemetry as telemetry_mod
    from sheeprl_tpu.parallel.fabric import tree_devices

    class _Tel:
        def __init__(self):
            self.seen = {}

        def record_resolved(self, name, value, **fields):
            self.seen[name] = {"value": value, **fields}

    tel = _Tel()
    monkeypatch.setattr(telemetry_mod, "_active_telemetry", tel)
    assert resolve_player_device("auto") is None
    assert resolve_train_device("auto", {"w": np.zeros((8, 8), np.float32)}, 1) is None
    assert resolve_train_device("cpu", {"w": np.zeros((8, 8), np.float32)}, 1).platform == "cpu"
    assert tel.seen["player_device"] == {"value": "cpu", "spec": "auto"}
    assert tel.seen["train_device"] == {"value": "cpu", "spec": "cpu"}
    # where a tree actually is: .devices() of its leaves, host leaves ignored
    tree = {"a": jax.device_put(jnp.ones(3), jax.devices()[2]), "b": np.ones(2), "c": jnp.zeros(1)}
    assert tree_devices(tree) == ["cpu:0", "cpu:2"]
