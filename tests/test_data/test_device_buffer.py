"""HBM-resident replay ring (data/device_buffer.py): semantic parity with
the EnvIndependent/Sequential host pair, on-device add/gather, checkpoint
round trips, and mode conversion."""

import numpy as np
import pytest

from sheeprl_tpu.data.device_buffer import (
    DeviceReplayBuffer,
    adapt_restored_buffer,
    estimate_ring_bytes,
)


def _step(rb, t, envs=None, n_envs=3):
    n = n_envs if envs is None else len(envs)
    rb.add(
        {
            "rgb": np.full((1, n, 8, 8, 3), t % 256, np.uint8),
            "actions": np.full((1, n, 2), t, np.float32),
            "rewards": np.full((1, n, 1), t, np.float32),
            "terminated": np.zeros((1, n, 1), np.float32),
            "truncated": np.zeros((1, n, 1), np.float32),
            "is_first": np.zeros((1, n, 1), np.float32),
        },
        envs,
    )


def _fresh(cap=16, n_envs=3, seed=0):
    return DeviceReplayBuffer(cap, n_envs=n_envs, obs_keys=("rgb",), seed=seed)


def test_add_and_sample_layout_and_dtypes():
    rb = _fresh()
    for t in range(10):
        _step(rb, t)
    (batch,) = rb.sample_batches(batch_size=5, sequence_length=4, n_samples=1)
    assert batch["rgb"].shape == (4, 5, 8, 8, 3) and str(batch["rgb"].dtype) == "uint8"
    assert batch["actions"].shape == (4, 5, 2) and str(batch["actions"].dtype) == "float32"


def test_sampled_windows_are_contiguous_and_never_straddle_the_cursor():
    rb = _fresh()
    for t in range(10):
        _step(rb, t)
    # wrap the ring: cursor sits mid-ring with old data behind it
    for t in range(20, 40):
        _step(rb, t)
    assert all(rb.full)
    for batch in rb.sample_batches(batch_size=8, sequence_length=6, n_samples=4):
        rewards = np.asarray(batch["rewards"])[..., 0]  # [T, B] step counters
        assert np.all(np.diff(rewards, axis=0) == 1), rewards.T
    # amend flags of the newest step (failure-recovery patch path)
    rb.amend_last(1, terminated=0.0, truncated=1.0, is_first=0.0)
    arrs = rb.host_arrays()
    slot = (rb._pos[1] - 1) % rb.buffer_size
    assert arrs["truncated"][1, slot] == 1.0 and arrs["terminated"][1, slot] == 0.0


def test_partial_add_advances_only_those_envs():
    rb = _fresh()
    for t in range(5):
        _step(rb, t)
    _step(rb, 99, envs=[1])
    assert rb._pos.tolist() == [5, 6, 5]
    arrs = rb.host_arrays()
    assert arrs["rewards"][1, 5, 0] == 99.0
    # the other envs' slot 5 is untouched (zeros)
    assert arrs["rewards"][0, 5, 0] == 0.0


def test_too_short_history_raises_like_host_buffer():
    rb = _fresh()
    for t in range(3):
        _step(rb, t)
    with pytest.raises(ValueError, match="Cannot sample a sequence"):
        list(rb.sample_batches(batch_size=2, sequence_length=8, n_samples=1))


def test_checkpoint_flag_fixup_roundtrip():
    rb = _fresh()
    for t in range(6):
        _step(rb, t)
    saved = rb.flag_last_truncated()
    arrs = rb.host_arrays()
    slots = (rb._pos - 1) % rb.buffer_size
    assert all(arrs["truncated"][e, slots[e]] == 1.0 for e in range(3))
    rb.restore_last_truncated(saved)
    arrs = rb.host_arrays()
    assert all(arrs["truncated"][e, slots[e]] == 0.0 for e in range(3))


def test_pickle_and_mode_conversion_roundtrips():
    import pickle

    rb = _fresh()
    for t in range(12):
        _step(rb, t)
    clone = pickle.loads(pickle.dumps(rb)).restore_to_device()
    assert np.array_equal(clone.host_arrays()["rewards"], rb.host_arrays()["rewards"])

    host = rb.to_host_buffer()
    assert [b._pos for b in host.buffer] == rb._pos.tolist()
    back = DeviceReplayBuffer.from_host_buffer(host)
    assert np.array_equal(back.host_arrays()["rgb"], rb.host_arrays()["rgb"])

    # adapt_restored_buffer covers all four (restored, wanted) combinations
    assert adapt_restored_buffer(host, want_device=False) is host
    assert isinstance(adapt_restored_buffer(host, want_device=True), DeviceReplayBuffer)
    unrestored = pickle.loads(pickle.dumps(rb))
    assert isinstance(adapt_restored_buffer(unrestored, want_device=True), DeviceReplayBuffer)
    host2 = adapt_restored_buffer(pickle.loads(pickle.dumps(rb)), want_device=False)
    assert np.array_equal(host2.buffer[0]["rewards"][:, 0], rb.host_arrays()["rewards"][0])


def test_estimate_ring_bytes():
    import gymnasium as gym

    space = gym.spaces.Dict(
        {
            "rgb": gym.spaces.Box(0, 255, (64, 64, 3), np.uint8),
            "state": gym.spaces.Box(-1, 1, (7,), np.float32),
        }
    )
    est = estimate_ring_bytes(space, actions_dim=(4,), buffer_size=100, n_envs=2)
    # pixels in whole 128-byte rows, the 7 + 4 + 4 floats as one 128-lane row
    per_step = 64 * 64 * 3 + 128 * 4
    assert est == per_step * 100 * 2


# ---------------------------------------------------- transition mode (SAC)


def _sac_step(rb, t, n_envs=3):
    rb.add(
        {
            "observations": np.full((1, n_envs, 4), t, np.float32),
            "next_observations": np.full((1, n_envs, 4), t + 1, np.float32),
            "actions": np.full((1, n_envs, 2), t, np.float32),
            "rewards": np.full((1, n_envs, 1), t, np.float32),
            "terminated": np.zeros((1, n_envs, 1), np.float32),
            "truncated": np.zeros((1, n_envs, 1), np.float32),
        }
    )


def test_sample_transitions_layout_and_consistency():
    rb = DeviceReplayBuffer(16, n_envs=3, obs_keys=("observations",), seed=0)
    for t in range(10):
        _sac_step(rb, t)
    data = rb.sample_transitions(batch_size=6, n_samples=4)
    assert data["observations"].shape == (4, 6, 4)
    assert data["actions"].shape == (4, 6, 2)
    # each drawn transition is internally consistent: obs == rewards == t
    obs = np.asarray(data["observations"])[..., 0]
    rew = np.asarray(data["rewards"])[..., 0]
    nxt = np.asarray(data["next_observations"])[..., 0]
    assert np.array_equal(obs, rew) and np.array_equal(nxt, obs + 1)


def test_sample_transitions_next_obs_gather():
    rb = DeviceReplayBuffer(16, n_envs=2, obs_keys=("observations",), seed=0)
    for t in range(12):
        rb.add(
            {
                "observations": np.full((1, 2, 4), t, np.float32),
                "actions": np.zeros((1, 2, 2), np.float32),
                "rewards": np.full((1, 2, 1), t, np.float32),
                "terminated": np.zeros((1, 2, 1), np.float32),
                "truncated": np.zeros((1, 2, 1), np.float32),
            }
        )
    data = rb.sample_transitions(batch_size=8, n_samples=2, sample_next_obs=True)
    obs = np.asarray(data["observations"])[..., 0]
    nxt = np.asarray(data["next_observations"])[..., 0]
    assert np.array_equal(nxt, obs + 1)


def test_sample_transitions_wraparound_validity():
    # after wrapping, samples never come from beyond the stored range and
    # sample_next_obs never pairs a transition with the overwritten oldest slot
    rb = DeviceReplayBuffer(8, n_envs=1, obs_keys=("observations",), seed=1)
    for t in range(20):
        rb.add(
            {
                "observations": np.full((1, 1, 1), t, np.float32),
                "rewards": np.full((1, 1, 1), t, np.float32),
            }
        )
    assert all(rb.full)
    data = rb.sample_transitions(batch_size=64, n_samples=1, sample_next_obs=True)
    obs = np.asarray(data["observations"]).reshape(-1)
    nxt = np.asarray(data["next_observations"]).reshape(-1)
    assert obs.min() >= 12 and obs.max() <= 18  # stored range is 12..19; 19's next wrapped
    assert np.array_equal(nxt, obs + 1)


def test_sample_transitions_errors_match_host_contract():
    rb = DeviceReplayBuffer(8, n_envs=1, obs_keys=("observations",), seed=0)
    with pytest.raises(RuntimeError, match="has not been initialized"):
        rb.sample_transitions(batch_size=2)
    rb.add({"observations": np.zeros((1, 1, 1), np.float32)})
    # insufficient data is ValueError, matching the host ReplayBuffer
    # contract (RuntimeError stays reserved for the uninitialized ring)
    with pytest.raises(ValueError, match="at least two samples"):
        rb.sample_transitions(batch_size=2, sample_next_obs=True)
    with pytest.raises(ValueError, match="must be both greater than 0"):
        rb.sample_transitions(batch_size=0)


def test_transition_host_buffer_roundtrip():
    from sheeprl_tpu.data.buffers import ReplayBuffer

    rb = DeviceReplayBuffer(16, n_envs=2, obs_keys=("observations",), seed=0)
    for t in range(10):
        _sac_step(rb, t, n_envs=2)
    host = rb.to_transition_host_buffer()
    assert isinstance(host, ReplayBuffer)
    assert host._pos == 10 and not host.full
    assert np.array_equal(
        np.asarray(host.buffer["rewards"]).swapaxes(0, 1), rb.host_arrays()["rewards"]
    )
    back = DeviceReplayBuffer.from_transition_host_buffer(host)
    assert back._pos.tolist() == [10, 10]
    assert np.array_equal(back.host_arrays()["rewards"], rb.host_arrays()["rewards"])
    # adapt_restored_buffer in transition mode, both directions
    assert isinstance(
        adapt_restored_buffer(host, want_device=True, mode="transition"), DeviceReplayBuffer
    )
    import pickle

    host2 = adapt_restored_buffer(
        pickle.loads(pickle.dumps(rb)), want_device=False, mode="transition"
    )
    assert isinstance(host2, ReplayBuffer)
    assert np.array_equal(
        np.asarray(host2.buffer["rewards"]).swapaxes(0, 1), rb.host_arrays()["rewards"]
    )


def test_estimate_transition_bytes():
    import gymnasium as gym

    from sheeprl_tpu.data.device_buffer import estimate_transition_bytes

    space = gym.spaces.Dict(
        {
            "rgb": gym.spaces.Box(0, 255, (32, 32, 3), np.uint8),
            "state": gym.spaces.Box(-1, 1, (5,), np.float32),
        }
    )
    est = estimate_transition_bytes(
        space, ["rgb", "state"], actions_dim=(2,), buffer_size=10, n_envs=2, store_next_obs=True
    )
    # 2 x 5 + 2 + 3 floats fit one 128-lane row
    per_step = 32 * 32 * 3 * 2 + 128 * 4
    assert est == per_step * 10 * 2


# ------------------------------------------- the stored form (whole lane rows)
#
# Pixel keys live on the device as ``uint8[E, cap + 1, ceil(n / 128), 128]``
# and every other key as columns of one packed ``float32[E, cap + 1, width]``
# array. Nothing outside the ring may see that: what goes in comes back bit
# for bit, in the items' own shapes, whatever the item's byte count.


class _Mirror:
    """A plain numpy ring fed the same steps: ``[E, cap, *item]`` a key."""

    def __init__(self, cap, n_envs, items, seed=0):
        self.cap, self.n_envs, self.items = cap, n_envs, items
        self.rng = np.random.default_rng(seed)
        self.count = np.zeros(n_envs, np.int64)  # adds an env has had: its cursor is count % cap
        self.arrays = {k: np.zeros((n_envs, cap, *item), np.uint8) for k, item in items.items()}
        self.arrays.update({k: np.zeros((n_envs, cap, w), np.float32) for k, w in (("actions", 3), ("env", 1), ("stamp", 1))})

    def step(self, rb, envs=None):
        envs = list(range(self.n_envs)) if envs is None else envs
        data = {k: self.rng.integers(0, 256, (1, len(envs), *item), dtype=np.uint8) for k, item in self.items.items()}
        data["actions"] = self.rng.normal(size=(1, len(envs), 3)).astype(np.float32)
        data["env"] = np.asarray(envs, np.float32).reshape(1, -1, 1)
        data["stamp"] = self.count[envs].astype(np.float32).reshape(1, -1, 1)
        rb.add(data, envs)
        for col, env in enumerate(envs):
            for k, v in data.items():
                self.arrays[k][env, self.count[env] % self.cap] = v[0, col]
            self.count[env] += 1

    def check_batch(self, batch):
        """Every position of a gathered batch (any leading dims) holds what
        the mirror has in the slot that the position's own ``env`` and
        ``stamp`` name."""
        env = np.asarray(batch["env"])[..., 0].astype(np.int64)
        slot = np.asarray(batch["stamp"])[..., 0].astype(np.int64) % self.cap
        for k, arr in self.arrays.items():
            got = np.asarray(batch[k])
            assert got.dtype == arr.dtype and got.shape == env.shape + arr.shape[2:], (k, got.shape)
            np.testing.assert_array_equal(got, arr[env, slot], err_msg=k)


_ITEMS = {
    "64x64x3": {"rgb": (64, 64, 3)},  # 96 whole rows
    "84x84x4": {"rgb": (84, 84, 4)},  # 220.5 rows: the last one padded
    "3": {"rgb": (3,)},  # under one row
    "two_pixel_keys": {"rgb": (64, 64, 3), "depth": (32, 32)},
}


def _filled(items, n_envs, cap=6, **kwargs):
    """A ring that has wrapped, with partial adds on the way (so the cursors
    differ and the scratch slot has absorbed the other envs' rows)."""
    rb = DeviceReplayBuffer(cap, n_envs=n_envs, obs_keys=tuple(items), seed=0, **kwargs)
    mirror = _Mirror(cap, n_envs, items)
    for t in range(cap + 3):
        mirror.step(rb)
        if t % 3 == 1:
            mirror.step(rb, envs=[n_envs - 1])
        if t % 4 == 2 and n_envs > 2:
            mirror.step(rb, envs=[0, n_envs - 2])
    return rb, mirror


@pytest.mark.parametrize("n_envs", [1, 4])
@pytest.mark.parametrize("items", list(_ITEMS.values()), ids=list(_ITEMS))
def test_write_then_gather_is_bit_exact_against_a_numpy_ring(items, n_envs):
    import jax.numpy as jnp

    rb, mirror = _filled(items, n_envs)
    cap = rb.buffer_size
    # the stored form: whole 128-byte rows a pixel item, one packed float array
    for k, item in items.items():
        assert rb._bufs.pixels[k].shape == (n_envs, cap + 1, -(-int(np.prod(item)) // 128), 128)
    assert rb._bufs.smalls.shape == (n_envs, cap + 1, 128) and rb._bufs.smalls.dtype == jnp.float32
    assert rb.ring_bytes() == sum(v.nbytes for v in rb._bufs.pixels.values()) + rb._bufs.smalls.nbytes
    # what is outside is unchanged: [E, cap, *item], the scratch slot left out
    assert rb._pos.tolist() == (mirror.count % cap).tolist()
    arrs = rb.host_arrays()
    assert set(arrs) == set(mirror.arrays)
    for k, v in mirror.arrays.items():
        assert arrs[k].dtype == v.dtype
        np.testing.assert_array_equal(arrs[k], v, err_msg=k)
    # every window of every env, those that wrap the capacity included
    T = 4
    env_idx = np.repeat(np.arange(n_envs, dtype=np.int32), cap)
    starts = np.tile(np.arange(cap, dtype=np.int32), n_envs)
    time_idx = (starts[:, None] + np.arange(T, dtype=np.int32)) % cap
    got = rb._gather(rb._bufs, jnp.asarray(env_idx), jnp.asarray(time_idx))
    for k, v in mirror.arrays.items():
        np.testing.assert_array_equal(np.asarray(got[k]), v[env_idx[:, None], time_idx].swapaxes(0, 1), err_msg=k)
    # and the sampling path end to end, on valid windows only
    for batch in rb.sample_batches(batch_size=8, sequence_length=3, n_samples=2):
        assert batch["rgb"].shape == (3, 8, *items["rgb"])
        mirror.check_batch(batch)
        assert np.all(np.diff(np.asarray(batch["stamp"])[..., 0], axis=0) == 1)


@pytest.mark.parametrize("items", [_ITEMS["84x84x4"], _ITEMS["two_pixel_keys"]], ids=["84x84x4", "two_pixel_keys"])
def test_transition_gathers_restore_pixel_items_and_their_next_twins(items):
    rb, mirror = _filled(items, n_envs=2)  # every pixel key is an obs key: each gets a next_ twin
    flat = rb.sample_transitions(batch_size=6, n_samples=3)
    assert flat["rgb"].shape == (3, 6, *items["rgb"])
    mirror.check_batch(flat)
    data = rb.sample_transitions(batch_size=6, n_samples=3, sample_next_obs=True)
    mirror.check_batch({k: v for k, v in data.items() if not k.startswith("next_")})
    env = np.asarray(data["env"])[..., 0].astype(np.int64)
    nxt = (np.asarray(data["stamp"])[..., 0].astype(np.int64) + 1) % rb.buffer_size
    assert {k for k in data if k.startswith("next_")} == {f"next_{k}" for k in items}
    for k in items:
        np.testing.assert_array_equal(np.asarray(data[f"next_{k}"]), mirror.arrays[k][env, nxt], err_msg=k)


@pytest.mark.parametrize("n_envs", [1, 4])
def test_in_graph_draws_restore_pixel_items_from_superstep_inputs(n_envs):
    import jax

    from sheeprl_tpu.data.device_buffer import draw_sequence_batch, draw_transition_batch

    items = _ITEMS["84x84x4"]
    rb, mirror = _filled(items, n_envs)
    bufs, pos, full = rb.superstep_inputs(sequence_length=3)
    # the ring crosses jit as an argument: the item shapes ride as its static part
    draw = jax.jit(lambda bufs, pos, full, key: draw_sequence_batch(bufs, pos, full, key, 8, 3))
    batch = draw(bufs, pos, full, jax.random.PRNGKey(0))
    assert batch["rgb"].shape == (3, 8, 84, 84, 4) and str(batch["rgb"].dtype) == "uint8"
    mirror.check_batch(batch)
    assert np.all(np.diff(np.asarray(batch["stamp"])[..., 0], axis=0) == 1)
    bufs, pos, full = rb.superstep_inputs(sample_next_obs=True)
    draw = jax.jit(
        lambda bufs, pos, full, key: draw_transition_batch(bufs, pos, full, key, 8, sample_next_obs=True, obs_keys=("rgb",))
    )
    data = draw(bufs, pos, full, jax.random.PRNGKey(1))
    assert data["next_rgb"].shape == (8, 84, 84, 4) and "next_actions" not in data
    mirror.check_batch({k: v for k, v in data.items() if k != "next_rgb"})
    env = np.asarray(data["env"])[..., 0].astype(np.int64)
    nxt = (np.asarray(data["stamp"])[..., 0].astype(np.int64) + 1) % rb.buffer_size
    np.testing.assert_array_equal(np.asarray(data["next_rgb"]), mirror.arrays["rgb"][env, nxt])


@pytest.mark.parametrize("items", [_ITEMS["64x64x3"], _ITEMS["84x84x4"]], ids=["64x64x3", "84x84x4"])
def test_sharded_ring_on_the_virtual_mesh_keeps_the_stored_form_per_shard(items):
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:4]), ("data",))
    rb, mirror = _filled(items, n_envs=8, mesh=mesh, data_axis="data")
    assert rb.sharded and len(rb.devices()) == 4
    rows = -(-int(np.prod(items["rgb"])) // 128)
    assert {s.data.shape for s in rb._bufs.pixels["rgb"].addressable_shards} == {(2, rb.buffer_size + 1, rows, 128)}
    for k, v in rb.host_arrays().items():
        np.testing.assert_array_equal(v, mirror.arrays[k], err_msg=k)
    for batch in rb.sample_batches(batch_size=8, sequence_length=3, n_samples=2):
        mirror.check_batch(batch)
        # stratified draw: batch block s comes from shard s's two env rows
        assert (np.asarray(batch["env"])[0, :, 0].astype(int) // 2).tolist() == [0, 0, 1, 1, 2, 2, 3, 3]
    mirror.check_batch(rb.sample_transitions(batch_size=8, n_samples=2))


def test_flag_patches_reach_the_owning_shard_only():
    """``amend_last`` and the checkpoint's ``truncated`` fix-up are row
    updates too; on a sharded ring the shards that do not own the env send
    their copy of the patch to the scratch slot."""
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:4]), ("data",))
    rb = DeviceReplayBuffer(16, n_envs=8, obs_keys=("rgb",), seed=0, mesh=mesh, data_axis="data")
    for t in range(5):
        _step(rb, t, n_envs=8)
    before = rb.host_arrays()
    rb.amend_last(5, terminated=1.0, truncated=0.0, is_first=1.0)
    after = rb.host_arrays()
    for k in ("terminated", "is_first"):
        want = before[k].copy()
        want[5, 4] = 1.0
        np.testing.assert_array_equal(after[k], want)
    for k in ("rgb", "actions", "rewards", "truncated"):
        np.testing.assert_array_equal(after[k], before[k])
    saved = rb.flag_last_truncated()
    assert saved.shape == (8, 1) and not saved.any()
    assert rb.host_arrays()["truncated"][:, 4, 0].tolist() == [1.0] * 8
    rb.restore_last_truncated(saved)
    np.testing.assert_array_equal(rb.host_arrays()["truncated"], before["truncated"])


def test_ring_programs_update_in_place_and_copy_nothing_of_the_rings_size():
    """``ring_write`` and ``ring_gather_sequences`` compiled from shapes alone
    (nothing is allocated) for a 4-env ring of 295 MB: the write aliases the
    ring it is given, neither program asks for temporaries of a tenth of the
    ring, and the optimised HLO holds no ``copy`` (nor anything else but the
    in-place updates) of the ring's shape.

    On the CPU backend this guards the donation and the expression of the
    programs (a scatter or a transposing gather would show here too), not the
    TPU's layouts: ``tests/test_chip_compile.py`` compiles the same programs
    for a described v5e, and ``chip_smoke.py`` on the attached one."""
    import chip_smoke

    programs = chip_smoke.describe_ring_programs(4, 6_000, 6)
    ring = 4 * 6_001 * (64 * 64 * 3 + 128 * 4)
    for name, got in programs.items():
        assert got["argument_bytes"] >= ring, (name, got)
        assert got["temp_bytes"] < ring // 10, (name, got)
        assert got["relayouts"] == [], (name, got)
    assert programs["ring_write"]["alias_bytes"] >= ring, programs["ring_write"]
    # the reader does see a relayout when there is one
    hlo = "  %copy.9 = u8[4,6001,64,64,3]{3,2,4,1,0} copy(%buf.1)\n  ROOT %t = (u8[4,6001,96,128]{3,2,1,0}) tuple(%x)"
    assert chip_smoke.ring_relayouts(hlo, 6_001) == ["copy copy.9 u8[4,6001,64,64,3]{3,2,4,1,0}"]


def test_a_checkpoint_in_the_external_format_restores_and_samples_the_same_rows():
    """The pickle of a ring is ``[E, cap, *item]`` arrays and three key
    tables, as every checkpoint written before the stored form changed: such
    a state restores, samples the rows the same draws name, converts to and
    from the host buffer unchanged, and is what a new ring pickles to."""
    import copy
    import pickle

    cap, n_envs = 8, 2
    rng = np.random.default_rng(3)
    arrays = {
        "rgb": rng.integers(0, 256, (n_envs, cap, 64, 64, 3), dtype=np.uint8),
        "actions": rng.normal(size=(n_envs, cap, 2)).astype(np.float32),
        **{k: rng.normal(size=(n_envs, cap, 1)).astype(np.float32) for k in ("rewards", "terminated", "truncated", "is_first")},
    }
    old_state = {
        "buffer_size": cap,
        "n_envs": n_envs,
        "obs_keys": ("rgb",),
        "rng": np.random.default_rng(7),
        "pos": np.array([3, 5], np.int64),
        "full": np.array([True, False]),
        "small_slices": {"actions": (0, 2, (2,)), "is_first": (2, 3, (1,)), "rewards": (3, 4, (1,)), "terminated": (4, 5, (1,)), "truncated": (5, 6, (1,))},
        "small_keys": ("actions", "is_first", "rewards", "terminated", "truncated"),
        "pixel_keys": ("rgb",),
        "arrays": arrays,
    }

    def restored():
        rb = DeviceReplayBuffer.__new__(DeviceReplayBuffer)  # what pickle.loads does
        rb.__setstate__(copy.deepcopy(old_state))
        return rb.restore_to_device()

    rb = restored()
    assert rb._bufs.pixels["rgb"].shape == (n_envs, cap + 1, 96, 128)
    env_idx, starts = restored().draw_indices(6, 3)
    (batch,) = rb.sample_batches(batch_size=6, sequence_length=3, n_samples=1)
    time_idx = (starts[:, None] + np.arange(3)) % cap
    for k, v in arrays.items():
        np.testing.assert_array_equal(np.asarray(batch[k]), v[env_idx[:, None], time_idx].swapaxes(0, 1), err_msg=k)
    # out again in the same external format, key tables included
    state = pickle.loads(pickle.dumps(rb)).__getstate__()
    assert set(state) == set(old_state)
    for k in ("small_slices", "small_keys", "pixel_keys"):
        assert state[k] == old_state[k]
    for k, v in arrays.items():
        assert state["arrays"][k].shape == v.shape and state["arrays"][k].dtype == v.dtype
        np.testing.assert_array_equal(state["arrays"][k], v, err_msg=k)
    host = rb.to_host_buffer()
    assert np.asarray(host.buffer[1]["rgb"]).shape == (cap, 1, 64, 64, 3)
    back = DeviceReplayBuffer.from_host_buffer(host)
    assert back._pos.tolist() == [3, 5] and back._full.tolist() == [True, False]
    for k, v in arrays.items():
        np.testing.assert_array_equal(back.host_arrays()[k], v, err_msg=k)
