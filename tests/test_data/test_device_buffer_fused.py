"""In-graph replay sampling kernels (data/device_buffer.py pure functions):
validity-mask parity with the host-side `_valid_starts`/`_valid_items`
oracles across every ring phase, wrap-around gather parity with the host
`SequentialReplayBuffer` storage for the SAME indices, and the
`superstep_inputs` contract the fused training supersteps consume."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.data.buffers import SequentialReplayBuffer
from sheeprl_tpu.data.device_buffer import (
    DeviceReplayBuffer,
    draw_sequence_batch,
    draw_transition_batch,
    gather_sequences,
    sequence_start_mask,
    transition_item_mask,
)

CAP = 8
N_ENVS = 3


def _step_data(t, n_envs=N_ENVS):
    return {
        "observations": np.full((1, n_envs, 2), t, np.float32),
        "actions": np.full((1, n_envs, 1), t, np.float32),
        "rewards": np.full((1, n_envs, 1), t, np.float32),
        "terminated": np.zeros((1, n_envs, 1), np.float32),
        "truncated": np.zeros((1, n_envs, 1), np.float32),
        "is_first": np.zeros((1, n_envs, 1), np.float32),
    }


def _fresh(cap=CAP, n_envs=N_ENVS, seed=0):
    return DeviceReplayBuffer(cap, n_envs=n_envs, obs_keys=("observations",), seed=seed)


@pytest.mark.parametrize("span", [1, 2, 4])
def test_sequence_mask_matches_host_valid_starts_at_every_fill_level(span):
    """The on-device mask must agree with the host `_valid_starts` oracle
    through the whole ring life cycle: filling, exactly full, wrapped."""
    rb = _fresh()
    for t in range(2 * CAP + 3):
        rb.add(_step_data(t))
        mask = np.asarray(
            sequence_start_mask(
                jnp.asarray(rb._pos, jnp.int32), jnp.asarray(rb._full), CAP, span
            )
        )
        for env in range(N_ENVS):
            expected = np.zeros(CAP, bool)
            expected[rb._valid_starts(env, span)] = True
            np.testing.assert_array_equal(
                mask[env], expected, err_msg=f"t={t} env={env} span={span}"
            )


@pytest.mark.parametrize("sample_next_obs", [False, True])
def test_transition_mask_matches_host_valid_items_at_every_fill_level(sample_next_obs):
    rb = _fresh()
    for t in range(2 * CAP + 3):
        rb.add(_step_data(t))
        mask = np.asarray(
            transition_item_mask(
                jnp.asarray(rb._pos, jnp.int32), jnp.asarray(rb._full), CAP, sample_next_obs
            )
        )
        for env in range(N_ENVS):
            expected = np.zeros(CAP, bool)
            expected[rb._valid_items(env, sample_next_obs)] = True
            np.testing.assert_array_equal(
                mask[env], expected, err_msg=f"t={t} env={env} next_obs={sample_next_obs}"
            )


def test_wraparound_sequence_gather_matches_host_buffer_for_same_indices():
    """Feed the SAME step stream to the device ring and to a host
    `SequentialReplayBuffer`; a gather of explicitly wrapped windows (starts
    behind the cursor, time indices wrapping mod capacity) must return
    identical values from both."""
    dev = _fresh()
    host = SequentialReplayBuffer(CAP, n_envs=N_ENVS)
    for t in range(2 * CAP + 5):  # cursor mid-ring, every slot overwritten once
        data = _step_data(t)
        dev.add(data)
        host.add(data)

    seq_len = 3
    # every valid start of every env — includes the wrapped region behind the
    # cursor; windows starting at CAP-1 wrap to slot 0
    env_idx, starts = [], []
    for env in range(N_ENVS):
        for s in dev._valid_starts(env, seq_len):
            env_idx.append(env)
            starts.append(int(s))
    env_idx = np.asarray(env_idx, np.int32)
    starts = np.asarray(starts, np.int32)
    assert (starts + seq_len > CAP).any(), "no wrapping window in the index set"

    offsets = np.arange(seq_len, dtype=np.int32)
    time_idx = (starts[:, None] + offsets[None, :]) % CAP
    got = gather_sequences(dev._bufs, jnp.asarray(env_idx), jnp.asarray(time_idx))

    for k, arr in host.buffer.items():
        # host layout is [time, env, ...]; device gather returns [T, B, ...]
        expected = np.asarray(arr)[time_idx, env_idx[:, None]].swapaxes(0, 1)
        np.testing.assert_array_equal(np.asarray(got[k]), expected, err_msg=k)

    # and the windows are temporally contiguous despite the wrap: the step
    # counter stored in every slot increases by exactly 1 along T
    t_vals = np.asarray(got["actions"])[..., 0]  # [T, B]
    np.testing.assert_array_equal(np.diff(t_vals, axis=0), 1)


def test_draw_sequence_batch_in_graph_draws_valid_windows():
    """The fully in-graph draw (mask -> indices -> gather, jitted as one
    program like a fused superstep does) only ever returns windows that are
    contiguous and inside the valid set."""
    rb = _fresh()
    for t in range(2 * CAP + 5):
        rb.add(_step_data(t))

    bufs, pos, full = rb.superstep_inputs(sequence_length=4)
    draw = jax.jit(lambda key: draw_sequence_batch(bufs, pos, full, key, 16, 4))
    for s in range(5):
        batch = draw(jax.random.PRNGKey(s))
        t_vals = np.asarray(batch["actions"])[..., 0]  # [T, B]
        np.testing.assert_array_equal(np.diff(t_vals, axis=0), 1)
        # never the slot being written next (the cursor) as a window interior
        assert t_vals.min() >= 2 * CAP + 5 - CAP


def test_draw_transition_batch_next_obs_is_the_successor_step():
    rb = _fresh()
    for t in range(CAP + 3):
        rb.add(_step_data(t))
    bufs, pos, full = rb.superstep_inputs(sample_next_obs=True)
    batch = jax.jit(
        lambda key: draw_transition_batch(
            bufs, pos, full, key, 32, sample_next_obs=True, obs_keys=("observations",)
        )
    )(jax.random.PRNGKey(0))
    obs = np.asarray(batch["observations"])[..., 0]
    nxt = np.asarray(batch["next_observations"])[..., 0]
    np.testing.assert_array_equal(nxt, obs + 1)


def test_superstep_inputs_validates_like_the_sampling_paths():
    rb = _fresh()
    with pytest.raises(RuntimeError, match="has not been initialized"):
        rb.superstep_inputs(sequence_length=2)
    rb.add(_step_data(0))
    with pytest.raises(ValueError, match="Cannot sample a sequence of length"):
        rb.superstep_inputs(sequence_length=4)
    with pytest.raises(ValueError, match="next observations"):
        rb.superstep_inputs(sample_next_obs=True)
    rb.add(_step_data(1))
    bufs, pos, full = rb.superstep_inputs(sequence_length=2)
    # the ring itself, in its stored form: the item shapes ride with it
    assert bufs is rb._bufs and set(bufs.layout.keys) == set(_step_data(0))
    assert bufs.smalls.shape == (N_ENVS, CAP + 1, 128) and not bufs.pixels
    np.testing.assert_array_equal(np.asarray(pos), rb._pos.astype(np.int32))
    np.testing.assert_array_equal(np.asarray(full), rb._full)
    # the cursor snapshot must not alias the live host mirrors (add() mutates
    # them in place while a superstep may still be queued)
    before = np.asarray(pos).copy()
    for t in range(2, 6):
        rb.add(_step_data(t))
    np.testing.assert_array_equal(np.asarray(pos), before)
