"""Native C++ gather vs the numpy reference path."""

import numpy as np
import pytest

from sheeprl_tpu import native
from sheeprl_tpu.data.buffers import SequentialReplayBuffer

pytestmark = pytest.mark.skipif(not native.available(), reason="no C++ toolchain")


def test_gather_sequences_matches_numpy():
    rng = np.random.default_rng(0)
    size, n_envs, L, n_samples, batch = 37, 3, 5, 2, 4
    src = rng.normal(size=(size, n_envs, 6, 2)).astype(np.float32)
    starts = rng.integers(0, size, size=(n_samples * batch,))
    envs = rng.integers(0, n_envs, size=(n_samples * batch,))

    got = native.gather_sequences(src, starts, envs, L, n_samples, batch)
    assert got is not None and got.shape == (n_samples, L, batch, 6, 2)
    assert got.flags.c_contiguous

    idxes = (starts[:, None] + np.arange(L)[None, :]) % size
    want = src[idxes, np.repeat(envs[:, None], L, axis=1)]
    want = want.reshape(n_samples, batch, L, 6, 2).swapaxes(1, 2)
    np.testing.assert_array_equal(got, want)

    # shifted (next-obs) window
    got1 = native.gather_sequences(src, starts, envs, L, n_samples, batch, shift=1)
    want1 = src[(idxes + 1) % size, np.repeat(envs[:, None], L, axis=1)]
    want1 = want1.reshape(n_samples, batch, L, 6, 2).swapaxes(1, 2)
    np.testing.assert_array_equal(got1, want1)


def test_gather_sequences_wraparound():
    size, n_envs, L = 8, 2, 6
    src = np.arange(size * n_envs, dtype=np.int64).reshape(size, n_envs)
    starts = np.array([5])  # rows 5,6,7,0,1,2
    envs = np.array([1])
    got = native.gather_sequences(src, starts, envs, L, 1, 1)
    want = src[(5 + np.arange(L)) % size, 1].reshape(1, L, 1)
    np.testing.assert_array_equal(got, want)


def test_gather_rows_matches_numpy():
    rng = np.random.default_rng(1)
    size, n_envs = 19, 4
    src = rng.integers(0, 255, size=(size, n_envs, 3, 3), dtype=np.int64).astype(np.uint8)
    rows = rng.integers(0, size, size=(11,))
    envs = rng.integers(0, n_envs, size=(11,))
    got = native.gather_rows(src, rows, envs)
    np.testing.assert_array_equal(got, src[rows, envs])


def test_replay_buffer_sample_native_equals_numpy(monkeypatch):
    from sheeprl_tpu.data.buffers import ReplayBuffer

    rb = ReplayBuffer(buffer_size=11, n_envs=2, obs_keys=("obs",))
    rng = np.random.default_rng(5)
    for _ in range(17):
        rb.add({"obs": rng.normal(size=(1, 2, 3)).astype(np.float32)})

    kwargs = dict(batch_size=6, n_samples=3, sample_next_obs=True)
    rb._rng = np.random.default_rng(9)
    with_native = rb.sample(**kwargs)
    rb._rng = np.random.default_rng(9)
    monkeypatch.setattr(native, "gather_rows", lambda *a, **k: None)
    without = rb.sample(**kwargs)
    assert set(with_native) == set(without)
    for k in with_native:
        np.testing.assert_array_equal(with_native[k], without[k])


def test_object_dtype_falls_back():
    src = np.empty((4, 2), dtype=object)
    src[:] = [["a", "b"]] * 4
    assert native.gather_rows(src, np.array([0]), np.array([1])) is None
    assert native.gather_sequences(src, np.array([0]), np.array([1]), 2, 1, 1) is None


@pytest.mark.parametrize("dtype", [np.uint8, np.float32, np.bool_])
def test_buffer_sample_native_equals_numpy(monkeypatch, dtype):
    """SequentialReplayBuffer.sample gives bit-identical batches with the
    native gather on and off (same RNG stream)."""
    rb = SequentialReplayBuffer(buffer_size=23, n_envs=3, obs_keys=("obs",))
    rng = np.random.default_rng(2)
    for _ in range(31):  # wraps
        rb.add(
            {
                "obs": rng.normal(size=(1, 3, 4)).astype(np.float32),
                "flag": rng.integers(0, 2, size=(1, 3, 1)).astype(dtype),
            }
        )

    kwargs = dict(batch_size=4, n_samples=2, sequence_length=5, sample_next_obs=True)
    rb._rng = np.random.default_rng(7)
    with_native = rb.sample(**kwargs)

    rb._rng = np.random.default_rng(7)
    monkeypatch.setattr(native, "gather_sequences", lambda *a, **k: None)
    without = rb.sample(**kwargs)

    assert set(with_native) == set(without)
    for k in with_native:
        np.testing.assert_array_equal(with_native[k], without[k])
        assert with_native[k].dtype == without[k].dtype


def test_build_dir_is_fixed_inside_the_checkout_and_status_names_the_path(monkeypatch, tmp_path):
    """The .so is built from the committed gather.cpp into one fixed
    git-ignored directory of the checkout (SHEEPRL_TPU_NATIVE_CACHE moves
    it), and ``status()`` — folded into every run record — says which gather
    ran and, for the numpy path, why."""
    import os

    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    monkeypatch.delenv("SHEEPRL_TPU_NATIVE_CACHE", raising=False)
    assert native._build_dir() == os.path.join(repo, ".native_cache") == native.REPO_NATIVE_CACHE_DIR
    monkeypatch.setenv("SHEEPRL_TPU_NATIVE_CACHE", str(tmp_path))
    assert native._build_dir() == str(tmp_path)
    assert native.status() == "native"  # this module is skipped when the build is unavailable
    # a load that fails lands on numpy and says why
    monkeypatch.setattr(native, "_LIB", None)
    monkeypatch.setattr(native, "_TRIED", False)
    monkeypatch.setenv("SHEEPRL_TPU_DISABLE_NATIVE", "1")
    assert native.gather_rows(np.zeros((2, 1)), np.zeros(1, np.int64), np.zeros(1, np.int64)) is None
    assert native.status() == "numpy (SHEEPRL_TPU_DISABLE_NATIVE is set)"
