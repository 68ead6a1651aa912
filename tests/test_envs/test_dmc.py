"""DMC adapter specs (reference: sheeprl/envs/dmc.py contract)."""

import numpy as np
import pytest

from sheeprl_tpu.utils.imports import _IS_DMC_AVAILABLE

if not _IS_DMC_AVAILABLE:
    pytest.skip("dm_control not installed", allow_module_level=True)

import os

# headless rendering backend (the adapter defaults to EGL too)
os.environ.setdefault("MUJOCO_GL", "egl")


@pytest.fixture(scope="module", autouse=True)
def _tensorboard_before_egl():
    """In one process, TensorFlow's extension modules (which the loggers'
    ``torch.utils.tensorboard`` import pulls in) segfault at import once
    mujoco's EGL stack is loaded. A CLI run builds its logger before its envs,
    so the program never meets that order; an xdist worker that runs this file
    and later its first CLI test does. Load them in the program's order."""
    import torch.utils.tensorboard  # noqa: F401


@pytest.fixture(scope="module")
def vector_env():
    from sheeprl_tpu.envs.dmc import DMCWrapper

    return DMCWrapper("cartpole", "balance", from_pixels=False, from_vectors=True, seed=0)


def test_vector_obs_space(vector_env):
    obs, _ = vector_env.reset(seed=0)
    assert set(obs.keys()) == {"state"}
    assert obs["state"].shape == vector_env.observation_space["state"].shape


def test_action_space_normalized(vector_env):
    assert (vector_env.action_space.low == -1).all()
    assert (vector_env.action_space.high == 1).all()


def test_step_contract(vector_env):
    vector_env.reset(seed=0)
    obs, reward, terminated, truncated, info = vector_env.step(vector_env.action_space.sample())
    assert np.isfinite(reward)
    assert "discount" in info and "internal_state" in info
    assert not terminated  # first steps of cartpole-balance never terminate


def test_time_limit_truncates(vector_env):
    vector_env.reset(seed=0)
    terminated = truncated = False
    steps = 0
    while not (terminated or truncated) and steps < 2000:
        _, _, terminated, truncated, _ = vector_env.step(vector_env.action_space.sample())
        steps += 1
    assert truncated and not terminated  # dm_control ends by time limit


def test_both_false_raises():
    from sheeprl_tpu.envs.dmc import DMCWrapper

    with pytest.raises(ValueError):
        DMCWrapper("cartpole", "balance", from_pixels=False, from_vectors=False)


@pytest.mark.skipif(os.environ.get("SHEEPRL_TPU_SKIP_RENDER_TESTS") == "1", reason="no GL")
def test_pixel_obs_nhwc():
    # EGL rendering segfaults when sharing a process with jax/torch GL state,
    # so probe the pixel path in a clean subprocess
    import subprocess
    import sys

    code = (
        "from sheeprl_tpu.envs.dmc import DMCWrapper\n"
        "import numpy as np\n"
        "try:\n"
        "    env = DMCWrapper('cartpole', 'balance', from_pixels=True, from_vectors=True,"
        " height=32, width=32, seed=0)\n"
        "    obs, _ = env.reset(seed=0)\n"
        "except Exception as e:\n"
        "    print('BACKEND_UNAVAILABLE:', e)\n"
        "    raise SystemExit(0)\n"
        "assert obs['rgb'].shape == (32, 32, 3), obs['rgb'].shape\n"
        "assert obs['rgb'].dtype == np.uint8\n"
        "assert obs['state'].ndim == 1\n"
        "print('PIXEL_OK')\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=120,
        env={**os.environ, "MUJOCO_GL": "egl", "JAX_PLATFORMS": "cpu"},
    )
    if "BACKEND_UNAVAILABLE" in proc.stdout:
        pytest.skip(f"mujoco rendering unavailable: {proc.stdout[-200:]}")
    # a real contract violation (wrong layout/dtype) must FAIL, not skip
    assert proc.returncode == 0 and "PIXEL_OK" in proc.stdout, proc.stdout + proc.stderr[-500:]
