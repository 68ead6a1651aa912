"""Hermetic launcher for the AOT executable-cache serving drills.

The drills in test_aotcache_serving.py serialize real XLA executables and
load them back; that round trip is only sound in a process where NOTHING was
ever deserialized from the warm cross-run trace cache (see that module's
docstring — a deserialized executable registers generically-named kernel
symbols process-wide, and the cache's on/off/dir state latches at the first
compile). A shared pytest session cannot guarantee that: even collection
imports compile. So each launcher here boots a fresh interpreter with the
persistent cache switched off (``JAX_ENABLE_COMPILATION_CACHE=false``) and
runs the real drills there, asserting the child's verdict.
"""

import os
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.serve]

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DRILLS = os.path.join("tests", "test_serve", "test_aotcache_serving.py")


def _run_hermetic(extra_args, timeout=420):
    env = dict(os.environ)
    env["SHEEPRL_TPU_AOT_HERMETIC"] = "1"
    # a clean room: the child runs with the persistent cache switched off
    # from its first import on (JAX's own switch), so nothing it compiles is
    # ever read back from the shared warm cache
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "pytest",
            DRILLS,
            "-q",
            "-p",
            "no:cacheprovider",
            "-p",
            "no:randomly",
            *extra_args,
        ],
        cwd=REPO_ROOT,
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    assert proc.returncode == 0, (
        f"hermetic AOT drills failed (rc={proc.returncode}):\n"
        f"{proc.stdout[-4000:]}\n{proc.stderr[-2000:]}"
    )
    return proc.stdout


def test_aot_roundtrip_drills_hermetic():
    out = _run_hermetic(["-m", "not slow"])
    assert "3 passed" in out, out[-2000:]


@pytest.mark.slow
@pytest.mark.fleet
def test_aot_autoscale_drill_hermetic():
    out = _run_hermetic(["-m", "slow"], timeout=540)
    assert "1 passed" in out, out[-2000:]
