"""Driver-entry tests.

``dryrun_multichip`` is about virtual CPU devices, and a process that has
touched jax holds the attached chip. The contract under test: the parent
process NEVER imports jax; the whole dry run happens in a fresh
``JAX_PLATFORMS=cpu`` child.
"""

import os
import pytest
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_PARENT_BLOCKER = r"""
import sys

class _NoJax:
    # ANY jax import in this process fails loudly: the parent code path
    # never needs jax.
    def find_spec(self, name, path=None, target=None):
        if name == "jax" or name.startswith("jax."):
            raise ImportError("parent process must not import jax")
        return None

sys.meta_path.insert(0, _NoJax())

import importlib.util

spec = importlib.util.spec_from_file_location("__graft_entry__", sys.argv[1])
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
mod.dryrun_multichip(2)
print("PARENT-NEVER-IMPORTED-JAX")
"""


@pytest.mark.slow
def test_dryrun_parent_never_imports_jax():
    env = dict(os.environ)
    env.pop("_SHEEPRL_TPU_DRYRUN_CHILD", None)
    # core DP topology only: the decoupled/elastic extras have their own
    # tests (test_sac_decoupled, test_elastic_resume) and would add ~6 min
    env["SHEEPRL_TPU_DRYRUN_CORE_ONLY"] = "1"
    proc = subprocess.run(
        [sys.executable, "-c", _PARENT_BLOCKER, os.path.join(REPO_ROOT, "__graft_entry__.py")],
        env=env,
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=480,
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout[-2000:]}\nstderr:\n{proc.stderr[-2000:]}"
    assert "PARENT-NEVER-IMPORTED-JAX" in proc.stdout
    assert "fused train step OK" in proc.stdout
    # the K=2 fused superstep window over the sharded ring compiled and ran
    assert "fused superstep OK" in proc.stdout
    # the fused on-policy PPO superstep (scanned JaxCartPole rollout + GAE +
    # fused update, envs sharded over the mesh) compiled and ran too
    assert "fused on-policy PPO superstep OK" in proc.stdout
