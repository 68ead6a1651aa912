"""Test harness setup (reference analogue: tests/conftest.py).

Runs everything on CPU with 8 virtual XLA devices so mesh/collective code paths
are exercised without TPU hardware — the JAX equivalent of the reference's
2-process gloo trick (SURVEY.md §4.2).  Must run before jax initializes.
"""

import os
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
# what is worth persisting on the CPU backend: programs that took half a
# second or more to compile (inherited by every child the suite spawns)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
# Redirect the run registry's default away from the repo's real RUNS.jsonl:
# every CLI run a test launches (in-process or as a subprocess — both inherit
# this env var) would otherwise append evidence records to the checked-in
# registry. Set at import time so _no_env_leaks (which snapshots per test)
# sees a constant value. Tests that assert on registry contents override via
# metric.telemetry.runs_jsonl, which takes precedence over the env var.
os.environ.setdefault(
    "SHEEPRL_TPU_RUNS_JSONL",
    os.path.join(tempfile.mkdtemp(prefix="sheeprl_tpu_test_runs_"), "RUNS.jsonl"),
)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# The persistent XLA compilation cache, placed by the program's own rule
# (JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache): this process
# AND every CLI child the suite spawns resolve the same directory, so identical
# tiny training graphs compile once per checkout instead of once per
# interpreter. JAX's own JAX_ENABLE_COMPILATION_CACHE=false turns it off.
from sheeprl_tpu.parallel.fabric import configure_compilation_cache  # noqa: E402

configure_compilation_cache()

import pytest  # noqa: E402


@pytest.fixture()
def tmp_logdir(tmp_path):
    return str(tmp_path / "logs")


def run_multi_process(code: str, argv=(), cwd=None, extra_env=None, timeout=540, nproc=2, device_count=2):
    """Launch ``code`` in ``nproc`` real ``jax.distributed`` CPU processes
    (TEST_COORD/TEST_NPROC/TEST_PID env contract), each with ``device_count``
    virtual CPU devices, and return their outputs, asserting all exit 0.
    Workers are killed on failure/timeout so a wedged group cannot leak into
    later tests. Shared by the decoupled-topology and collective-plane
    tests."""
    import socket
    import subprocess
    import sys

    # the gloo CPU collectives client must be selected before the worker's
    # jax.distributed.initialize — without it the CPU backend refuses
    # cross-process computations. Prepended here so every multi-process
    # worker snippet gets it (the production path sets the same knob in
    # Fabric._maybe_init_distributed).
    code = (
        "import jax as _jax_boot\n"
        '_jax_boot.config.update("jax_cpu_collectives_implementation", "gloo")\n'
    ) + code

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = []
    try:
        for pid in range(nproc):
            env = dict(os.environ)
            env.pop("SHEEPRL_TPU_COORDINATOR", None)
            env.pop("SHEEPRL_TPU_NUM_PROCESSES", None)
            env.pop("SHEEPRL_TPU_PROCESS_ID", None)
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={device_count}"
            # the persistent trace cache is unusable in gloo worker groups:
            # it neither keys on process topology (a single-process run of
            # the same global program poisons it) nor round-trips a gloo
            # executable from a warm cache of the SAME topology — either way
            # the deserialized collectives silently compute garbage. Fabric
            # refuses it too (configure_compilation_cache); switching it off
            # here also covers workers that call jax.distributed.initialize
            # directly.
            env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
            env["TEST_COORD"] = f"127.0.0.1:{port}"
            env["TEST_NPROC"] = str(nproc)
            env["TEST_PID"] = str(pid)
            env["PYTHONPATH"] = os.pathsep.join(p for p in (repo_root, env.get("PYTHONPATH")) if p)
            env.update(extra_env or {})
            procs.append(
                subprocess.Popen(
                    [sys.executable, "-c", code, *argv],
                    env=env,
                    cwd=cwd,
                    stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT,
                    text=True,
                )
            )
        outs = [p.communicate(timeout=timeout)[0] for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {pid} failed:\n{out[-4000:]}"
    return outs


def run_two_process(code: str, argv=(), cwd=None, extra_env=None, timeout=540):
    return run_multi_process(code, argv=argv, cwd=cwd, extra_env=extra_env, timeout=timeout, nproc=2)


@pytest.fixture()
def multichip_run():
    """Run a module-qualified helper over a virtual ``n_devices`` CPU mesh in
    a FRESH subprocess (the ``__graft_entry__`` ``_SHEEPRL_TPU_DRYRUN_CHILD``
    pattern): this pytest process is pinned to 8 virtual devices at import
    time, so tests that need a different mesh size (e.g. the 4-device vs
    1-device sharded-superstep equivalence pair, marked ``multichip``) fork a
    child with its own ``--xla_force_host_platform_device_count``. Usage::

        out = multichip_run("tests.test_parallel.test_x:helper", 4, str(tmp))

    ``target`` is ``module:function``; extra args are passed through as
    strings. Returns the child's combined stdout/stderr, asserting rc == 0."""
    import subprocess
    import sys

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import sys, importlib, jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "mod, fn = sys.argv[1].split(':')\n"
        "getattr(importlib.import_module(mod), fn)(*sys.argv[2:])\n"
    )

    def run(target: str, n_devices: int, *argv, timeout: int = 540, extra_env=None):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={int(n_devices)}"
        env["_SHEEPRL_TPU_DRYRUN_CHILD"] = "1"
        env["PYTHONPATH"] = os.pathsep.join(p for p in (repo_root, env.get("PYTHONPATH")) if p)
        env.update(extra_env or {})
        proc = subprocess.run(
            [sys.executable, "-c", code, target, *map(str, argv)],
            env=env,
            cwd=repo_root,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            timeout=timeout,
        )
        assert proc.returncode == 0, f"multichip child ({target}, {n_devices} devices) failed:\n{proc.stdout[-4000:]}"
        return proc.stdout

    return run


@pytest.fixture(autouse=True)
def _no_env_leaks():
    """Fail a test that leaks SHEEPRL_TPU_* env vars (reference conftest.py:20-61)."""
    before = {k: v for k, v in os.environ.items() if k.startswith("SHEEPRL_TPU")}
    yield
    after = {k: v for k, v in os.environ.items() if k.startswith("SHEEPRL_TPU")}
    assert before == after, f"test leaked env vars: {set(after) ^ set(before)}"


@pytest.fixture(autouse=True)
def _reset_observability_switches():
    """run_algorithm() flips the CLASS-level kill-switches
    (MetricAggregator.disabled / timer.disabled) from cfg.metric.log_level;
    restore them so a log_level=0 CLI test cannot poison later metric tests
    (the reference resets global state per test the same way,
    conftest.py:64-69)."""
    from sheeprl_tpu.utils.metric import MetricAggregator
    from sheeprl_tpu.utils.timer import timer

    agg_disabled = MetricAggregator.disabled
    timer_disabled = timer.disabled
    yield
    MetricAggregator.disabled = agg_disabled
    timer.disabled = timer_disabled


def find_checkpoints(base):
    """Every checkpoint under ``base`` (pickle .ckpt files and orbax .ckpt
    directories), oldest first — shared by the resume/decoupled tests."""
    found = []
    for root, dirs, files in os.walk(base):
        found += [os.path.join(root, f) for f in files if f.endswith(".ckpt")]
        found += [os.path.join(root, d) for d in dirs if d.endswith(".ckpt")]
    return sorted(set(found), key=os.path.getmtime)
