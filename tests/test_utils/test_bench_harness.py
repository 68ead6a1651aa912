"""bench.py's contract with a machine that has no chip, and its telemetry
readers.

``python bench.py`` prints device numbers, so where the probed platform is not
``tpu`` it exits non-zero and prints no number — there is no cached or CPU
number to fall back on. Its parent process never imports jax: a chip belongs
to one process at a time, and the workloads are children.
"""

import json
import os
import subprocess
import sys
import time

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO_ROOT, "bench.py")


def _run_bench_without_chip(argv=None, timeout=180):
    """Run bench (directly, or via a wrapper ``argv``) where JAX is held to
    the CPU; return the finished process."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        argv or [sys.executable, BENCH],
        env=env,
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=timeout,
    )


def _assert_no_number(proc):
    assert proc.returncode != 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-2000:]}"
    assert not [l for l in proc.stdout.splitlines() if l.lstrip().startswith("{")], proc.stdout
    assert "'cpu'" in proc.stderr and "no number is printed" in proc.stderr, proc.stderr[-2000:]


def test_bench_exits_nonzero_and_prints_no_number_without_a_chip():
    """No chip, no number: not a cached one, not a CPU one, and not exit 0."""
    t0 = time.monotonic()
    _assert_no_number(_run_bench_without_chip())
    assert time.monotonic() - t0 < 120  # the probe is one short child, no wait loop


_NOJAX_BENCH_PARENT = r"""
import sys

class _NoJax:
    # a parent that has touched jax holds the chip, and its workload children
    # then fail or hang: the bench PARENT must never import jax at all
    def find_spec(self, name, path=None, target=None):
        if name == "jax" or name.startswith("jax."):
            raise ImportError("bench parent must not import jax")
        return None

sys.meta_path.insert(0, _NoJax())
import importlib.util

spec = importlib.util.spec_from_file_location("bench", sys.argv[1])
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
mod.main()
"""


def test_bench_parent_never_imports_jax():
    """``main()`` driven with jax imports POISONED in the parent process: it
    still reaches its verdict (here: no chip, so no number), because only the
    probe and the workloads — separate interpreters — import jax."""
    _assert_no_number(_run_bench_without_chip(argv=[sys.executable, "-c", _NOJAX_BENCH_PARENT, BENCH]))


@pytest.mark.parametrize("script", ["bench.py", "benchmarks/serve_cold_start.py", "__graft_entry__.py"])
def test_launchers_of_chip_children_load_without_jax(script):
    """Every script that starts children which need the chip loads — module
    level and all — with jax imports poisoned: a parent that had touched jax
    would hold the chip and its children would fail or hang."""
    code = _NOJAX_BENCH_PARENT.replace("mod.main()", 'print("LOADED-WITHOUT-JAX")')
    proc = subprocess.run(
        [sys.executable, "-c", code, os.path.join(REPO_ROOT, script)],
        capture_output=True,
        text=True,
        timeout=60,
        cwd=REPO_ROOT,
    )
    assert proc.returncode == 0 and "LOADED-WITHOUT-JAX" in proc.stdout, proc.stderr[-2000:]


def test_assemble_builds_the_record_from_both_workloads():
    sys.path.insert(0, REPO_ROOT)
    try:
        import bench
    finally:
        sys.path.pop(0)

    rec = bench._assemble(
        {"steps": 2048, "seconds": 10.0, "mfu": 0.25, "flops_per_train_step": 1e9},
        {"steps": 32768, "seconds": 4.0},
    )
    assert rec["value"] == 204.8 and rec["mfu"] == 0.25
    assert rec["vs_baseline"] == round(204.8 / bench._DV3_TORCH_CPU_SPS, 3)
    assert rec["secondary"]["value"] == 8192.0
    assert "outage" not in rec and "stale" not in rec


def _write_telemetry(path):
    """Synthetic telemetry.jsonl in the documented schema (howto/telemetry.md),
    including a torn final line (run killed mid-flush)."""
    events = [
        {"event": "run_start", "t": 0.0, "step": 0, "process_index": 0, "backend": "cpu"},
        {
            "event": "device_poll",
            "t": 0.1,
            "step": 0,
            "process_index": 0,
            "devices": [{"id": 0, "kind": "TPU v5e", "platform": "tpu", "peak_bytes_in_use": 123456}],
        },
        {"event": "compile", "t": 0.2, "step": 0, "process_index": 0, "name": "train_fn", "phase": "lower", "dur": 1.5, "post_warm": False},
        {"event": "compile", "t": 0.3, "step": 0, "process_index": 0, "name": "train_fn", "phase": "backend", "dur": 3.0, "post_warm": False},
        {"event": "span", "t": 1.0, "step": 10, "process_index": 0, "name": "Time/train_time", "t_start": 0.5, "dur": 0.5},
        {"event": "span", "t": 2.0, "step": 20, "process_index": 0, "name": "Time/train_time", "t_start": 1.5, "dur": 0.5},
        {"event": "compile", "t": 2.5, "step": 20, "process_index": 0, "name": "train_fn", "phase": "lower", "dur": 1.0, "post_warm": True},
        {
            "event": "heartbeat", "t": 3.0, "step": 1000, "process_index": 0,
            "window_env_steps": 1000, "window_env_time": 2.0,
            "window_train_steps": 400, "window_train_time": 1.0,
            "mfu": 0.10, "train_flops_per_sec": 1.0e12,
        },
        {
            "event": "heartbeat", "t": 6.0, "step": 2000, "process_index": 0,
            "window_env_steps": 1000, "window_env_time": 2.0,
            "window_train_steps": 400, "window_train_time": 3.0,
            "mfu": 0.30, "train_flops_per_sec": 3.0e12,
        },
    ]
    with open(path, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")
        f.write('{"event": "heartbe')  # torn tail: must be skipped, not fatal


def test_telemetry_summary_from_jsonl(tmp_path):
    sys.path.insert(0, REPO_ROOT)
    try:
        import bench
    finally:
        sys.path.pop(0)

    path = str(tmp_path / "telemetry.jsonl")
    _write_telemetry(path)
    s = bench.telemetry_summary(path)
    assert s["heartbeats"] == 2
    assert s["sps_env"] == 2000 / 4.0
    assert s["sps_train"] == 800 / 4.0
    assert s["duty_cycle_train"] == 4.0 / 8.0
    # train_time-weighted: (1*0.1 + 3*0.3) / 4
    assert abs(s["mfu"] - 0.25) < 1e-9
    assert abs(s["train_flops_per_sec"] - 2.5e12) < 1e3
    assert s["spans"]["Time/train_time"] == {"count": 2, "total_s": 1.0}
    # only phase=lower counts as a compile; the backend phase is not double-counted
    assert s["compiles"] == 2
    assert s["recompiles_post_warm"] == 1
    assert s["device_polls"] == 1
    assert s["hbm_peak_bytes"] == 123456


def test_telemetry_summary_cli(tmp_path):
    """`bench.py --telemetry PATH` prints one JSON summary line."""
    path = str(tmp_path / "telemetry.jsonl")
    _write_telemetry(path)
    proc = subprocess.run(
        [sys.executable, BENCH, "--telemetry", path],
        capture_output=True,
        text=True,
        timeout=60,
        cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["sps_env"] == 500.0 and rec["heartbeats"] == 2


def test_telemetry_summary_needs_no_jax(tmp_path):
    """The summary runs with jax imports poisoned — the bench parent must
    stay jax-free even when digesting telemetry."""
    path = str(tmp_path / "telemetry.jsonl")
    _write_telemetry(path)
    code = _NOJAX_BENCH_PARENT.replace("mod.main()", "") + (
        "import json\n"
        "print(json.dumps(mod.telemetry_summary(sys.argv[2])))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code, BENCH, path],
        capture_output=True,
        text=True,
        timeout=60,
        cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["sps_train"] == 200.0


def test_read_probe_window_never_opened_is_distinct(tmp_path):
    """The probe's 'window never opened' record must raise a targeted config
    error, not be mistaken for a throughput record."""
    sys.path.insert(0, REPO_ROOT)
    try:
        import bench
    finally:
        sys.path.pop(0)

    path = str(tmp_path / "probe.json")
    with open(path, "w") as f:
        json.dump({"error": "window_never_opened", "detail": "run shorter than warmup"}, f)
    with pytest.raises(RuntimeError, match="before its steady-state window opened"):
        bench._read_probe(path, "dv3")


def test_dispatch_stats_prefers_run_end_totals(tmp_path):
    sys.path.insert(0, REPO_ROOT)
    try:
        import bench
    finally:
        sys.path.pop(0)

    # run_end totals include the trailing window the heartbeats never flushed
    events = [
        {"event": "run_start"},
        {"event": "heartbeat", "window_train_windows": 2, "window_train_dispatches": 2,
         "window_train_gradient_steps": 5},
        {"event": "run_end", "train_windows": 3, "train_dispatches": 3,
         "train_gradient_steps": 9},
    ]
    ds = bench.dispatch_stats(events)
    assert ds["train_windows"] == 3
    assert ds["dispatches_per_window"] == 1.0
    assert ds["gradient_steps_per_dispatch"] == 3.0

    # still-running stream (no run_end): fall back to summing heartbeats
    ds = bench.dispatch_stats(events[:-1])
    assert ds["train_windows"] == 2
    assert ds["train_dispatches"] == 2

    # and from a file path, the way --dispatch-stats consumes it
    path = tmp_path / "telemetry.jsonl"
    path.write_text("\n".join(json.dumps(e) for e in events) + "\n")
    ds = bench.dispatch_stats(str(path))
    assert ds["dispatches_per_window"] == 1.0

    # no train windows at all -> no ratios, no division by zero
    assert "dispatches_per_window" not in bench.dispatch_stats([{"event": "run_start"}])
