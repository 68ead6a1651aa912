"""tools/report.py: the telemetry readers and their command line, and the
rule that whatever runs beside (or starts) a process holding the chip imports
no jax — a chip belongs to one process at a time.
"""

import json
import os
import subprocess
import sys

import pytest

from tools import report

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REPORT = os.path.join(REPO_ROOT, "tools", "report.py")


_NOJAX_LOADER = r"""
import sys

class _NoJax:
    # a process that has touched jax holds the chip, and children that need
    # it then fail or hang
    def find_spec(self, name, path=None, target=None):
        if name == "jax" or name.startswith("jax."):
            raise ImportError("this script must not import jax")
        return None

sys.meta_path.insert(0, _NoJax())
import importlib.util

spec = importlib.util.spec_from_file_location("script", sys.argv[1])
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
"""


@pytest.mark.parametrize("script", ["tools/report.py", "benchmarks/serve_cold_start.py", "__graft_entry__.py"])
def test_launchers_of_chip_children_load_without_jax(script):
    """Every script that starts children which need the chip, or reads a run
    beside one, loads — module level and all — with jax imports poisoned: a
    parent that had touched jax would hold the chip and its children would
    fail or hang."""
    code = _NOJAX_LOADER + 'print("LOADED-WITHOUT-JAX")\n'
    proc = subprocess.run(
        [sys.executable, "-c", code, os.path.join(REPO_ROOT, script)],
        capture_output=True,
        text=True,
        timeout=60,
        cwd=REPO_ROOT,
    )
    assert proc.returncode == 0 and "LOADED-WITHOUT-JAX" in proc.stdout, proc.stderr[-2000:]


def _write_telemetry(path, extra=()):
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
        for e in [*events, *extra]:
            f.write(json.dumps(e) + "\n")
        f.write('{"event": "heartbe')  # torn tail: must be skipped, not fatal


def test_telemetry_summary_from_jsonl(tmp_path):
    path = str(tmp_path / "telemetry.jsonl")
    _write_telemetry(path)
    s = report.telemetry_summary(path)
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


# one event (or more) of the kind each reader digests, after the base stream
_EVERY_READERS_EVENTS = [
    {"event": "span", "t": 6.1, "step": 2000, "name": "rollout/env_step", "dur": 0.004, "attrs": {"queue_wait_s": 0.001}},
    {"event": "worker_restart", "t": 6.2, "step": 2000, "worker": 1, "reason": "crash", "restarts": 1},
    {"event": "span", "t": 6.3, "step": 2000, "name": "ckpt/write", "dur": 0.2, "attrs": {"sync": False}},
    {"event": "ckpt_committed", "t": 6.4, "step": 2000, "ckpt_step": 2000},
    {"event": "aot_cache", "t": 6.5, "step": 2000, "action": "hit", "tag": "serve_b8"},
    {"event": "serve_stats", "t": 6.6, "step": 2000, "qps": 100.0, "p95_ms": 20.0, "slo_ms": 100.0},
    {"event": "net_event", "t": 6.7, "step": 2000, "kind": "reconnect", "transport": "tcp.learner", "peer": "actor0"},
    {"event": "run_end", "t": 7.0, "step": 2000, "train_windows": 3, "train_dispatches": 3, "train_gradient_steps": 9},
]


@pytest.mark.parametrize(
    "flag, key, value",
    [
        ("--telemetry", "sps_env", 500.0),
        ("--dispatch-stats", "gradient_steps_per_dispatch", 3.0),
        ("--env-stats", "worker_restarts", [{"worker": 1, "reason": "crash", "restarts": 1, "step": 2000}]),
        ("--resilience-stats", "committed_steps", [2000]),
        ("--compile-stats", "aot_cache_hit_tags", {"serve_b8": 1}),
        ("--serve-stats", "snapshots", 1),
        ("--net-stats", "events", {"reconnect": 1}),
        ("--trace", "traces", 0),
    ],
)
def test_every_flag_prints_one_json_document(tmp_path, flag, key, value):
    """``python -m tools.report <flag> PATH``: one JSON document on stdout,
    digested from the stream, for each of the eight readers."""
    path = str(tmp_path / "telemetry.jsonl")
    _write_telemetry(path, extra=_EVERY_READERS_EVENTS)
    proc = subprocess.run(
        [sys.executable, "-m", "tools.report", flag, path],
        capture_output=True,
        text=True,
        timeout=60,
        cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(proc.stdout)[key] == value


def test_telemetry_summary_needs_no_jax(tmp_path):
    """The summary runs with jax imports poisoned."""
    path = str(tmp_path / "telemetry.jsonl")
    _write_telemetry(path)
    code = _NOJAX_LOADER + (
        "import json\n"
        "print(json.dumps(mod.telemetry_summary(sys.argv[2])))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code, REPORT, path],
        capture_output=True,
        text=True,
        timeout=60,
        cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["sps_train"] == 200.0


def test_dispatch_stats_prefers_run_end_totals(tmp_path):
    # run_end totals include the trailing window the heartbeats never flushed
    events = [
        {"event": "run_start"},
        {"event": "heartbeat", "window_train_windows": 2, "window_train_dispatches": 2,
         "window_train_gradient_steps": 5},
        {"event": "run_end", "train_windows": 3, "train_dispatches": 3,
         "train_gradient_steps": 9},
    ]
    ds = report.dispatch_stats(events)
    assert ds["train_windows"] == 3
    assert ds["dispatches_per_window"] == 1.0
    assert ds["gradient_steps_per_dispatch"] == 3.0

    # still-running stream (no run_end): fall back to summing heartbeats
    ds = report.dispatch_stats(events[:-1])
    assert ds["train_windows"] == 2
    assert ds["train_dispatches"] == 2

    # and from a file path, the way --dispatch-stats consumes it
    path = tmp_path / "telemetry.jsonl"
    path.write_text("\n".join(json.dumps(e) for e in events) + "\n")
    ds = report.dispatch_stats(str(path))
    assert ds["dispatches_per_window"] == 1.0

    # no train windows at all -> no ratios, no division by zero
    assert "dispatches_per_window" not in report.dispatch_stats([{"event": "run_start"}])
