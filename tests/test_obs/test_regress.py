"""Regression gates (tools/regress.py): verdicts on synthetic history, the
SCENARIOS.json grid, exit codes, and the CLI surfaces."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REGRESS_PY = os.path.join(REPO_ROOT, "tools", "regress.py")


@pytest.fixture(scope="module")
def regress():
    spec = importlib.util.spec_from_file_location("_regress_under_test", REGRESS_PY)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _rec(t, algo="ppo", kind="train", outcome="completed", **metrics):
    return {
        "schema": 1,
        "t": t,
        "kind": kind,
        "algo": algo,
        "env": "CartPole-v1",
        "backend": "cpu",
        "local_device_count": 1,
        "process_count": 1,
        "outcome": outcome,
        **metrics,
    }


def test_verdicts_pass_regress_insufficient(regress):
    records = (
        [_rec(t, "ppo", sps_env=100.0 + t) for t in range(4)]
        + [_rec(t, "sac", sps_env=200.0) for t in range(3)]
        + [_rec(3, "sac", sps_env=100.0)]  # far below the 20% band
        + [_rec(0, "dreamer_v3", sps_env=50.0)]  # lone record
    )
    doc = regress.evaluate(records)
    verdicts = {key.split(":")[1]: cell["verdict"] for key, cell in doc["cells"].items()}
    assert verdicts == {"ppo": "pass", "sac": "regress", "dreamer_v3": "insufficient_history"}
    assert doc["summary"] == {"pass": 1, "regress": 1, "insufficient_history": 1}
    assert regress.exit_code(doc) == 1
    sac = doc["cells"]["train:sac:CartPole-v1:cpux1p1"]
    assert sac["metrics"]["sps_env"]["verdict"] == "regress"
    assert sac["metrics"]["sps_env"]["baseline"] == 200.0


def test_not_completed_runs_never_enter_a_cell(regress):
    records = [_rec(t, sps_env=100.0) for t in range(3)] + [
        _rec(3, sps_env=1.0, outcome="crashed"),
        _rec(4, sps_env=1.0, outcome="preempted"),
    ]
    doc = regress.evaluate(records)
    cell = doc["cells"]["train:ppo:CartPole-v1:cpux1p1"]
    assert cell["verdict"] == "pass"  # the crashed/preempted SPS never gated
    assert cell["newest_outcome"] == "completed"
    assert doc["records_ignored_not_completed"] == 2


def test_lower_is_better_and_count_slack(regress):
    # serve p95 going UP is a regression
    serve = [_rec(t, kind="serve", serve={"stats": {"qps": 100.0, "p95_ms": 10.0}}) for t in range(3)]
    doc = regress.evaluate(serve + [_rec(3, kind="serve", serve={"stats": {"qps": 100.0, "p95_ms": 30.0}})])
    cell = next(iter(doc["cells"].values()))
    assert cell["metrics"]["serve_p95_ms"]["verdict"] == "regress"
    assert cell["metrics"]["serve_qps"]["verdict"] == "pass"

    # count metrics carry +1 absolute slack: 0 -> 1 restart passes, 0 -> 5 regresses
    quiet = [_rec(t, worker_restarts=0, sps_env=100.0) for t in range(3)]
    doc = regress.evaluate(quiet + [_rec(3, worker_restarts=1, sps_env=100.0)])
    assert next(iter(doc["cells"].values()))["verdict"] == "pass"
    doc = regress.evaluate(quiet + [_rec(3, worker_restarts=5, sps_env=100.0)])
    cell = next(iter(doc["cells"].values()))
    assert cell["verdict"] == "regress"
    assert cell["metrics"]["worker_restarts"]["verdict"] == "regress"


def test_cells_split_by_kind_algo_env_topology(regress):
    a = _rec(0, sps_env=100.0)
    b = dict(_rec(1, sps_env=1.0), local_device_count=8)  # different topology
    c = dict(_rec(2, sps_env=1.0), env="Walker-v4")  # different env
    d = _rec(3, kind="eval", sps_env=1.0)  # different kind
    doc = regress.evaluate([a, b, c, d])
    assert len(doc["cells"]) == 4  # none of them compare against each other
    assert all(cell["verdict"] == "insufficient_history" for cell in doc["cells"].values())
    assert regress.exit_code(doc) == 0


def test_run_gate_writes_scenarios_and_exit_code(regress, tmp_path):
    runs = str(tmp_path / "RUNS.jsonl")
    out = str(tmp_path / "SCENARIOS.json")
    with open(runs, "w") as f:
        for t in range(3):
            f.write(json.dumps(_rec(t, sps_env=100.0)) + "\n")
        f.write("{torn\n")  # reader tolerance
        f.write(json.dumps(_rec(3, sps_env=10.0)) + "\n")
    assert regress.run_gate(runs, out, quiet=True) == 1
    with open(out) as f:
        doc = json.load(f)
    assert doc["summary"]["regress"] == 1
    assert doc["cells"]["train:ppo:CartPole-v1:cpux1p1"]["verdict"] == "regress"

    # repair the newest record -> gate goes green, grid is rewritten
    with open(runs, "a") as f:
        f.write(json.dumps(_rec(4, sps_env=101.0)) + "\n")
    assert regress.run_gate(runs, out, quiet=True) == 0
    with open(out) as f:
        assert json.load(f)["summary"]["regress"] == 0


def test_self_test_cli(tmp_path):
    proc = subprocess.run(
        [sys.executable, REGRESS_PY, "--self-test"],
        capture_output=True,
        text=True,
        timeout=60,
        cwd=str(tmp_path),
    )
    assert proc.returncode == 0, proc.stderr
    assert "ok" in proc.stdout


def test_bench_regress_cli(tmp_path):
    """``python -m tools.regress`` drives the gate: grid on disk, nonzero
    exit on a synthetically regressed record."""
    runs = tmp_path / "RUNS.jsonl"
    out = tmp_path / "SCENARIOS.json"
    with open(runs, "w") as f:
        for t, sps in enumerate([100.0, 102.0, 98.0, 10.0]):
            f.write(json.dumps(_rec(t, sps_env=sps)) + "\n")
    cmd = [
        sys.executable,
        "-m",
        "tools.regress",
        "--runs",
        str(runs),
        "--out",
        str(out),
    ]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120, cwd=REPO_ROOT)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "REGRESS" in proc.stdout
    with open(out) as f:
        assert json.load(f)["summary"]["regress"] == 1

    # make the newest healthy again: exit 0
    with open(runs, "a") as f:
        f.write(json.dumps(_rec(9, sps_env=101.0)) + "\n")
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120, cwd=REPO_ROOT)
    assert proc.returncode == 0, proc.stdout + proc.stderr
