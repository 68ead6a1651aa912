"""CLI acceptance (`python -m sheeprl_tpu serve` == cli_serve.serving): load
a committed checkpoint by manifest, AOT-warm the ladder, run the scripted
load generator, and have `tools.report --serve-stats` digest the telemetry — plus
the torn-checkpoint refusal and the reader's targeted degradation."""

import json

import pytest
import yaml

from sheeprl_tpu.serve.errors import SwapRejected
from tools.report import serve_stats

from .conftest import commit_linear

pytestmark = pytest.mark.serve


def _serve_run(tmp_path, step=100):
    """A run directory the serve CLI can consume: config.yaml + a committed
    linear checkpoint under <run>/checkpoint/."""
    run_dir = tmp_path / "run"
    run_dir.mkdir(parents=True, exist_ok=True)
    cfg = {
        "algo": {"name": "linear"},
        "seed": 42,
        "metric": {"telemetry": {"enabled": True, "poll_interval": 0.0}},
    }
    with open(run_dir / "config.yaml", "w") as f:
        yaml.safe_dump(cfg, f)
    path, state = commit_linear(str(run_dir / "checkpoint"), step)
    return run_dir, path, state


def _parse_serve_stats(stdout: str) -> dict:
    payload = stdout[stdout.index('{\n  "serve_stats"') :]
    return json.loads(payload)["serve_stats"]


def test_cli_acceptance_load_run_meets_slo_and_bench_reads_it(tmp_path, capsys, monkeypatch):
    """The ISSUE acceptance path: serve a committed checkpoint, AOT-warm,
    drive the load generator, sustain QPS with p95 <= SLO on CPU, and read
    the same numbers back through tools.report --serve-stats."""
    from sheeprl_tpu.cli_serve import serving

    run_dir, ckpt_path, _ = _serve_run(tmp_path)
    monkeypatch.chdir(tmp_path)
    serving(
        [
            f"checkpoint_path={ckpt_path}",
            "serve.slo_ms=150",
            "serve.num_replicas=2",
            "serve.load.enabled=true",
            "serve.load.duration_s=1.0",
            "serve.load.concurrency=4",
        ]
    )
    out = capsys.readouterr().out
    assert "serving linear step=100" in out
    assert "AOT ladder warmed" in out
    snap = _parse_serve_stats(out)
    report = snap["load_report"]
    assert report["ok"] > 0 and report["qps"] > 0
    assert report["p95_ms"] is not None and report["p95_ms"] <= 150.0
    assert report["slo_met"] is True
    assert snap["completed"] >= report["ok"]
    # every rung of the default ladder was AOT-warmed before traffic
    assert sorted(int(k) for k in snap["warmup_s"]) == [1, 2, 4, 8]

    # the reader takes the run's own telemetry stream — no log scraping
    jsonl = str(run_dir / "telemetry.jsonl")
    stats = serve_stats(jsonl)
    assert "error" not in stats
    assert stats["totals"]["completed"] == snap["completed"]
    assert stats["load_report"]["ok"] == report["ok"]
    assert stats["slo_met"] is True


def test_cli_serves_newest_commit_from_ckpt_dir(tmp_path, capsys, monkeypatch):
    from sheeprl_tpu.cli_serve import serving

    run_dir, _, _ = _serve_run(tmp_path, step=100)
    commit_linear(str(run_dir / "checkpoint"), 250, seed=5)
    monkeypatch.chdir(tmp_path)
    serving(
        [
            f"ckpt_dir={run_dir / 'checkpoint'}",
            "serve.load.enabled=true",
            "serve.load.duration_s=0.2",
            "serve.load.concurrency=2",
        ]
    )
    out = capsys.readouterr().out
    assert "serving linear step=250" in out
    assert _parse_serve_stats(out)["serving_step"] == 250


def test_cli_refuses_torn_checkpoint(tmp_path):
    from sheeprl_tpu.cli_serve import serving

    run_dir, _, _ = _serve_run(tmp_path)
    torn = str(run_dir / "checkpoint" / "ckpt_999_0.ckpt")
    with open(torn, "wb") as f:
        f.write(b"half a checkpoint")
    with pytest.raises(SwapRejected, match="manifest"):
        serving([f"checkpoint_path={torn}"])


def test_cli_requires_a_source():
    from sheeprl_tpu.cli_serve import serving

    with pytest.raises(ValueError, match="checkpoint_path"):
        serving(["serve.slo_ms=50"])


def test_bench_serve_stats_degrades_with_targeted_errors(tmp_path):
    missing = serve_stats(str(tmp_path / "nope.jsonl"))
    assert "cannot read telemetry stream" in missing["error"]
    # a training-run stream without serve activity: targeted message, no dump
    stream = tmp_path / "telemetry.jsonl"
    with open(stream, "w") as f:
        f.write(json.dumps({"event": "run_start"}) + "\n")
        f.write(json.dumps({"event": "run_end", "preemptions": 0}) + "\n")
    empty = serve_stats(str(stream))
    assert "no serve telemetry" in empty["error"]


@pytest.mark.slow
def test_load_drill_open_loop_sheds_and_clients_back_off(tmp_path):
    """The full load drill (slow tier): open-loop traffic over capacity
    against a deliberately slowed single replica — admission control sheds,
    clients retry with backoff, and the report accounts for every request."""
    from sheeprl_tpu.serve.config import serve_config_from_cfg
    from sheeprl_tpu.serve.loadgen import run_load
    from sheeprl_tpu.serve.policy import build_linear_policy, make_linear_state
    from sheeprl_tpu.serve.server import PolicyServer

    ckpt_dir = str(tmp_path / "checkpoint")
    path, state = commit_linear(ckpt_dir, 100)
    cfg = serve_config_from_cfg(
        {
            "serve": {
                "batch_ladder": [1, 2, 4],
                "slo_ms": 50.0,
                # generous server-side deadline: admitted work still succeeds,
                # so the drill isolates admission-control shedding
                "default_deadline_ms": 2000.0,
                "max_queue": 4,
                "num_replicas": 1,
                "monitor_interval_s": 0.01,
                "fault_injection": {
                    "enabled": True,
                    "faults": [
                        {
                            "kind": "slow_inference",
                            "replica": 0,
                            "at_batch": 0,
                            "duration_s": 0.1,
                            "for_batches": 100000,
                        }
                    ],
                },
                "load": {
                    "enabled": True,
                    "duration_s": 3.0,
                    "concurrency": 16,
                    "rate_hz": 1000.0,  # far over the ~40 req/s slowed capacity
                    "max_retries": 2,
                    "seed": 0,
                },
            }
        }
    )
    policy = build_linear_policy({"algo": {"name": "linear"}}, state)
    server = PolicyServer(policy, cfg, step=100, path=path, ckpt_dir=ckpt_dir)
    try:
        server.start()
        report = run_load(server, cfg.load)
    finally:
        server.close()
    assert report["mode"] == "open-loop"
    assert report["ok"] > 0  # the slowed replica still serves
    assert report["shed"] > 0, "over-capacity open-loop traffic must shed"
    assert report["client_rejections"] > 0
    assert report["client_retries"] > 0, "clients must back off and retry, not just fail"
    snap = server.snapshot()
    assert snap["shed_overloaded"] > 0
    assert snap["queue_depth"] <= cfg.max_queue
