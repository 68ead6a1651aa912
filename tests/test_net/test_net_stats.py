"""`python -m tools.report --net-stats` plumbing: the report reads the run_end ``net``
section (per-endpoint transport counters + per-kind event totals), the
sparse ``net_event`` log, and the ``net_handshake`` clock-skew observations
— and falls back to summing the event stream when the run is still going
(no run_end yet)."""

import json
import os
import subprocess
import sys

import pytest

from tools import report

pytestmark = pytest.mark.net

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _write(path, events):
    with open(path, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")


_EVENTS = [
    {"event": "trace", "kind": "net_handshake", "trace_id": 0, "t": 1.0, "t_mono": 1.0,
     "peer": "actor0", "skew_s": 0.002, "transport": "tcp"},
    {"event": "trace", "kind": "net_handshake", "trace_id": 0, "t": 1.1, "t_mono": 1.1,
     "peer": "actor0", "skew_s": 0.004, "transport": "tcp"},
    {"event": "net_event", "kind": "reconnect", "transport": "tcp.learner", "actor": 0, "generation": 1, "t": 2.0},
    {"event": "net_event", "kind": "disconnect", "transport": "tcp.agent", "peer": "fleet0", "reason": "eof", "t": 3.0},
]

_RUN_END = {
    "event": "run_end",
    "t": 9.0,
    "net": {
        # run_end counted one more reconnect than the flushed stream shows
        "events": {"reconnect": 2, "disconnect": 1},
        "transports": {
            "tcp.learner": {"frames_sent": 10, "frames_recv": 8, "bytes_sent": 1000,
                            "bytes_recv": 800, "reconnects": 2, "checksum_rejects": 1,
                            "heartbeat_gaps": 0, "stale_slabs": 0, "torn_frames": 1},
            "tcp.actor0": {"frames_sent": 8, "frames_recv": 10, "bytes_sent": 800,
                           "bytes_recv": 1000, "reconnects": 0, "checksum_rejects": 0,
                           "heartbeat_gaps": 1, "stale_slabs": 0, "torn_frames": 0},
        },
    },
}


def test_report_prefers_run_end_counters(tmp_path):
    path = str(tmp_path / "telemetry.jsonl")
    _write(path, _EVENTS + [_RUN_END])
    out = report.net_stats_report(path)
    assert out["events"] == {"reconnect": 2, "disconnect": 1}
    assert set(out["transports"]) == {"tcp.learner", "tcp.actor0"}
    assert out["transports"]["tcp.learner"]["checksum_rejects"] == 1
    assert out["totals"]["frames_sent"] == 18
    assert out["totals"]["bytes_recv"] == 1800
    assert out["totals"]["torn_frames"] == 1
    assert out["handshakes"]["count"] == 2
    assert out["handshakes"]["peers"] == ["actor0"]
    assert out["handshakes"]["skew_s"]["actor0"] == 0.004  # upper median of 2
    # the event log keeps the identifying fields for each sparse event
    kinds = [row["kind"] for row in out["event_log"]]
    assert kinds == ["reconnect", "disconnect"]
    assert out["event_log"][1]["reason"] == "eof"


def test_report_falls_back_to_stream_without_run_end(tmp_path):
    path = str(tmp_path / "telemetry.jsonl")
    _write(path, _EVENTS)
    out = report.net_stats_report(path)
    assert out["events"] == {"disconnect": 1, "reconnect": 1}
    assert "transports" not in out  # counters only live in run_end
    assert out["handshakes"]["count"] == 2


def test_report_notes_streams_with_no_net_plane(tmp_path):
    path = str(tmp_path / "telemetry.jsonl")
    _write(path, [{"event": "heartbeat", "t": 1.0}])
    out = report.net_stats_report(path)
    assert "note" in out and "multihost" in out["note"]


def test_net_stats_cli(tmp_path):
    """`python -m tools.report --net-stats PATH` prints the JSON report."""
    path = str(tmp_path / "telemetry.jsonl")
    _write(path, _EVENTS + [_RUN_END])
    proc = subprocess.run(
        [sys.executable, "-m", "tools.report", "--net-stats", path],
        capture_output=True,
        text=True,
        timeout=60,
        cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout)
    assert rec["totals"]["frames_sent"] == 18
