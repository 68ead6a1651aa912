#!/usr/bin/env python3
"""Regression gates over the run registry (``RUNS.jsonl``) → ``SCENARIOS.json``.

ROADMAP item 5's scenario health grid, fed mechanically: every registry
record (appended by the entrypoints at run end, see
``sheeprl_tpu/obs/registry.py`` and ``howto/evidence.md``) lands in a
*scenario cell* keyed ``kind:algo:env:topology``. For each cell the newest
completed record is compared, metric by metric, against a tolerance-banded
baseline — the median of up to ``--window`` prior completed records — and
the per-cell verdicts (``pass`` / ``regress`` / ``insufficient_history``)
are written as a grid to ``SCENARIOS.json``. Exit status is nonzero when any
cell regresses, so a nightly job can gate on it.

Gated metrics (direction, and an absolute slack for count metrics so a
single flaky restart doesn't page anyone):

==================  ======  =====================================
metric              better  source
==================  ======  =====================================
sps_env             higher  heartbeat rollup (run-average)
sps_train           higher  heartbeat rollup (run-average)
sps_end_to_end      higher  heartbeat rollup (env steps / whole timed loop)
overlap_fraction    higher  heartbeat rollup (env time hidden behind train)
mfu                 higher  last heartbeat MFU
serve_qps           higher  serve run_end stats (``serve.stats.qps``)
serve_p95_ms        lower   serve run_end stats (``serve.stats.p95_ms``)
qps@p95             higher  SLO-conditioned goodput: the load/ramp report's
                            completed QPS while p95 <= SLO, else 0 (fleet
                            acceptance cells gate on this — throughput that
                            blows the SLO counts as zero)
worker_restarts     lower   rollout supervision totals (slack 1)
masked_slots        lower   rollout supervision totals (slack 1)
nan_rollbacks       lower   resilience totals (slack 1)
recompiles          lower   compile watchdog totals (slack 1)
net_checksum_rejects lower  run_end ``net.transports`` summed over endpoints
net_torn_frames     lower   run_end ``net.transports`` summed over endpoints
net_reconnects      lower   run_end ``net.transports`` sums (slack 1)
net_heartbeat_gaps  lower   run_end ``net.transports`` sums (slack 1)
==================  ======  =====================================

Deliberately dependency-free (stdlib only): it imports no jax, so it runs
beside a process that holds the chip, and CI can run it on any box.

``--self-test`` runs the verdict logic against a synthetic history
(pass / regress / insufficient) and exits nonzero on any mismatch — the
pytest-visible smoke for the gate itself.
"""

from __future__ import annotations

import argparse
import fnmatch
import json
import os
import sys
import time
from statistics import median
from typing import Any, Dict, List, Optional, Tuple

SCHEMA_VERSION = 1
DEFAULT_TOL = 0.2
DEFAULT_WINDOW = 5
DEFAULT_MIN_HISTORY = 2

# metric -> (higher_is_better, absolute_slack)
METRICS: Dict[str, Tuple[bool, float]] = {
    "sps_env": (True, 0.0),
    "sps_train": (True, 0.0),
    "sps_end_to_end": (True, 0.0),
    "overlap_fraction": (True, 0.0),
    "mfu": (True, 0.0),
    "serve_qps": (True, 0.0),
    "serve_p95_ms": (False, 0.0),
    "qps@p95": (True, 0.0),
    "worker_restarts": (False, 1.0),
    "masked_slots": (False, 1.0),
    "nan_rollbacks": (False, 1.0),
    "recompiles": (False, 1.0),
    # replica cold start (benchmarks/serve_cold_start.py --record): process
    # spawn -> first request served on a warm AOT executable cache.
    # Lower-better in the default 20% band, like the latency metrics.
    "cold_start_s": (False, 0.0),
    # multi-host data plane (sheeprl_tpu/net): summed over every transport
    # endpoint in the record's run_end `net.transports` section. The `*:p2`
    # localhost-TCP drill cells (ISSUE 18) gate on these — a healthy drill
    # has zero corrupt frames; reconnects get slack 1 because the chaos
    # drill's budgeted restart IS a reconnect.
    "net_checksum_rejects": (False, 0.0),
    "net_torn_frames": (False, 0.0),
    "net_reconnects": (False, 1.0),
    "net_heartbeat_gaps": (False, 1.0),
    # online-learning bridge (ISSUE 20, kind=serve_train): eval-return
    # improvement of the served policy over the run (the whole point of the
    # loop — gated with its own floor below), and experience shed to
    # backpressure/hook failure (counted, never silent; slack 1 because a
    # deliberate ring-full drill window sheds by design)
    "eval_return_delta": (True, 0.0),
    "shed_experience": (False, 1.0),
}

# (cell-key glob, metric, absolute lower bound). Floors are enforced on the
# NEWEST completed record of every matching cell REGARDLESS of history depth:
# an absolute bar must not hide behind a regressed baseline or an
# insufficient-history verdict the way the relative band can. All floored
# metrics are higher-is-better.
METRIC_FLOORS: Tuple[Tuple[str, str, float], ...] = (
    # The ISSUE-19 bar: the batched domain-randomization sweep
    # (benchmarks/scenario_sweep.py --record) must sustain >=100k AGGREGATE
    # env-steps/s across its scenario instances — on every backend, CPU
    # included (the bar was set on a single-core CPU host).
    ("train:ppo:scenario_sweep:*", "sps_env", 100_000.0),
    # The ISSUE-20 bar: a serve_train run must IMPROVE the served policy —
    # eval return (mean feedback reward on a fixed eval set) strictly better
    # at the end than at boot, on every backend, even on a first record.
    ("serve_train:*", "eval_return_delta", 0.5),
)


def cell_floors(key: str) -> List[Tuple[str, float]]:
    """Absolute lower bounds applying to one cell key."""
    return [(name, floor) for pat, name, floor in METRIC_FLOORS if fnmatch.fnmatch(key, pat)]


# ------------------------------------------------------------------ loading ----


def read_records(path: str) -> List[Dict[str, Any]]:
    """Tolerant JSONL reader: skips blank/unparsable lines and records from
    a newer schema (mirrors obs/registry.py without importing the package)."""
    out: List[Dict[str, Any]] = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict) and int(rec.get("schema", 1) or 1) <= SCHEMA_VERSION:
                    out.append(rec)
    except OSError:
        return []
    return out


# ---------------------------------------------------------------- cells ----


def cell_key(rec: Dict[str, Any]) -> str:
    backend = rec.get("backend") or "?"
    devices = rec.get("local_device_count")
    procs = rec.get("process_count")
    topo = f"{backend}x{devices or '?'}p{procs or '?'}"
    key = f"{rec.get('kind', 'train')}:{rec.get('algo') or '?'}:{rec.get('env') or '?'}:{topo}"
    # loop variants (fused_rollout, overlap_collection) have
    # their own throughput regime — gate them against their own history
    variant = rec.get("variant")
    if variant:
        key += f":{variant}"
    return key


def record_metrics(rec: Dict[str, Any]) -> Dict[str, float]:
    """Extract the gated metrics present in one registry record."""
    out: Dict[str, float] = {}
    for key in (
        "sps_env",
        "sps_train",
        "sps_end_to_end",
        "overlap_fraction",
        "mfu",
        "worker_restarts",
        "masked_slots",
        "nan_rollbacks",
        "recompiles",
        "cold_start_s",
    ):
        value = rec.get(key)
        if isinstance(value, (int, float)):
            out[key] = float(value)
    serve = rec.get("serve") or {}
    stats = serve.get("stats") if isinstance(serve, dict) else None
    if not isinstance(stats, dict):
        stats = rec.get("serve_stats") if isinstance(rec.get("serve_stats"), dict) else {}
    if isinstance(stats.get("qps"), (int, float)):
        out["serve_qps"] = float(stats["qps"])
    if isinstance(stats.get("p95_ms"), (int, float)):
        out["serve_p95_ms"] = float(stats["p95_ms"])
    goodput = slo_goodput(stats)
    if goodput is not None:
        out["qps@p95"] = goodput
    online = rec.get("online")
    if isinstance(online, dict):
        for name in ("eval_return_delta", "shed_experience"):
            if isinstance(online.get(name), (int, float)):
                out[name] = float(online[name])
    net = rec.get("net")
    if isinstance(net, dict) and isinstance(net.get("transports"), dict):
        sums: Dict[str, float] = {}
        for counters in net["transports"].values():
            if isinstance(counters, dict):
                for k, v in counters.items():
                    if isinstance(v, (int, float)):
                        sums[k] = sums.get(k, 0.0) + float(v)
        for short in ("checksum_rejects", "torn_frames", "reconnects", "heartbeat_gaps"):
            if short in sums:
                out[f"net_{short}"] = sums[short]
    return out


def slo_goodput(stats: Dict[str, Any]) -> Optional[float]:
    """``qps@p95``: completed QPS while p95 <= SLO, else 0.0. Prefers the
    load/ramp report inside the snapshot (measured under offered load; a
    ramp's ``max_good_qps`` already encodes the conditioning), falling back
    to the server-side uptime counters."""
    report = stats.get("load_report")
    if isinstance(report, dict):
        if report.get("mode") == "ramp":
            value = report.get("max_good_qps")
            return float(value) if isinstance(value, (int, float)) else None
        qps, p95, slo = report.get("qps"), report.get("p95_ms"), report.get("slo_ms")
        if isinstance(qps, (int, float)):
            met = isinstance(p95, (int, float)) and isinstance(slo, (int, float)) and p95 <= slo
            return float(qps) if met else 0.0
    qps, p95, slo = stats.get("qps"), stats.get("p95_ms"), stats.get("slo_ms")
    if isinstance(qps, (int, float)) and isinstance(p95, (int, float)) and isinstance(slo, (int, float)):
        return float(qps) if p95 <= slo else 0.0
    return None


def _metric_verdict(
    name: str, newest: float, history: List[float], tol: float, min_history: int
) -> Dict[str, Any]:
    if len(history) < min_history:
        return {"newest": newest, "history": len(history), "verdict": "insufficient_history"}
    higher_better, slack = METRICS[name]
    base = median(history)
    if higher_better:
        allowed = base * (1.0 - tol) - slack
        regressed = newest < allowed
    else:
        allowed = base * (1.0 + tol) + slack
        regressed = newest > allowed
    return {
        "newest": newest,
        "baseline": base,
        "allowed": allowed,
        "history": len(history),
        "verdict": "regress" if regressed else "pass",
    }


def evaluate(
    records: List[Dict[str, Any]],
    *,
    tol: float = DEFAULT_TOL,
    window: int = DEFAULT_WINDOW,
    min_history: int = DEFAULT_MIN_HISTORY,
) -> Dict[str, Any]:
    """Group completed records into cells and gate the newest of each
    against its own history. Returns the SCENARIOS.json document."""
    completed = [r for r in records if r.get("outcome") == "completed"]
    cells: Dict[str, List[Dict[str, Any]]] = {}
    for rec in sorted(completed, key=lambda r: float(r.get("t", 0) or 0)):
        cells.setdefault(cell_key(rec), []).append(rec)

    grid: Dict[str, Any] = {}
    counts = {"pass": 0, "regress": 0, "insufficient_history": 0}
    for key, recs in sorted(cells.items()):
        newest = recs[-1]
        prior = recs[:-1][-window:]
        newest_metrics = record_metrics(newest)
        verdicts: Dict[str, Any] = {}
        for name, value in sorted(newest_metrics.items()):
            history = [record_metrics(r)[name] for r in prior if name in record_metrics(r)]
            verdicts[name] = _metric_verdict(name, value, history, tol, min_history)
        for name, floor in cell_floors(key):
            v = verdicts.get(name)
            if v is None:
                continue  # metric absent from the newest record: nothing to floor
            v["floor"] = floor
            if v["newest"] < floor:
                v["verdict"] = "regress"
        states = {v["verdict"] for v in verdicts.values()}
        if "regress" in states:
            cell_state = "regress"
        elif "pass" in states:
            cell_state = "pass"
        else:
            cell_state = "insufficient_history"
        counts[cell_state] += 1
        grid[key] = {
            "verdict": cell_state,
            "runs": len(recs),
            "newest_t": newest.get("t"),
            "newest_outcome": newest.get("outcome"),
            "metrics": verdicts,
        }
    ignored = len(records) - len(completed)
    return {
        "schema": SCHEMA_VERSION,
        "generated_t": time.time(),
        "tolerance": tol,
        "window": window,
        "min_history": min_history,
        "records": len(records),
        "records_ignored_not_completed": ignored,
        "summary": counts,
        "cells": grid,
    }


# ---------------------------------------------------------------- output ----


# keys owned by other tools writing into the same grid file — a
# regression-gate rewrite must carry them forward: tools/jaxcheck's static
# config-matrix verdicts (config_*, static_findings) and tools/sweep.py's
# executed scenario verdicts (executed_*)
PRESERVED_KEYS = (
    "config_cells",
    "config_summary",
    "static_findings",
    "executed_cells",
    "executed_summary",
)


def write_scenarios(doc: Dict[str, Any], path: str) -> None:
    try:
        with open(path) as f:
            prev = json.load(f)
    except (OSError, ValueError):
        prev = {}
    if isinstance(prev, dict):
        for key in PRESERVED_KEYS:
            if key in prev and key not in doc:
                doc[key] = prev[key]
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, default=str)
        f.write("\n")
    os.replace(tmp, path)


def render_grid(doc: Dict[str, Any], stream=sys.stdout) -> None:
    marks = {"pass": "PASS   ", "regress": "REGRESS", "insufficient_history": "HISTORY"}
    for key, cell in doc["cells"].items():
        print(f"{marks[cell['verdict']]} {key} (runs={cell['runs']})", file=stream)
        if cell["verdict"] == "regress":
            for name, v in cell["metrics"].items():
                if v["verdict"] != "regress":
                    continue
                if "floor" in v and v["newest"] < v["floor"]:
                    print(
                        f"        {name}: {v['newest']:.4g} below floor {v['floor']:.4g}",
                        file=stream,
                    )
                else:
                    print(
                        f"        {name}: {v['newest']:.4g} vs baseline {v['baseline']:.4g} "
                        f"(allowed {v['allowed']:.4g})",
                        file=stream,
                    )
    s = doc["summary"]
    print(
        f"# {len(doc['cells'])} cells: {s['pass']} pass, {s['regress']} regress, "
        f"{s['insufficient_history']} insufficient history "
        f"({doc['records']} records, {doc['records_ignored_not_completed']} not-completed ignored)",
        file=stream,
    )


# -------------------------------------------------------------- self-test ----


def self_test() -> int:
    """Verdict logic against synthetic history: a stable cell passes, a
    collapsed-SPS cell regresses, a single-record cell reports insufficient
    history — and not-completed records never enter a baseline."""

    def rec(t, algo, sps, outcome="completed", **extra):
        return {
            "schema": SCHEMA_VERSION,
            "t": t,
            "kind": "train",
            "algo": algo,
            "env": "CartPole-v1",
            "backend": "cpu",
            "local_device_count": 1,
            "process_count": 1,
            "outcome": outcome,
            "sps_env": sps,
            **extra,
        }

    records = [
        # stable cell: newest within the band
        rec(1, "ppo", 100.0),
        rec(2, "ppo", 104.0),
        rec(3, "ppo", 98.0),
        rec(4, "ppo", 101.0),
        # regressed cell: newest collapses far past the tolerance band
        rec(1, "sac", 200.0),
        rec(2, "sac", 198.0),
        rec(3, "sac", 202.0),
        rec(4, "sac", 90.0),
        # crashed runs must not count as history OR newest
        rec(5, "sac", 1.0, outcome="crashed"),
        # insufficient history: a single record
        rec(1, "dreamer_v3", 50.0),
        # variant runs (fused_rollout etc.) gate against their OWN history,
        # never against the base cell's — 3x the base SPS must not regress it
        rec(1, "ppo", 320.0, variant="fused_rollout"),
        rec(2, "ppo", 310.0, variant="fused_rollout"),
        rec(3, "ppo", 315.0, variant="fused_rollout"),
    ]
    # fleet serve cells gate SLO-conditioned goodput: blowing the SLO zeroes
    # qps@p95 even when raw QPS looks healthy
    def serve_rec(t, qps, p95):
        r = rec(t, "ppo", None, variant="fleet")
        r.pop("sps_env")
        r["kind"] = "serve"
        r["serve_stats"] = {"qps": qps, "p95_ms": p95, "slo_ms": 100.0}
        return r

    records += [serve_rec(1, 400.0, 40.0), serve_rec(2, 410.0, 45.0), serve_rec(3, 405.0, 50.0)]

    # ISSUE-18 p2 topology cells: a 2-process localhost-TCP drill gets its
    # own `...p2:...` cell (never pooled with the p1 history) and gates the
    # summed per-transport counters from the run_end net section
    def p2_rec(t):
        r = rec(t, "ppo_decoupled", 500.0, variant="actor_learner")
        r["process_count"] = 2
        r["net"] = {
            "events": {"reconnect": 1},
            "transports": {
                "tcp.learner": {"checksum_rejects": 0, "torn_frames": 0, "reconnects": 1},
                "tcp.actor0": {"checksum_rejects": 0, "torn_frames": 0, "reconnects": 0},
            },
        }
        return r

    records += [p2_rec(1), p2_rec(2), p2_rec(3)]
    # ISSUE-19 scenario-sweep floor: the batched domain-randomization cell
    # carries an absolute 100k aggregate-sps bar on EVERY backend (the bar
    # was set on a single-core CPU host), firing even on a first record
    def sweep_rec(t, sps, backend="cpu"):
        return rec(t, "ppo", sps, env="scenario_sweep", backend=backend, variant="fused_scenarios")

    records += [
        sweep_rec(1, 190000.0),
        sweep_rec(2, 230000.0),
        sweep_rec(3, 240000.0),
        sweep_rec(1, 60000.0, backend="fake"),
    ]

    # ISSUE-20 serve_train cells: the online-learning loop gets its OWN kind
    # (never pooled with plain serve cells) and carries the absolute
    # eval-improvement floor — a run that fails to improve the served policy
    # regresses even with no history
    def st_rec(t, delta, env="linear_feedback"):
        r = rec(t, "linear", None, env=env, variant="bridge")
        r.pop("sps_env")
        r["kind"] = "serve_train"
        r["online"] = {"eval_return_delta": delta, "shed_experience": 0}
        r["serve_stats"] = {"qps": 300.0, "p95_ms": 30.0, "slo_ms": 100.0}
        return r

    records += [
        st_rec(1, 4.2),
        st_rec(2, 4.6),
        st_rec(3, 4.4),
        st_rec(1, 0.1, env="linear_feedback_flat"),
    ]
    doc = evaluate(records)
    got = {}
    for key, cell in doc["cells"].items():
        parts = key.split(":")
        got[parts[1] if len(parts) == 4 else f"{parts[1]}:{parts[4]}"] = cell["verdict"]
    want = {"ppo": "pass", "sac": "regress", "dreamer_v3": "insufficient_history"}
    failures = [f"{k}: want {want[k]}, got {got.get(k)}" for k in want if got.get(k) != want[k]]
    sac = doc["cells"]["train:sac:CartPole-v1:cpux1p1"]
    if sac["newest_outcome"] != "completed":
        failures.append("crashed record selected as newest")
    fused = doc["cells"].get("train:ppo:CartPole-v1:cpux1p1:fused_rollout")
    if fused is None or fused["verdict"] != "pass" or fused["runs"] != 3:
        failures.append(f"variant cell: want separate 3-run pass cell, got {fused}")
    if doc["cells"]["train:ppo:CartPole-v1:cpux1p1"]["runs"] != 4:
        failures.append("variant records leaked into the base cell history")
    p2_cell = doc["cells"].get("train:ppo_decoupled:CartPole-v1:cpux1p2:actor_learner")
    if (
        p2_cell is None
        or p2_cell["verdict"] != "pass"
        or p2_cell["runs"] != 3
        or "net_checksum_rejects" not in (p2_cell.get("metrics") or {})
        or "net_reconnects" not in (p2_cell.get("metrics") or {})
    ):
        failures.append(f"p2 cell: want separate 3-run pass cell gating net counters, got {p2_cell}")
    fleet_cell = doc["cells"].get("serve:ppo:CartPole-v1:cpux1p1:fleet")
    if (
        fleet_cell is None
        or fleet_cell["verdict"] != "pass"
        or "qps@p95" not in (fleet_cell.get("metrics") or {})
    ):
        failures.append(f"fleet serve cell: want 3-run pass cell gating qps@p95, got {fleet_cell}")
    sweep_ok = doc["cells"].get("train:ppo:scenario_sweep:cpux1p1:fused_scenarios")
    if (
        sweep_ok is None
        or sweep_ok["verdict"] != "pass"
        or sweep_ok["metrics"]["sps_env"].get("floor") != 100_000.0
    ):
        failures.append(f"scenario_sweep floor: want passing cell carrying floor=100k, got {sweep_ok}")
    sweep_low = doc["cells"].get("train:ppo:scenario_sweep:fakex1p1:fused_scenarios")
    if sweep_low is None or sweep_low["verdict"] != "regress":
        failures.append(f"scenario_sweep floor: a 60k cell must regress even with no history, got {sweep_low}")
    st_cell = doc["cells"].get("serve_train:linear:linear_feedback:cpux1p1:bridge")
    if (
        st_cell is None
        or st_cell["verdict"] != "pass"
        or st_cell["metrics"]["eval_return_delta"].get("floor") != 0.5
        or "shed_experience" not in st_cell["metrics"]
        or "qps@p95" not in st_cell["metrics"]
    ):
        failures.append(
            f"serve_train cell: want own-kind cell flooring eval_return_delta and "
            f"gating shed/goodput, got {st_cell}"
        )
    st_flat = doc["cells"].get("serve_train:linear:linear_feedback_flat:cpux1p1:bridge")
    if st_flat is None or st_flat["verdict"] != "regress":
        failures.append(
            f"serve_train floor: a no-improvement run must regress even with no history, got {st_flat}"
        )
    if slo_goodput({"qps": 900.0, "p95_ms": 250.0, "slo_ms": 100.0}) != 0.0:
        failures.append("qps@p95: an SLO miss must zero the goodput")
    if slo_goodput({"load_report": {"mode": "ramp", "max_good_qps": 123.0}}) != 123.0:
        failures.append("qps@p95: a ramp report's max_good_qps must win over uptime counters")
    if exit_code(doc) != 1:
        failures.append(f"exit code: want 1, got {exit_code(doc)}")
    healthy = [
        r
        for r in records
        if r["algo"] != "sac"
        and r.get("env") != "linear_feedback_flat"
        and not (r.get("env") == "scenario_sweep" and r.get("backend") == "fake")
    ]
    if exit_code(evaluate(healthy)) != 0:
        failures.append("exit code without the regressed cells: want 0")

    # a regress rewrite of the grid file must carry every PRESERVED_KEYS
    # section (static config verdicts AND tools/sweep.py executed verdicts)
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        grid_path = os.path.join(td, "SCENARIOS.json")
        prev = {
            "schema": SCHEMA_VERSION,
            "config_cells": {"config:exp=ppo:fabric=cpu": {"verdict": "pass"}},
            "config_summary": {"cells": 1, "pass": 1},
            "static_findings": [],
            "executed_cells": {
                "sweep:ppo:CartPole-v1+sticky_actions": {"tier": "learn", "verdict": "learn_pass"}
            },
            "executed_summary": {"cells": 1, "verdicts": {"learn_pass": 1}},
        }
        with open(grid_path, "w") as f:
            json.dump(prev, f)
        write_scenarios(evaluate(healthy), grid_path)
        with open(grid_path) as f:
            merged = json.load(f)
        missing = [k for k in PRESERVED_KEYS if k not in merged]
        if missing:
            failures.append(f"write_scenarios dropped preserved sections: {missing}")
        kept = (merged.get("executed_cells") or {}).get("sweep:ppo:CartPole-v1+sticky_actions") or {}
        if kept.get("verdict") != "learn_pass":
            failures.append(f"executed cell mutated through the regress rewrite: {kept}")
        if "cells" not in merged:
            failures.append("regress rewrite lost its own verdict grid")
    if failures:
        print("regress self-test FAILED:\n  " + "\n  ".join(failures), file=sys.stderr)
        return 1
    print("regress self-test: ok (pass / regress / insufficient_history verdicts verified)")
    return 0


def exit_code(doc: Dict[str, Any]) -> int:
    return 1 if doc["summary"]["regress"] else 0


# ------------------------------------------------------------------- main ----


def run_gate(
    runs_path: str,
    out_path: Optional[str] = None,
    *,
    tol: float = DEFAULT_TOL,
    window: int = DEFAULT_WINDOW,
    min_history: int = DEFAULT_MIN_HISTORY,
    quiet: bool = False,
) -> int:
    """Load → evaluate → write grid → render. Returns the process exit code
    (``1`` on any regressed cell)."""
    records = read_records(runs_path)
    doc = evaluate(records, tol=tol, window=window, min_history=min_history)
    if out_path:
        write_scenarios(doc, out_path)
    if not quiet:
        render_grid(doc)
    return exit_code(doc)


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--runs", default="RUNS.jsonl", help="run-registry JSONL (default: ./RUNS.jsonl)")
    parser.add_argument("--out", default="SCENARIOS.json", help="verdict-grid output (default: ./SCENARIOS.json)")
    parser.add_argument("--tol", type=float, default=DEFAULT_TOL, help="relative tolerance band (default 0.2)")
    parser.add_argument("--window", type=int, default=DEFAULT_WINDOW, help="baseline history window (default 5)")
    parser.add_argument(
        "--min-history", type=int, default=DEFAULT_MIN_HISTORY, help="prior runs required to gate (default 2)"
    )
    parser.add_argument("--quiet", action="store_true", help="no grid on stdout, exit code only")
    parser.add_argument("--self-test", action="store_true", help="verify the verdict logic and exit")
    args = parser.parse_args(argv)
    if args.self_test:
        return self_test()
    return run_gate(
        args.runs,
        args.out,
        tol=args.tol,
        window=args.window,
        min_history=args.min_history,
        quiet=args.quiet,
    )


if __name__ == "__main__":
    sys.exit(main())
