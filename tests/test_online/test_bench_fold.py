"""tools/report.py --serve-stats / tools/regress.py folds for the online bridge."""

import importlib.util
import os

import pytest

pytestmark = [pytest.mark.online]

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _load(name, rel):
    spec = importlib.util.spec_from_file_location(name, os.path.join(REPO, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bench():
    return _load("_report_online_fold", "tools/report.py")


def test_serve_stats_folds_bridge_events_and_run_end_online(bench):
    events = [
        {"event": "serve_stats", "qps": 100.0, "p95_ms": 20.0, "slo_ms": 100.0},
        {"event": "serve_event", "kind": "online_exp_slab", "rows": 8},
        {"event": "serve_event", "kind": "online_exp_slab", "rows": 8},
        {"event": "serve_event", "kind": "online_exp_slab_shed", "rows": 8},
        {"event": "serve_event", "kind": "online_hook_hang"},
        {"event": "serve_event", "kind": "online_publish_committed", "step": 101},
        {
            "event": "run_end",
            "serve": {"stats": {"qps": 100.0, "p95_ms": 20.0, "slo_ms": 100.0}},
            "online": {"shed_experience": 8, "eval_return_delta": 4.2, "hook_hangs": 1},
        },
    ]
    out = bench.serve_stats(events)
    online = out["online"]
    assert online["shed_experience"] == 8
    assert online["eval_return_delta"] == 4.2
    assert online["events"] == {
        "exp_slab": 2,
        "exp_slab_shed": 1,
        "hook_hang": 1,
        "publish_committed": 1,
    }


def test_registry_rows_carry_serve_train_kind_and_online_counters(bench):
    records = [
        {
            "kind": "serve_train",
            "algo": "linear",
            "env": "linear_feedback",
            "outcome": "completed",
            "online": {"eval_return_delta": 4.9, "shed_experience": 80},
            "serve": {"stats": {"qps": 300.0, "p95_ms": 25.0, "slo_ms": 100.0}},
        },
        {"kind": "train", "algo": "ppo"},  # never aggregated as a serve row
    ]
    out = bench.serve_registry_stats(records)
    assert out["serve_records"] == 1
    row = out["records"][0]
    assert row["kind"] == "serve_train"
    assert row["online"] == {"eval_return_delta": 4.9, "shed_experience": 80}
    assert row["qps@p95"] == 300.0


def test_regress_gives_serve_train_its_own_floored_cell():
    regress = _load("_regress_online_fold", "tools/regress.py")
    rec = {
        "schema": regress.SCHEMA_VERSION,
        "t": 1,
        "kind": "serve_train",
        "algo": "linear",
        "env": "linear_feedback",
        "backend": "cpu",
        "local_device_count": 1,
        "process_count": 1,
        "variant": "bridge",
        "outcome": "completed",
        "online": {"eval_return_delta": 4.9, "shed_experience": 80},
        "serve_stats": {"qps": 300.0, "p95_ms": 25.0, "slo_ms": 100.0},
    }
    assert regress.cell_key(rec) == "serve_train:linear:linear_feedback:cpux1p1:bridge"
    metrics = regress.record_metrics(rec)
    assert metrics["eval_return_delta"] == 4.9
    assert metrics["shed_experience"] == 80.0
    assert regress.cell_floors(regress.cell_key(rec)) == [("eval_return_delta", 0.5)]
    # the floor fires even on a first record: no improvement => regress
    doc = regress.evaluate([{**rec, "online": {"eval_return_delta": 0.0}}])
    cell = doc["cells"]["serve_train:linear:linear_feedback:cpux1p1:bridge"]
    assert cell["verdict"] == "regress"
    assert regress.self_test() == 0
