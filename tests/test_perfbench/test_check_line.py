"""``perfbench/check_line.py``: a run's last line against what the driver asks
of it, on the line PR 32 was refused for and on a sound line of each kind."""

import json
import os

import pytest

from perfbench import check_line

CELL = "glm47_flash_ep8.train"
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 11480049152}


def _line(traced, **device):
    metrics = {name: {"value": 1.5, "unit": unit} for name, unit in check_line.expected_metrics(CELL, traced).items()}
    return {"correct": True, "attempted": 1280, "failed": 0, "metrics": metrics, "device": {**DEVICE, **device}, "compared": {}}


@pytest.mark.parametrize("traced", [False, True], ids=["timed", "traced"])
def test_a_sound_line_of_each_kind_passes(traced, tmp_path, capsys):
    line = _line(traced, **({"busy_s": 0.582, "window_s": 0.989} if traced else {}))
    assert check_line.faults(json.dumps(line), CELL, traced) == []
    out = tmp_path / "run.log"
    out.write_text("[perfbench] the whole run took 300.0s\n" + json.dumps(line) + "\n")
    assert check_line.main(["--workload", CELL, "--trace", str(int(traced)), str(out)]) == 0
    assert capsys.readouterr().out.strip() == "check_line: ok"


def test_the_line_pr_32_was_refused_for_fails():
    """Its traced stretch began after the window's last vector step: ``"busy_s": null, "window_s": 0.0``."""
    found = check_line.faults(json.dumps(_line(True, busy_s=None, window_s=0.0)), CELL, True)
    assert found == ["device.window_s is not a number above 0", "device.busy_s is not a number above 0"]


@pytest.mark.parametrize("broken, fault", [
    (lambda l: l.pop("device"), "no key 'device'"),
    (lambda l: l["metrics"].pop("device.idle_share"), "metrics.device.idle_share is not given as a value and a unit"),
    (lambda l: l["metrics"]["device.idle_share"].update(unit="share"), "metrics.device.idle_share has the unit 'share', not '%'"),
    (lambda l: l["device"].update(busy_s=1.2), "device.busy_s is above device.window_s"),
    (lambda l: l["device"].update(memory_peak_bytes=None), "device.memory_peak_bytes is not a number above 0"),
    (lambda l: l.update(correct="yes"), "correct is not true or false"),
], ids=["no_device", "metric_missing", "unit", "busy_over_window", "no_peak", "correct"])  # fmt: skip
def test_each_fault_is_named(broken, fault):
    line = _line(True, busy_s=0.582, window_s=0.989)
    broken(line)
    assert check_line.faults(json.dumps(line), CELL, True) == [fault]


def test_what_is_no_json_object_fails(tmp_path):
    assert check_line.faults("perfbench: the program left with 1", CELL, False)[0].startswith("the last line is not JSON")
    assert check_line.faults("[1, 2]", CELL, False) == ["the last line is not a JSON object"]
    out = tmp_path / "run.log"
    out.write_text("Traceback (most recent call last):\n")
    assert check_line.main(["--workload", CELL, "--trace", "0", str(out)]) == 1


def _texts(bench):
    for kind in ("configs", "workloads"):
        for entry in bench[kind]:
            yield f"{kind}.{entry['name']}.why", entry["why"]
    for entry in bench["configs"]:
        yield f"configs.{entry['name']}.source", entry["source"]
    for entry in bench["per_layer"]:
        yield f"per_layer.{entry['name']}.layer", entry["layer"]
    for i, word in enumerate(bench["command"]):
        yield f"command.{i}", word


def test_every_text_of_benchmark_json_has_the_length_the_driver_takes():
    """PR 33 was refused before any run for a ``why`` of 233 characters: 1 to 200, printable, on one line."""
    with open(os.path.join(os.path.dirname(check_line.__file__), "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [where for where, text in _texts(bench) if not (1 <= len(text) <= 200 and text.isprintable())] == []
