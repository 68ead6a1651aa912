"""``python3 perfbench/check_line.py --workload <cell> --trace <0|1> [FILE]``

Reads a run's output (a file, or standard input) and holds its last line to
what the driver asks of a result line: a JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` (each metric of the workload, as
``BENCHMARK.json`` lists it for a run of that kind, as its value and unit) and
``device`` (``platform``, ``kind``, ``count``, ``memory_peak_bytes`` and, in a
traced run, ``window_s`` above 0 and ``busy_s`` above 0 and at most
``window_s``). Other keys are ignored. Exit code 0 and ``ok``, or 1 and what
is wrong, one fault a line.

A timed run reports the workload's end-to-end metrics and a traced run its
per-layer metrics (``run.py``); a per-layer metric whose reader found nothing
to read is missing from the line, and is a fault here as it is to the driver.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _number(value: Any) -> bool:
    return isinstance(value, (int, float)) and not isinstance(value, bool) and math.isfinite(value)


def expected_metrics(workload: str, traced: bool, root: str = ROOT) -> Dict[str, str]:
    """``name -> unit`` of the metrics a run of that kind reports in that cell."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if workload not in {w["name"] for w in bench["workloads"]}:
        raise SystemExit(f"check_line: no workload {workload!r} in BENCHMARK.json")
    listed = bench["per_layer"] if traced else bench["end_to_end"]
    return {m["name"]: m["unit"] for m in listed if workload in m.get("workloads", [workload])}


def faults(line: str, workload: str, traced: bool, root: str = ROOT) -> List[str]:
    """What is wrong with a run's last line; empty where nothing is."""
    try:
        result = json.loads(line)
    except ValueError as err:
        return [f"the last line is not JSON: {err}"]
    if not isinstance(result, dict):
        return ["the last line is not a JSON object"]
    found = [f"no key {key!r}" for key in ("correct", "attempted", "failed", "metrics", "device") if key not in result]
    if found:
        return found
    if not isinstance(result["correct"], bool):
        found.append("correct is not true or false")
    for key in ("attempted", "failed"):
        if not _number(result[key]) or result[key] < 0:
            found.append(f"{key} is not a number of 0 or more")
    metrics = result["metrics"] if isinstance(result["metrics"], dict) else {}
    for name, unit in expected_metrics(workload, traced, root).items():
        got = metrics.get(name)
        if not isinstance(got, dict) or not _number(got.get("value")):
            found.append(f"metrics.{name} is not given as a value and a unit")
        elif got.get("unit") != unit:
            found.append(f"metrics.{name} has the unit {got.get('unit')!r}, not {unit!r}")
    device = result["device"] if isinstance(result["device"], dict) else {}
    for key in ("platform", "kind"):
        if not isinstance(device.get(key), str) or not device.get(key):
            found.append(f"device.{key} is not a name")
    if not _number(device.get("count")) or device.get("count") < 1:
        found.append("device.count is not a number of 1 or more")
    if not _number(device.get("memory_peak_bytes")) or device.get("memory_peak_bytes") <= 0:
        found.append("device.memory_peak_bytes is not a number above 0")
    if traced:
        window, busy = device.get("window_s"), device.get("busy_s")
        if not _number(window) or window <= 0:
            found.append("device.window_s is not a number above 0")
        if not _number(busy) or busy <= 0:
            found.append("device.busy_s is not a number above 0")
        elif _number(window) and busy > window:
            found.append("device.busy_s is above device.window_s")
    return found


def last_line(text: str) -> str:
    lines = [line for line in text.splitlines() if line.strip()]
    return lines[-1] if lines else ""


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    parser.add_argument("file", nargs="?", help="the run's output; standard input if not given")
    args = parser.parse_args(argv)
    if args.file:
        with open(args.file) as f:
            text = f.read()
    else:
        text = sys.stdin.read()
    found = faults(last_line(text), args.workload, bool(args.trace))
    for fault in found:
        print(f"check_line: {fault}")
    if not found:
        print("check_line: ok")
    return 1 if found else 0


if __name__ == "__main__":
    sys.exit(main())
