"""A copy of the benchmark at widths a CPU test can hold: the real files with
the sizes shrunk, in a temporary root that the harness's loader is pointed at.

Each configuration is shrunk by the rule of its own algorithm, found by the
``reference`` key of its file: ``tests/test_perfbench/tiny_<reference>.py``
with ``tiny_config(name, precision, root)`` (the configuration at tiny widths,
its ``overrides`` grown by what sets them), ``LIMITS`` (what a sound run
keeps at those widths: written into the algorithm's cells, and the names a
cell's own limits may use) and, if 4 do not do, ``WARM_STEPS``."""

from __future__ import annotations

import importlib
import json
import os
import shutil
from typing import Any

from perfbench.loader import ROOT


def rule(reference: str) -> Any:
    """``tiny_<reference>.py``: how a configuration of that algorithm is shrunk."""
    return importlib.import_module(f"tests.test_perfbench.tiny_{reference}")


def rule_of(name: str, root: str = ROOT) -> Any:
    """The rule of the configuration ``name``, by its file's ``reference`` key."""
    with open(os.path.join(root, "perfbench", "configs", f"{name}.json")) as f:
        return rule(json.load(f)["reference"])


def make_root(tmp: str, precision: str = "fp32", source: str = ROOT) -> str:
    """``tmp`` becomes a benchmark root: ``source``'s ``BENCHMARK.json``,
    readers and peaks, with every configuration at tiny widths and every cell
    under its algorithm's ``LIMITS`` and ``WARM_STEPS`` (4 where it names none)."""
    bench_dir = os.path.join(tmp, "perfbench")
    os.makedirs(os.path.join(bench_dir, "configs"))
    shutil.copytree(os.path.join(source, "perfbench", "layer_metrics"), os.path.join(bench_dir, "layer_metrics"))
    shutil.copytree(os.path.join(source, "perfbench", "workloads"), os.path.join(bench_dir, "workloads"))
    shutil.copy(os.path.join(source, "perfbench", "peaks.json"), bench_dir)
    shutil.copy(os.path.join(source, "BENCHMARK.json"), tmp)
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        bench = json.load(f)
    rules = {}
    for entry in bench["configs"]:
        rules[entry["name"]] = rule_of(entry["name"], source)
        with open(os.path.join(tmp, entry["file"]), "w") as f:
            json.dump(rules[entry["name"]].tiny_config(entry["name"], precision, source), f)
    for cell in bench["workloads"]:
        path = os.path.join(bench_dir, "workloads", f"{cell['name']}.json")
        with open(path) as f:
            workload = json.load(f)
        workload["warm_steps"] = getattr(rules[cell["config"]], "WARM_STEPS", 4)
        workload["limits"] = rules[cell["config"]].LIMITS
        with open(path, "w") as f:
            json.dump(workload, f)
    return tmp
