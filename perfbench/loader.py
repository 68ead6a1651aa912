"""Find a cell, its configuration and the per-layer readers by name.

The harness is driven by data: ``BENCHMARK.json`` names the cells, and each
name leads to a file of its own under this directory. A later PR adds a
configuration, a cell, a per-layer metric or an algorithm by adding files and
entries, never by editing one that is there.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _read_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with everything its name leads to."""

    def __init__(self, name: str, root: str = ROOT) -> None:
        self.root = root
        self.bench = _read_json(os.path.join(root, "BENCHMARK.json"))
        entries = {w["name"]: w for w in self.bench["workloads"]}
        if name not in entries:
            raise SystemExit(f"perfbench: no workload {name!r} in BENCHMARK.json (have {sorted(entries)})")
        self.name = name
        self.entry = entries[name]
        self.chips = int(self.entry["chips"])
        self.dir = os.path.join(root, self.bench["paths"][0])
        self.workload = _read_json(os.path.join(self.dir, "workloads", f"{name}.json"))
        config_entry = {c["name"]: c for c in self.bench["configs"]}[self.entry["config"]]
        self.config = _read_json(os.path.join(root, config_entry["file"]))
        self.peaks = _read_json(os.path.join(self.dir, "peaks.json"))

    # -- what the cell reports ------------------------------------------------

    def _reported(self, metrics: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
        return [m for m in metrics if self.name in m.get("workloads", [self.name])]

    @property
    def end_to_end(self) -> List[Dict[str, Any]]:
        return self._reported(self.bench["end_to_end"])

    @property
    def per_layer(self) -> List[Dict[str, Any]]:
        return self._reported(self.bench["per_layer"])

    def overrides(self, run_dir: str, stamps: str, seed: int, trace: bool) -> List[str]:
        """The program's command line: the configuration's overrides, the
        cell's traffic, then what the harness itself adds, which every recipe
        composes (README.md lists these; nothing else is set). The env factory
        is the configuration's ``env.make`` and is named nowhere else."""
        env = self.config["env"]
        if "wrapper._target_" in self.config.get("env_overrides", {}):
            raise SystemExit(f"perfbench: {self.config['name']} names its env factory in env_overrides: that is env.make's to say")
        return [
            *self.config["overrides"],
            *self.workload.get("overrides", []),
            "env=dummy",
            f"env.id={self.config['name']}",
            f"env.wrapper._target_={env.get('make', 'perfbench.env.make')}",
            f"+env.wrapper.spec={json.dumps(env, separators=(',', ':'))}",
            f"+env.wrapper.seed={seed}",
            "+env.wrapper.rank=0",
            f"+env.wrapper.stamps={stamps}",
            *[f"env.{k}={v}" for k, v in self.config.get("env_overrides", {}).items()],
            f"seed={seed}",
            "env.capture_video=False",
            "checkpoint.every=1000000000",
            "checkpoint.save_last=False",
            "fabric.callbacks=[]",
            "algo.run_test=False",
            "algo.total_steps=1000000000",
            f"log_base_dir={os.path.join(run_dir, 'logs')}",
            f"metric.telemetry.runs_jsonl={os.path.join(run_dir, 'RUNS.jsonl')}",
            f"metric.telemetry.enabled={bool(trace)}",
        ]


def algorithm(cell: Cell) -> Any:
    """``algorithms/<reference>.py``, by the ``reference`` key of the cell's
    configuration: the bridge into the program, the comparison for ``correct``
    and the trace tables (README.md lists the names it supplies)."""
    return importlib.import_module(f"perfbench.algorithms.{cell.config['reference']}")


def layer_readers(cell: Cell) -> Dict[str, Callable[[Any], Optional[float]]]:
    """``name -> read(run)`` for every per-layer metric the cell reports: the
    reader is ``layer_metrics/<name>.py`` (dots in the name become
    underscores), found by the name in ``BENCHMARK.json``."""
    readers = {}
    for metric in cell.per_layer:
        path = os.path.join(cell.dir, "layer_metrics", metric["name"].replace(".", "_") + ".py")
        spec = importlib.util.spec_from_file_location(f"perfbench_layer_{metric['name'].replace('.', '_')}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        readers[metric["name"]] = module.read
    return readers
