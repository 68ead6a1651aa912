"""jaxcheck — static analysis for JAX/TPU hazards, plus config-space validation.

Two halves, both hardware-free and executed-code-free:

* the **rule engine** (:mod:`tools.jaxcheck.rules`) parses every python file
  with stdlib ``ast`` and reports JX01–JX12 hazards in three families —
  tracing (JX01–JX05: PRNG key reuse, host syncs in hot paths,
  use-after-donate, tracer branching, retrace hazards), concurrency/lifecycle
  (JX06–JX10: lock discipline, seqlock protocol, thread lifecycle, shm
  lifecycle, callback-under-lock), and sharding consistency (JX11–JX12:
  PartitionSpec axis names vs the mesh, donated args returned un-aliased) —
  the static complement of the runtime ``CompileWatchdog`` and chaos drills;
* **configcheck** (:mod:`tools.jaxcheck.configcheck`) composes every cell of
  the ``exp × fabric`` / env / algo scenario matrix through the first-party
  Hydra-lite compose API and validates interpolations, required keys, and
  mesh/batch divisibility, folding per-cell verdicts into ``SCENARIOS.json``.

Run ``python -m tools.jaxcheck`` (see ``howto/static_analysis.md``).
Findings are gated against ``tools/jaxcheck_baseline.json``: only *new*
findings (keyed by rule + qualified name, never line numbers) fail the run.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from .core import (  # noqa: F401  (re-exported API)
    Finding,
    ModuleInfo,
    compare_to_baseline,
    load_baseline,
    prune_baseline,
    write_baseline,
)
from .rules import FAMILIES, RULES, family_of, run_rules  # noqa: F401

DEFAULT_TARGETS = ("sheeprl_tpu", "tools", "benchmarks", "examples")
EXCLUDE_DIR_NAMES = {"__pycache__", ".git", "configs", "tests"}
DEFAULT_BASELINE = os.path.join("tools", "jaxcheck_baseline.json")


def repo_root() -> str:
    """tools/jaxcheck/__init__.py → the repo checkout."""
    return os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def iter_python_files(targets: Sequence[str], root: str) -> Iterator[str]:
    """Absolute paths of the .py files under the given repo-relative targets."""
    for target in targets:
        full = target if os.path.isabs(target) else os.path.join(root, target)
        if os.path.isfile(full) and full.endswith(".py"):
            yield full
        elif os.path.isdir(full):
            for dirpath, dirnames, filenames in os.walk(full):
                dirnames[:] = sorted(d for d in dirnames if d not in EXCLUDE_DIR_NAMES)
                for name in sorted(filenames):
                    if name.endswith(".py"):
                        yield os.path.join(dirpath, name)


def analyze_source(source: str, path: str, disabled: Optional[Set[str]] = None) -> List[Finding]:
    """Run all (non-disabled) rules over one source string.  ``path`` is the
    repo-relative path used in finding keys (and for the ``algos/`` hot-loop
    heuristic of JX02)."""
    tree = ast.parse(source, filename=path)
    info = ModuleInfo(tree, path)
    return run_rules(info, disabled=disabled)


def scan(
    targets: Optional[Sequence[str]] = None,
    root: Optional[str] = None,
    disabled: Optional[Set[str]] = None,
) -> Tuple[List[Finding], int, List[str]]:
    """Scan the repo (or explicit targets).  Returns (findings, files_scanned,
    unparsable_paths).  A file that does not parse is reported, not fatal —
    the test suite owns syntax errors."""
    root = root or repo_root()
    targets = list(targets) if targets else [t for t in DEFAULT_TARGETS if os.path.exists(os.path.join(root, t))]
    findings: List[Finding] = []
    errors: List[str] = []
    count = 0
    for full in iter_python_files(targets, root):
        rel = os.path.relpath(full, root).replace(os.sep, "/")
        try:
            with open(full, encoding="utf-8") as f:
                source = f.read()
            findings.extend(analyze_source(source, rel, disabled=disabled))
        except SyntaxError:
            errors.append(rel)
        except OSError:
            errors.append(rel)
        count += 1
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings, count, errors


def counts_by_rule(findings: Sequence[Finding]) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for f in findings:
        out[f.rule] = out.get(f.rule, 0) + 1
    return {k: out[k] for k in sorted(out)}


def counts_by_family(findings: Sequence[Finding]) -> Dict[str, int]:
    """Findings bucketed by rule family (tracing/concurrency/sharding) —
    the per-family breakdown the gate folds into SCENARIOS.json."""
    out: Dict[str, int] = {family: 0 for family in FAMILIES}
    for f in findings:
        out[family_of(f.rule)] = out.get(family_of(f.rule), 0) + 1
    return out
