"""Chaos-drill registry (``python -m tools.drills``).

Every fault kind registered with the unified fault machinery
(:func:`sheeprl_tpu.utils.faults.fault_domains`) is cross-referenced
against the test suite: which tests *drill* that kind (reference it in
their body), what pytest markers gate them, and — when a pytest cache is
present — the last recorded verdict per drill.

The scan is static (``ast`` + source regex), so it never executes a test:
a drill is any test function whose source mentions a registered fault-kind
string. That is deliberately the same contract the fault schedules use —
faults are named by their ``kind`` string in configs and test bodies — so
a kind nobody's source mentions really is an undrilled kind.

Verdicts come from ``.pytest_cache/v/cache/lastfailed`` (and ``nodeids``
for the pass side). The tier-1 command runs with ``-p no:cacheprovider``,
so verdicts show ``unknown`` until someone runs the suite with the cache
enabled — the registry reports that honestly instead of guessing.
"""

from __future__ import annotations

import argparse
import ast
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

# importing a domain module registers its kinds; the list is the closed set
# of fault domains (ISSUE 20: every bridge fault lives in one of these)
DOMAIN_MODULES = (
    "sheeprl_tpu.rollout.fault_injection",
    "sheeprl_tpu.actor_learner.fault_injection",
    "sheeprl_tpu.serve.fault_injection",
    "sheeprl_tpu.online.fault_injection",
)


def registered_domains() -> Dict[str, Tuple[str, ...]]:
    for mod in DOMAIN_MODULES:
        __import__(mod)
    from sheeprl_tpu.utils.faults import fault_domains

    return fault_domains()


# ------------------------------------------------------------------ scan ----


def _module_marks(tree: ast.Module) -> List[str]:
    """Names from a module-level ``pytestmark = [pytest.mark.x, ...]``."""
    marks: List[str] = []
    for node in tree.body:
        if not isinstance(node, ast.Assign):
            continue
        if not any(isinstance(t, ast.Name) and t.id == "pytestmark" for t in node.targets):
            continue
        value = node.value
        elts = value.elts if isinstance(value, (ast.List, ast.Tuple)) else [value]
        for elt in elts:
            if isinstance(elt, ast.Attribute):
                marks.append(elt.attr)
    return marks


def _decorator_marks(fn: ast.FunctionDef) -> List[str]:
    marks: List[str] = []
    for dec in fn.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        if (
            isinstance(target, ast.Attribute)
            and isinstance(target.value, ast.Attribute)
            and target.value.attr == "mark"
        ):
            marks.append(target.attr)
    return marks


def _kind_patterns(domains: Dict[str, Sequence[str]]) -> Dict[str, re.Pattern]:
    # quoted occurrences only: the kind is a config/string contract, so a
    # drill always spells it as a string literal
    return {
        kind: re.compile(r"""['"]{}['"]""".format(re.escape(kind)))
        for kinds in domains.values()
        for kind in kinds
    }


def scan(
    tests_root: str = "tests",
    *,
    domains: Optional[Dict[str, Sequence[str]]] = None,
    cache_dir: str = ".pytest_cache",
) -> Dict[str, Any]:
    """Walk ``tests_root`` and build the drill registry."""
    domains = dict(domains) if domains is not None else dict(registered_domains())
    patterns = _kind_patterns(domains)
    kind_domains: Dict[str, List[str]] = {}
    for domain, kinds in domains.items():
        for kind in kinds:
            kind_domains.setdefault(kind, []).append(domain)

    lastfailed, known_nodeids = _load_cache(cache_dir)
    drills: List[Dict[str, Any]] = []
    for dirpath, _dirnames, filenames in sorted(os.walk(tests_root)):
        for fname in sorted(filenames):
            if not (fname.startswith("test_") or fname == "conftest.py") or not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            with open(path, "r", encoding="utf-8") as fh:
                src = fh.read()
            try:
                tree = ast.parse(src)
            except SyntaxError:
                continue
            module_marks = _module_marks(tree)
            for fn in ast.walk(tree):
                if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue
                if not fn.name.startswith("test_"):
                    continue
                segment = ast.get_source_segment(src, fn) or ""
                kinds_hit = sorted(k for k, pat in patterns.items() if pat.search(segment))
                if not kinds_hit:
                    continue
                nodeid = f"{path}::{fn.name}"
                drills.append(
                    {
                        "nodeid": nodeid,
                        "file": path,
                        "markers": sorted(set(module_marks + _decorator_marks(fn))),
                        "fault_kinds": kinds_hit,
                        "domains": sorted({d for k in kinds_hit for d in kind_domains[k]}),
                        "verdict": _verdict(nodeid, lastfailed, known_nodeids),
                    }
                )

    coverage: Dict[str, Dict[str, int]] = {
        domain: {kind: 0 for kind in kinds} for domain, kinds in domains.items()
    }
    for drill in drills:
        for kind in drill["fault_kinds"]:
            for domain in kind_domains[kind]:
                coverage[domain][kind] += 1
    uncovered = {
        domain: [kind for kind, n in kinds.items() if n == 0]
        for domain, kinds in coverage.items()
    }
    return {
        "domains": {d: list(k) for d, k in domains.items()},
        "drills": drills,
        "coverage": coverage,
        "uncovered": {d: k for d, k in uncovered.items() if k},
        "totals": {
            "drills": len(drills),
            "kinds": sum(len(k) for k in domains.values()),
            "kinds_covered": sum(
                1 for kinds in coverage.values() for n in kinds.values() if n > 0
            ),
        },
    }


# ------------------------------------------------------------- verdicts ----


def _load_cache(cache_dir: str) -> Tuple[Dict[str, Any], Set[str]]:
    def read(name: str, default: Any) -> Any:
        path = os.path.join(cache_dir, "v", "cache", name)
        try:
            with open(path, "r", encoding="utf-8") as fh:
                return json.load(fh)
        except (OSError, ValueError):
            return default

    lastfailed = read("lastfailed", {})
    nodeids = read("nodeids", [])
    return (
        lastfailed if isinstance(lastfailed, dict) else {},
        set(nodeids) if isinstance(nodeids, list) else set(),
    )


def _verdict(nodeid: str, lastfailed: Dict[str, Any], known: Set[str]) -> str:
    if nodeid in lastfailed:
        return "failed"
    if nodeid in known:
        return "passed"
    return "unknown"


# ------------------------------------------------------------------ main ----


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--tests", default="tests", help="test-suite root to scan")
    parser.add_argument("--cache", default=".pytest_cache", help="pytest cache dir for verdicts")
    parser.add_argument("--json", action="store_true", help="emit the full registry as JSON")
    args = parser.parse_args(argv)

    registry = scan(args.tests, cache_dir=args.cache)
    if args.json:
        print(json.dumps(registry, indent=1))
    else:
        totals = registry["totals"]
        print(
            f"drills: {totals['drills']} tests exercise "
            f"{totals['kinds_covered']}/{totals['kinds']} registered fault kinds"
        )
        for drill in registry["drills"]:
            marks = ",".join(drill["markers"]) or "-"
            kinds = ",".join(drill["fault_kinds"])
            print(f"  [{drill['verdict']:>7}] {drill['nodeid']} marks={marks} faults={kinds}")
        for domain, kinds in sorted(registry["uncovered"].items()):
            print(f"  UNDRILLED {domain}: {', '.join(kinds)}")
    return 1 if registry["uncovered"] else 0


if __name__ == "__main__":
    sys.exit(main())
