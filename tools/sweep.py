"""Budget-tiered scenario sweep runner: execute the grid, don't just compose it.

The static config matrix (``tools/jaxcheck`` → ``config_cells`` in
SCENARIOS.json) proves 132 scenario configs *compose*; this runner proves a
curated slice of the scenario plane actually *runs and learns*. Each grid
cell is one CLI training run (a subprocess of ``python -m sheeprl_tpu``)
drained through budget tiers:

``smoke``
    ``dry_run=True`` one-update run on the CPU backend — compile + step +
    checkpoint plumbing. Verdict ``smoke_pass`` requires exit 0 AND a
    completed run-registry record.
``learn``
    A short CPU learning check reusing the ``benchmarks/learning_checks.sh``
    method: the run prints per-episode rewards ("Rank-0: ...
    reward_env_N=R" at ``metric.log_level=1``), and the verdict compares the
    first fifth of episodes against the last. ``learn_pass`` requires
    ``late >= min_late`` and ``late - early >= min_gain``. The learn tier
    leans on ``algo.fused_rollout`` (ops/rollout_scan.py) so a 6-figure-step
    check costs seconds, and on ``env.variants.*`` so domain-randomized
    scenarios are first-class cells.
``chip``
    Cells whose recipes need a real accelerator (pixel Dreamer learning,
    XL scenario-matrix sweeps) are NOT run here: each keeps the verdict
    ``deferred_chip`` and carries the command to run on a TPU.

Executed verdicts land in SCENARIOS.json as ``executed_cells`` /
``executed_summary`` — next to (never replacing) the static ``config_cells``
— and ``tools/regress.py`` carries both sections through its rewrites
(PRESERVED_KEYS). ``--stats`` summarizes the executed section.

Sweep knobs (the ``sweep.*`` surface):

``--only GLOB``      run the matching subset of cell keys (fnmatch)
``--max-tier T``     stop the ladder at ``smoke`` or ``learn``
``--budget-s S``     wall-clock budget; cells past it report ``skipped_budget``
``--scenarios-out``  the verdict-grid file to fold ``executed_cells`` into
``--keep-logs DIR``  retain per-cell run dirs (default: tmpdir, deleted)
``--list``           print the grid (key, tier, bars) without running

Usage::

    python tools/sweep.py --list
    python tools/sweep.py --only 'sweep:ppo:*'
    python tools/sweep.py --stats
"""

from __future__ import annotations

import argparse
import fnmatch
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_SCENARIOS = os.path.join(REPO_ROOT, "SCENARIOS.json")

# ------------------------------------------------------------------ grid ----

# overrides shared by every executed cell: no video/memmap IO, no eval
# episode, reward lines on stdout, telemetry+registry into the cell's run dir
_COMMON = (
    "fabric=cpu",
    "env.capture_video=False",
    "buffer.memmap=False",
    "algo.run_test=False",
    "checkpoint.save_last=False",
    "metric.log_level=1",
    "metric.log_every=1000000000",
    "metric.telemetry.enabled=True",
    "metric.telemetry.poll_interval=0.0",
)

# variant bundles (envs/variants.py VARIANT_ORDER names)
_PHYS = "phys_size,phys_speed,phys_mass"
_ALL6 = "phys_size,phys_speed,phys_mass,sticky_actions,reward_delay,distractors"


def _scenario_id(env_id: str, variants: str) -> str:
    """compose_variant_env_id's naming, stdlib-side: base+v1+v2..."""
    return env_id + "".join("+" + v for v in variants.split(",") if v) if variants else env_id


def _learn_fused(
    algo: str,
    env_id: str,
    variants: str,
    *,
    total_steps: int,
    min_late: float,
    min_gain: float,
    envs: int = 64,
    rollout: int = 64,
    extra: tuple = (),
    timeout_s: float = 900.0,
) -> Dict[str, Any]:
    argv = [
        f"exp={algo}",
        "env=gym",
        f"env.id={env_id}",
        f"env.num_envs={envs}",
        f"algo.rollout_steps={rollout}",
        "algo.fused_rollout=True",
        f"algo.total_steps={total_steps}",
        "algo.dense_units=64",
        "algo.mlp_layers=1",
        "seed=7",
    ]
    if variants:
        argv.append(f"env.variants.enabled=[{variants}]")
    return {
        "key": f"sweep:{algo}:{_scenario_id(env_id, variants)}",
        "tier": "learn",
        "argv": argv + list(extra),
        "timeout_s": timeout_s,
        "min_late": min_late,
        "min_gain": min_gain,
    }


def _smoke(algo: str, scenario: str, argv: List[str], timeout_s: float = 600.0) -> Dict[str, Any]:
    return {
        "key": f"sweep:{algo}:{scenario}",
        "tier": "smoke",
        "argv": ["dry_run=True"] + argv,
        "timeout_s": timeout_s,
    }


# tiny-but-real Dreamer-V3 dims shared by the pixel smoke cells (the proven
# recipe from tests/test_envs/test_jittable_pixels.py)
_DV3_TINY = [
    "algo.per_rank_batch_size=1",
    "algo.per_rank_sequence_length=1",
    "buffer.size=8",
    "algo.learning_starts=0",
    "algo.replay_ratio=1",
    "algo.horizon=8",
    "algo.dense_units=8",
    "algo.mlp_layers=1",
    "algo.world_model.encoder.cnn_channels_multiplier=2",
    "algo.world_model.recurrent_model.recurrent_state_size=8",
    "algo.world_model.representation_model.hidden_size=8",
    "algo.world_model.transition_model.hidden_size=8",
    "algo.world_model.discrete_size=4",
    "algo.world_model.stochastic_size=4",
    "env.num_envs=2",
]


def build_grid() -> List[Dict[str, Any]]:
    """The executed scenario grid: 20 learn cells over the fused jittable
    plane (3 on-policy algos x 2 twins x variant bundles), 5 host-loop smoke
    cells (off-policy + pixel Dreamer), 3 chip deferrals. Bars (min_late /
    min_gain) are the measured-with-margin values from the committed sweep —
    see executed_cells in SCENARIOS.json."""
    ppo = lambda env, var, **kw: _learn_fused("ppo", env, var, **kw)
    a2c = lambda env, var, **kw: _learn_fused("a2c", env, var, **kw)
    rec = lambda env, var, **kw: _learn_fused("ppo_recurrent", env, var, **kw)

    ppo_extra = ("algo.per_rank_batch_size=1024", "algo.update_epochs=4")
    # Pendulum needs the classic continuous-control recipe: short effective
    # horizon (gamma 0.9), lower lr, clipped grads, more epochs per batch
    ppo_pend = (
        "algo.per_rank_batch_size=1024",
        "algo.update_epochs=10",
        "algo.gamma=0.9",
        "algo.optimizer.lr=3e-4",
        "algo.max_grad_norm=0.5",
    )
    # fused recurrent windowing: 64x64 rollout -> 256 16-step sequences, 8
    # minibatches; inherits update_epochs=8 from exp=ppo_recurrent
    rec_extra = (
        "algo.per_rank_sequence_length=16",
        "algo.per_rank_num_batches=8",
        "algo.per_rank_batch_size=64",
    )
    # A2C: one full-batch gradient step per update -> small rollouts, many updates
    a2c_kw = dict(envs=32, rollout=32, extra=("algo.per_rank_batch_size=1024",))

    grid: List[Dict[str, Any]] = [
        # --- PPO x CartPole: every variant axis alone, then all six ---
        ppo("CartPole-v1", "", total_steps=262144, min_late=60, min_gain=10, extra=ppo_extra),
        ppo("CartPole-v1", _PHYS, total_steps=262144, min_late=60, min_gain=10, extra=ppo_extra),
        ppo("CartPole-v1", "sticky_actions", total_steps=262144, min_late=60, min_gain=10, extra=ppo_extra),
        ppo("CartPole-v1", "reward_delay", total_steps=262144, min_late=60, min_gain=10, extra=ppo_extra),
        ppo("CartPole-v1", "distractors", total_steps=262144, min_late=60, min_gain=10, extra=ppo_extra),
        ppo("CartPole-v1", _ALL6, total_steps=262144, min_late=50, min_gain=10, extra=ppo_extra),
        # --- PPO x Pendulum (continuous; returns in [-1600, 0]) ---
        ppo("Pendulum-v1", "", total_steps=819200, min_late=-1150, min_gain=50, extra=ppo_pend),
        ppo("Pendulum-v1", _PHYS, total_steps=819200, min_late=-1150, min_gain=50, extra=ppo_pend),
        ppo("Pendulum-v1", "sticky_actions", total_steps=819200, min_late=-1150, min_gain=50, extra=ppo_pend),
        ppo("Pendulum-v1", _ALL6, total_steps=819200, min_late=-1200, min_gain=50, extra=ppo_pend),
        # --- A2C (fused port) ---
        a2c("CartPole-v1", "", total_steps=262144, min_late=50, min_gain=10, **a2c_kw),
        a2c("CartPole-v1", _PHYS, total_steps=262144, min_late=50, min_gain=10, **a2c_kw),
        a2c("CartPole-v1", "sticky_actions", total_steps=262144, min_late=50, min_gain=10, **a2c_kw),
        a2c("CartPole-v1", "distractors", total_steps=262144, min_late=50, min_gain=10, **a2c_kw),
        # (A2C x Pendulum was trialed and dropped: one full-batch gradient
        # step per update does not move continuous Pendulum inside a CPU
        # budget — the continuous twins are covered by PPO / recurrent PPO)
        # reward_delay is the hardest credit-assignment cell for A2C's
        # single full-batch step per update: 256k steps lands just under the
        # bar (late ~49.9), 512k clears it
        a2c("CartPole-v1", "reward_delay", total_steps=524288, min_late=50, min_gain=10, **a2c_kw),
        # --- recurrent PPO (fused port; LSTM carry through the scan) ---
        rec("CartPole-v1", "", total_steps=327680, min_late=60, min_gain=10, extra=rec_extra),
        rec("CartPole-v1", "sticky_actions", total_steps=327680, min_late=50, min_gain=10, extra=rec_extra),
        rec("CartPole-v1", _PHYS, total_steps=327680, min_late=50, min_gain=10, extra=rec_extra),
        rec("CartPole-v1", _ALL6, total_steps=327680, min_late=50, min_gain=10, extra=rec_extra),
        rec(
            "Pendulum-v1", "", total_steps=655360, min_late=-1250, min_gain=30,
            extra=rec_extra + ("algo.gamma=0.9", "algo.optimizer.lr=3e-4", "algo.max_grad_norm=0.5"),
        ),
        # --- host-loop + pixel smoke (learning recipes are minutes-long on
        # one CPU core: benchmarks/learning_checks.sh keeps those) ---
        _smoke(
            "sac",
            "Pendulum-v1",
            ["exp=sac", "env=gym", "env.id=Pendulum-v1", "env.num_envs=2",
             "algo.learning_starts=0", "algo.per_rank_batch_size=16"],
        ),
        _smoke(
            "droq",
            "Pendulum-v1",
            ["exp=droq", "env=gym", "env.id=Pendulum-v1", "env.num_envs=2",
             "algo.learning_starts=0", "algo.per_rank_batch_size=16"],
        ),
        _smoke(
            "dreamer_v3",
            "CartPole-v1",
            ["exp=dreamer_v3", "env=gym", "env.id=CartPole-v1",
             "algo.cnn_keys.encoder=[]", "algo.mlp_keys.encoder=[state]",
             "algo.cnn_keys.decoder=[]", "algo.mlp_keys.decoder=[state]"] + _DV3_TINY,
        ),
        _smoke(
            "dreamer_v3",
            "PixelPointmass-v0",
            ["exp=dreamer_v3", "env=pixel_pointmass", "env.screen_size=16",
             "algo.cnn_keys.encoder=[rgb]", "algo.mlp_keys.encoder=[]"] + _DV3_TINY,
        ),
        _smoke(
            "dreamer_v3",
            "PixelPendulum-v0",
            ["exp=dreamer_v3", "env=pixel_pendulum", "env.screen_size=16",
             "algo.cnn_keys.encoder=[rgb]", "algo.mlp_keys.encoder=[]"] + _DV3_TINY,
        ),
    ]
    grid += chip_deferrals()
    return grid


def chip_deferrals() -> List[Dict[str, Any]]:
    """Chip-tier cells: full-resolution pixel Dreamer learning checks and the
    XL scenario-matrix sweep. Never run here — each carries the command
    (from the repo root, on a TPU) that would produce its verdict."""

    def dv3_pixel(env_cfg: str, scenario: str) -> Dict[str, Any]:
        # `:tpu` keeps the deferral distinct from the CPU smoke cell over the
        # same scenario. A Dreamer-V3 learning check over the jittable env
        # (the pixel_catcher recipe of benchmarks/learning_checks.sh pointed
        # at the dependency-free pixel family); verdict = first-fifth vs
        # last-fifth of the Rank-0 reward lines
        return {
            "key": f"sweep:dreamer_v3:{scenario}:tpu",
            "tier": "chip",
            "command": [
                "python", "-m", "sheeprl_tpu", "exp=dreamer_v3", f"env={env_cfg}",
                "env.num_envs=4", "env.capture_video=False",
                "buffer.memmap=False", "buffer.size=60000",
                "algo.total_steps=30720", "algo.learning_starts=1024",
                "algo.replay_ratio=0.5", "algo.dense_units=128", "algo.mlp_layers=1",
                "algo.world_model.discrete_size=16", "algo.world_model.stochastic_size=16",
                "algo.world_model.encoder.cnn_channels_multiplier=8",
                "algo.world_model.recurrent_model.recurrent_state_size=128",
                "algo.world_model.transition_model.hidden_size=128",
                "algo.world_model.representation_model.hidden_size=128",
                "algo.cnn_keys.encoder=[rgb]", "algo.mlp_keys.encoder=[]",
                "algo.run_test=False", "checkpoint.every=10000000",
                "checkpoint.save_last=False", "metric.log_level=1",
                "metric.log_every=4000",
            ],
        }

    return [
        dv3_pixel("pixel_pointmass", "PixelPointmass-v0"),
        dv3_pixel("pixel_pendulum", "PixelPendulum-v0"),
        # the batched domain-randomization superstep at 65536 scenario
        # instances; --record appends train:ppo:scenario_sweep:tpu* cells
        # gated by the 100k sps_env floor in tools/regress.py
        {
            "key": "sweep:ppo:scenario_sweep_xl:tpu",
            "tier": "chip",
            "command": [
                "python", "benchmarks/scenario_sweep.py", "--envs", "65536",
                "--rollout-steps", "64", "--updates", "10",
                "--repeats", "3", "--record",
            ],
        },
    ]


# -------------------------------------------------------------- execution ----

_REWARD_RE = re.compile(r"reward_env_\d+=(-?\d+(?:\.\d+)?(?:e-?\d+)?)", re.IGNORECASE)


def reward_trend(stdout: str) -> Optional[Dict[str, float]]:
    """First-fifth vs last-fifth of the per-episode reward lines — the
    benchmarks/learning_checks.sh method, automated."""
    rewards = [float(m.group(1)) for m in _REWARD_RE.finditer(stdout)]
    if len(rewards) < 10:
        return None
    fifth = max(1, len(rewards) // 5)
    return {
        "episodes": len(rewards),
        "rew_first_fifth": round(sum(rewards[:fifth]) / fifth, 2),
        "rew_last_fifth": round(sum(rewards[-fifth:]) / fifth, 2),
        "rew_best": round(max(rewards), 2),
    }


def _registry_record(run_dir: str) -> Optional[Dict[str, Any]]:
    path = os.path.join(run_dir, "RUNS.jsonl")
    try:
        with open(path) as f:
            recs = [json.loads(line) for line in f if line.strip()]
    except (OSError, ValueError):
        return None
    recs = [r for r in recs if isinstance(r, dict) and r.get("kind") == "train"]
    return recs[-1] if recs else None


def run_cell(cell: Dict[str, Any], work_dir: str) -> Dict[str, Any]:
    """Execute one smoke/learn cell as a subprocess and score it."""
    run_dir = os.path.join(work_dir, cell["key"].replace(":", "_").replace("+", "-"))
    os.makedirs(run_dir, exist_ok=True)
    argv = (
        [sys.executable, "-m", "sheeprl_tpu"]
        + cell["argv"]
        + list(_COMMON)
        + [
            f"metric.telemetry.runs_jsonl={run_dir}/RUNS.jsonl",
            f"log_base_dir={run_dir}/logs",
        ]
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.time()
    try:
        proc = subprocess.run(
            argv, cwd=REPO_ROOT, env=env, capture_output=True, text=True,
            timeout=float(cell.get("timeout_s", 900.0)),
        )
        rc: Optional[int] = proc.returncode
        stdout = proc.stdout or ""
        stderr = proc.stderr or ""
    except subprocess.TimeoutExpired as exc:
        rc, stdout, stderr = None, str(exc.stdout or ""), str(exc.stderr or "")
    wall_s = round(time.time() - t0, 1)

    rec = _registry_record(run_dir)
    result: Dict[str, Any] = {"tier": cell["tier"], "wall_s": wall_s, "t": round(t0, 1)}
    if rc is None:
        result["verdict"] = f"{cell['tier']}_fail"
        result["error"] = f"timeout after {cell.get('timeout_s')}s"
    elif cell["tier"] == "smoke":
        ok = rc == 0 and rec is not None and rec.get("outcome") == "completed"
        result["verdict"] = "smoke_pass" if ok else "smoke_fail"
        if not ok:
            result["error"] = f"rc={rc}, registry={'missing' if rec is None else rec.get('outcome')}"
    else:
        trend = reward_trend(stdout)
        result["min_late"] = cell["min_late"]
        result["min_gain"] = cell["min_gain"]
        if rc != 0 or trend is None:
            result["verdict"] = "learn_fail"
            result["error"] = f"rc={rc}, " + ("no reward trend (<10 episodes)" if trend is None else "run failed")
        else:
            result.update(trend)
            gained = trend["rew_last_fifth"] - trend["rew_first_fifth"]
            ok = trend["rew_last_fifth"] >= cell["min_late"] and gained >= cell["min_gain"]
            result["verdict"] = "learn_pass" if ok else "learn_fail"
    if rec is not None:
        for k in ("sps_env", "backend", "variant", "train_dispatches"):
            if rec.get(k) is not None:
                result[k] = rec[k]
    if result["verdict"].endswith("_fail"):
        tail = "\n".join((stdout + "\n" + stderr).strip().splitlines()[-15:])
        result["log_tail"] = tail[-2000:]
    return result


# ------------------------------------------------------------------ output ----


def fold_executed(
    results: Dict[str, Dict[str, Any]],
    deferred: List[Dict[str, Any]],
    scenarios_path: str,
) -> Dict[str, Any]:
    """Merge executed verdicts into SCENARIOS.json next to the static
    sections. Cells accumulate across partial sweeps (merge by key);
    tools/regress.py PRESERVED_KEYS carries both keys through its rewrites."""
    try:
        with open(scenarios_path) as f:
            doc = json.load(f)
        if not isinstance(doc, dict):
            doc = {}
    except (OSError, ValueError):
        doc = {"schema": 1}
    cells = dict(doc.get("executed_cells") or {})
    cells.update(results)
    for cell in deferred:
        cells[cell["key"]] = {
            "tier": "chip",
            "verdict": "deferred_chip",
            "command": cell["command"],
        }
    doc["executed_cells"] = dict(sorted(cells.items()))
    counts: Dict[str, int] = {}
    for c in doc["executed_cells"].values():
        counts[c["verdict"]] = counts.get(c["verdict"], 0) + 1
    doc["executed_summary"] = {
        "cells": len(doc["executed_cells"]),
        "verdicts": dict(sorted(counts.items())),
        "generated_t": round(time.time(), 1),
    }
    tmp = scenarios_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, default=str)
        f.write("\n")
    os.replace(tmp, scenarios_path)
    return doc["executed_summary"]


def stats(scenarios_path: str) -> Dict[str, Any]:
    """``--stats``: tier reached, verdict and sps per executed
    cell, plus the rollup — read-only over SCENARIOS.json."""
    try:
        with open(scenarios_path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return {"error": f"unreadable {scenarios_path}"}
    cells = doc.get("executed_cells") or {}
    rows = []
    for key, c in sorted(cells.items()):
        row = {"cell": key, "tier": c.get("tier"), "verdict": c.get("verdict")}
        for k in ("sps_env", "rew_first_fifth", "rew_last_fifth", "episodes", "wall_s", "command"):
            if c.get(k) is not None:
                row[k] = c[k]
        rows.append(row)
    by_verdict: Dict[str, int] = {}
    for c in cells.values():
        by_verdict[c.get("verdict", "?")] = by_verdict.get(c.get("verdict", "?"), 0) + 1
    return {
        "cells": len(rows),
        "by_verdict": dict(sorted(by_verdict.items())),
        "executed_summary": doc.get("executed_summary"),
        "rows": rows,
    }


# -------------------------------------------------------------------- main ----


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--scenarios-out", default=DEFAULT_SCENARIOS, help="verdict-grid file")
    parser.add_argument("--only", metavar="GLOB", help="run only matching cell keys")
    parser.add_argument(
        "--max-tier", choices=("smoke", "learn"), default="learn",
        help="highest tier to execute (smoke skips every learn cell)",
    )
    parser.add_argument(
        "--budget-s", type=float, default=0.0,
        help="wall-clock budget; 0 = unlimited. Cells past it report skipped_budget",
    )
    parser.add_argument("--keep-logs", metavar="DIR", help="retain per-cell run dirs here")
    parser.add_argument("--list", action="store_true", help="print the grid and exit")
    parser.add_argument("--stats", action="store_true", help="print the executed-cell rollup and exit")
    args = parser.parse_args(argv)

    if args.stats:
        print(json.dumps(stats(args.scenarios_out), indent=1))
        return 0

    grid = build_grid()
    if args.only:
        grid = [c for c in grid if fnmatch.fnmatch(c["key"], args.only)]
    if args.list:
        for cell in grid:
            bars = (
                f" min_late={cell['min_late']} min_gain={cell['min_gain']}"
                if cell["tier"] == "learn"
                else ""
            )
            print(f"{cell['tier']:5s} {cell['key']}{bars}")
        return 0

    chip = [c for c in grid if c["tier"] == "chip"]
    runnable = [c for c in grid if c["tier"] != "chip"]
    if args.max_tier == "smoke":
        runnable = [c for c in runnable if c["tier"] == "smoke"]

    work_dir = args.keep_logs or tempfile.mkdtemp(prefix="sheeprl_tpu_sweep_")
    os.makedirs(work_dir, exist_ok=True)
    t0 = time.time()
    results: Dict[str, Dict[str, Any]] = {}
    failed = 0
    for cell in runnable:
        if args.budget_s and time.time() - t0 > args.budget_s:
            results[cell["key"]] = {"tier": cell["tier"], "verdict": "skipped_budget"}
            print(f"SKIP   {cell['key']} (budget {args.budget_s:.0f}s exhausted)", flush=True)
            continue
        res = run_cell(cell, work_dir)
        results[cell["key"]] = res
        failed += res["verdict"].endswith("_fail")
        detail = ""
        if "rew_last_fifth" in res:
            detail = f" rew {res['rew_first_fifth']} -> {res['rew_last_fifth']} ({res['episodes']} eps)"
        if res.get("sps_env"):
            detail += f", {res['sps_env'] / 1000:.1f}k sps"
        marker = "PASS  " if res["verdict"].endswith("_pass") else "FAIL  "
        print(f"{marker} {cell['key']} [{res['verdict']}] {res['wall_s']}s{detail}", flush=True)
        if res["verdict"].endswith("_fail") and res.get("log_tail"):
            print("  " + "\n  ".join(res["log_tail"].splitlines()[-6:]), flush=True)

    summary = fold_executed(results, chip, args.scenarios_out)
    if not args.keep_logs:
        shutil.rmtree(work_dir, ignore_errors=True)
    print(
        f"# {summary['cells']} executed cells -> {args.scenarios_out} "
        f"{json.dumps(summary['verdicts'])}",
        flush=True,
    )
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
