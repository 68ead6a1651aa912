"""tools/sweep.py unit tests (ISSUE 19 tentpole part 5).

The budget-tiered sweep runner must (a) enumerate a grid with >= 20 learn
cells all riding the fused path, (b) score reward trends with the
learning_checks.sh method, (c) give chip-tier cells a ``deferred_chip``
verdict that carries the command to run on a TPU, and (d) fold executed
verdicts into SCENARIOS.json without clobbering the static sections (the
half tools/regress.py PRESERVED_KEYS carries through its rewrites).

Everything here is pure-stdlib — no subprocess, no jax.
"""

import json
import os

from tools import sweep


def test_grid_has_twenty_learn_cells_all_fused():
    grid = sweep.build_grid()
    learn = [c for c in grid if c["tier"] == "learn"]
    smoke = [c for c in grid if c["tier"] == "smoke"]
    assert len(learn) >= 20, f"acceptance floor: >=20 learn cells, got {len(learn)}"
    assert smoke, "the cheap dry-run tier must cover the off-policy algos too"
    keys = [c["key"] for c in grid]
    assert len(keys) == len(set(keys)), "duplicate cell keys would merge verdicts"
    for cell in learn:
        assert "algo.fused_rollout=True" in cell["argv"], cell["key"]
        assert cell["min_gain"] > 0, "a learn cell must demand an actual reward trend"
    for cell in smoke:
        assert cell["argv"][0] == "dry_run=True"
    # the grid spans algos and scenario compositions, not one env repeated
    algos = {c["key"].split(":")[1] for c in grid}
    assert {"ppo", "a2c", "ppo_recurrent", "dreamer_v3", "sac", "droq"} <= algos
    variant_cells = [c for c in learn if "+" in c["key"]]
    assert len(variant_cells) >= 10, "most learn cells should exercise variants"


def test_chip_deferrals_do_not_collide_with_smoke_keys():
    executed_keys = {c["key"] for c in sweep.build_grid() if c["tier"] != "chip"}
    chip = sweep.chip_deferrals()
    assert chip, "chip tier must defer at least the pixel-Dreamer cells"
    for cell in chip:
        assert cell["key"] not in executed_keys, "chip key would overwrite an executed verdict"
        assert cell["command"][0] == "python" and len(cell["command"]) > 1, cell["key"]


def test_reward_trend_first_vs_last_fifth():
    lines = [
        f"Rank-0: policy_step={i * 64}, reward_env_{i % 4}={float(10 + i)}" for i in range(20)
    ]
    trend = sweep.reward_trend("\n".join(lines))
    assert trend["episodes"] == 20
    assert trend["rew_first_fifth"] == 11.5  # mean of 10..13
    assert trend["rew_last_fifth"] == 27.5  # mean of 26..29
    assert trend["rew_best"] == 29.0
    # negative / scientific-notation rewards parse too (Pendulum)
    assert sweep.reward_trend(
        "\n".join(f"Rank-0: policy_step=1, reward_env_0={r}" for r in ["-1200.5"] * 5 + ["-1.2e2"] * 5)
    )["rew_last_fifth"] == -120.0
    # fewer than 10 episodes -> no verdict, not a crash
    assert sweep.reward_trend(lines[0]) is None
    assert sweep.reward_trend("") is None


def test_fold_executed_merges_and_preserves_static_sections(tmp_path):
    path = os.path.join(tmp_path, "SCENARIOS.json")
    with open(path, "w") as f:
        json.dump(
            {
                "schema": 1,
                "cells": {"train:ppo:CartPole-v1:cpux1p1": {"status": "pass"}},
                "config_cells": {"ppo/gym": {"status": "ok"}},
                "static_findings": [{"rule": "J001"}],
                "executed_cells": {
                    "sweep:ppo:CartPole-v1": {"tier": "learn", "verdict": "learn_pass"}
                },
            },
            f,
        )
    results = {
        "sweep:a2c:CartPole-v1": {"tier": "learn", "verdict": "learn_fail", "wall_s": 9.0},
        "sweep:ppo:CartPole-v1": {"tier": "learn", "verdict": "learn_pass", "wall_s": 30.0},
    }
    chip = sweep.chip_deferrals()[:1]
    summary = sweep.fold_executed(results, chip, path)
    with open(path) as f:
        doc = json.load(f)
    # merged by key: re-run overwrote its old verdict, new cells appended
    assert doc["executed_cells"]["sweep:ppo:CartPole-v1"]["wall_s"] == 30.0
    assert doc["executed_cells"]["sweep:a2c:CartPole-v1"]["verdict"] == "learn_fail"
    assert doc["executed_cells"][chip[0]["key"]]["verdict"] == "deferred_chip"
    assert doc["executed_cells"][chip[0]["key"]]["command"] == chip[0]["command"]
    # the static sections next door are untouched
    assert doc["cells"] == {"train:ppo:CartPole-v1:cpux1p1": {"status": "pass"}}
    assert doc["config_cells"] == {"ppo/gym": {"status": "ok"}}
    assert doc["static_findings"] == [{"rule": "J001"}]
    assert summary["cells"] == 3 == doc["executed_summary"]["cells"]
    assert summary["verdicts"] == {"deferred_chip": 1, "learn_fail": 1, "learn_pass": 1}


def test_stats_rolls_up_executed_cells(tmp_path):
    path = os.path.join(tmp_path, "SCENARIOS.json")
    sweep.fold_executed(
        {
            "sweep:ppo:CartPole-v1+sticky_actions": {
                "tier": "learn",
                "verdict": "learn_pass",
                "sps_env": 33000.0,
                "rew_first_fifth": 20.0,
                "rew_last_fifth": 200.0,
                "episodes": 120,
                "wall_s": 35.0,
            },
            "sweep:sac:Pendulum-v1": {"tier": "smoke", "verdict": "smoke_pass", "wall_s": 15.0},
        },
        [],
        path,
    )
    out = sweep.stats(path)
    assert out["cells"] == 2
    assert out["by_verdict"] == {"learn_pass": 1, "smoke_pass": 1}
    (row,) = [r for r in out["rows"] if r["tier"] == "learn"]
    assert row["sps_env"] == 33000.0 and row["rew_last_fifth"] == 200.0
    # unreadable path reports instead of raising (the --stats UX)
    assert "error" in sweep.stats(os.path.join(tmp_path, "missing.json"))
