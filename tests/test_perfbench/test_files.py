"""The benchmark's files and its arithmetic, without a run: every name in
``BENCHMARK.json`` leads to its file, every configuration composes and says
what the program's recipe says, the FLOP function against a hand count, the
window on synthetic timestamps, the trace reduction, and a configuration, a
cell and a per-layer metric added as new files only (an algorithm is added by
``test_second_algorithm.py``, which then runs this whole file in a checkout
whose ``BENCHMARK.json`` lists it: a test here that is about Dreamer-V3 names
its cells, and one over every cell asks the cell's own algorithm)."""

import glob
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from perfbench import flops, loader, run, trace_reduce, window
from perfbench.loader import ROOT
from tests.test_perfbench import tiny

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
CONFIG_FILES = sorted(glob.glob(os.path.join(ROOT, "perfbench", "configs", "*.json")))
WORKLOAD_FILES = sorted(glob.glob(os.path.join(ROOT, "perfbench", "workloads", "*.json")))
#: the command lines of the cells that PR 27 found, as the harness of PR 26 gave them
with open(os.path.join(os.path.dirname(__file__), "recorded_command_lines.json")) as _f:
    RECORDED_COMMANDS = json.load(_f)


def _composed(overrides):
    from sheeprl_tpu.config import compose

    composed = compose("config", overrides).to_dict()
    composed.pop("run_name", None)  # carries the second it was composed in
    return composed


@pytest.mark.parametrize("name", CELLS)
def test_every_name_leads_to_its_files(name):
    cell = loader.Cell(name)
    assert cell.workload["config"] == cell.config["name"] == cell.entry["config"]
    assert cell.chips == cell.workload["chips"]
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "env_steps_per_s"}
    assert set(loader.layer_readers(cell)) == {m["name"] for m in cell.per_layer}
    moved = {m["name"] for m in cell.end_to_end}
    assert all(m["moves"] in moved for m in cell.per_layer)
    # the limits carry names that the comparison of the cell's algorithm gives
    compared = set(cell.workload["limits"])
    assert compared and compared <= set(tiny.rule(cell.config["reference"]).LIMITS)


@pytest.mark.parametrize("path", CONFIG_FILES, ids=os.path.basename)
def test_configuration_composes_and_states_the_recipe(path):
    """The file is the one its entry names, composes under the harness's command line, says what the
    program's recipe says (the algorithm's ``check_stated``) and states the algorithm's own FLOP count."""
    with open(path) as f:
        config = json.load(f)
    entry = {c["name"]: c for c in BENCH["configs"]}[config["name"]]
    assert entry["file"] == os.path.relpath(path, ROOT) and entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"] and len(config["source"]) <= 200
    cell = loader.Cell([w["name"] for w in BENCH["workloads"] if w["config"] == config["name"]][0])
    algorithm = loader.algorithm(cell)
    composed = _composed(cell.overrides("/tmp/x", "/tmp/x/stamps", 0, False))
    algorithm.check_stated(config, composed)
    assert config["model_flops_per_grad_step"] == algorithm.model_flops(config)
    if "episode_frames" in config["env"]:  # a spec of the benchmark's own env: an episode ends on a whole policy step
        assert config["env"]["episode_frames"]["low"] % config["algo"]["action_repeat"] == 0
    # the way out of the window gathers a checkpoint: a recipe that keeps a replay buffer must not put it in
    assert composed.get("buffer", {}).get("checkpoint", False) is False, "add buffer.checkpoint=False to the file's overrides"
    for key in config["reduced"]:
        assert config[key] != config["published"][key]


@pytest.mark.parametrize("path", WORKLOAD_FILES, ids=os.path.basename)
def test_workload_names_a_configuration_that_exists(path):
    with open(path) as f:
        workload = json.load(f)
    assert os.path.exists(os.path.join(ROOT, "perfbench", "configs", f"{workload['config']}.json"))
    assert workload["name"] == os.path.basename(path)[: -len(".json")]


@pytest.mark.parametrize("name", sorted(RECORDED_COMMANDS["cells"]))
def test_the_command_line_of_a_cell_is_the_parents(name):
    """PR 27 moved ``buffer.checkpoint=False`` from the harness's list into the two configurations' own and
    named the env factory from the file: the cells that were there are started with the same words as before
    (the one that moved stands earlier in the line) and so with the same composed configuration."""
    theirs, at = RECORDED_COMMANDS["cells"][name], RECORDED_COMMANDS["arguments"]
    ours = loader.Cell(name).overrides(at["run_dir"], at["stamps"], at["seed"], at["trace"])
    assert sorted(ours) == sorted(theirs)
    assert _composed(ours) == _composed(theirs)
    traced = loader.Cell(name).overrides(at["run_dir"], at["stamps"], at["seed"], True)
    assert [a for a, b in zip(ours, traced) if a != b] == ["metric.telemetry.enabled=False"] and len(ours) == len(traced)


@pytest.mark.parametrize("exp", ["ppo", "ppo_recurrent", "a2c", "sac", "dreamer_v3"])
def test_a_recipe_composes_under_the_harness_overrides(exp, tmp_path, monkeypatch):
    """What the harness adds is what every recipe has: the on-policy recipes keep no buffer to checkpoint."""
    cell = loader.Cell(CELLS[0])
    monkeypatch.setitem(cell.config, "overrides", [f"exp={exp}"])
    composed = _composed(cell.overrides(str(tmp_path), str(tmp_path / "stamps"), 1, False))
    assert ("checkpoint" in composed["buffer"]) == (exp in ("sac", "dreamer_v3"))
    assert composed["env"]["wrapper"]["_target_"] == "perfbench.env.make" and composed["algo"]["run_test"] is False


def test_the_env_factory_is_named_in_one_place(monkeypatch):
    cell = loader.Cell(CELLS[0])
    monkeypatch.setitem(cell.config, "env", {**cell.config["env"], "make": "somewhere.else.make"})
    overrides = cell.overrides("/tmp/x", "/tmp/x/stamps", 1, False)
    assert [o for o in overrides if o.startswith("env.wrapper._target_=")] == ["env.wrapper._target_=somewhere.else.make"]
    monkeypatch.setitem(cell.config, "env_overrides", {"wrapper._target_": "a.third.make"})
    with pytest.raises(SystemExit, match="env.make"):
        cell.overrides("/tmp/x", "/tmp/x/stamps", 1, False)


def test_stated_check_refuses_a_departure():
    cell = loader.Cell(CELLS[0])
    overrides = [*cell.overrides("/tmp/x", "/tmp/x/stamps", 0, False), "algo.dense_units=64"]
    with pytest.raises(SystemExit, match="algo.dense_units"):
        loader.algorithm(cell).check_stated(cell.config, _composed(overrides))


def test_peaks_table_has_the_v5e_row_and_no_default():
    with open(os.path.join(ROOT, "perfbench", "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12 and "source" in peaks["TPU v5 lite"]
    assert peaks.get("cpu") is None


# --------------------------------------------------------------------------- #
# FLOPs
# --------------------------------------------------------------------------- #


def test_flops_of_one_dense_and_one_conv_by_hand():
    # [1024, 1536] @ [1536, 512]: 1024 * 1536 * 512 multiply-adds, 2 FLOPs each
    assert flops.dense(1024, 1536, 512) == 2 * 1024 * 1536 * 512 == 1_610_612_736
    # 4x4 stride-2 conv 3 -> 32 channels to a 32x32 map: 32*32 outputs x 32 channels x 48 taps
    assert flops.conv(1, 32, 4, 3, 32) == 2 * 32 * 32 * 32 * (4 * 4 * 3) == 3_145_728
    assert flops.mlp(2, 10, 8, 2, out=3) == 2 * 2 * (10 * 8 + 8 * 8 + 8 * 3)


def test_flops_split_adds_up_and_grows_with_width():
    small, large = (loader.Cell(name).config for name in ("dv3_S_walker.train", "dv3_XL_crafter.train"))
    for cfg in (small, large):
        split = flops.per_gradient_step(cfg)
        assert split["total"] == split["world_model"] + split["imagination"] + split["critic"]
    assert flops.per_gradient_step(large)["total"] > 5 * flops.per_gradient_step(small)["total"]
    # S encoder by hand: four 4x4 stride-2 convs, 3->32->64->128->256 channels
    by_hand = 2 * (32 * 32 * 48 * 32 + 16 * 16 * 512 * 64 + 8 * 8 * 1024 * 128 + 4 * 4 * 2048 * 256)
    assert flops.parts(small)["encoder"] == by_hand


# --------------------------------------------------------------------------- #
# the window
# --------------------------------------------------------------------------- #


def _stamps(waits_ms, step_us=50, action_repeat=1):
    """Timestamps of a loop whose i-th wait is ``waits_ms[i]``."""
    out, t = [0], 1_000_000
    for wait in waits_ms:
        t += int(wait * 1e6)
        for _ in range(action_repeat):
            out += [t, t + step_us * 1000]
            t += step_us * 1000
    out[0] = len(waits_ms) * action_repeat
    return np.asarray(out, np.int64)


def test_window_counts_cycles_and_time():
    entry, exit_ = window.vector_steps(_stamps([10.0] * 101, action_repeat=2), 2)
    assert len(entry) == 101
    win = window.measure(entry, exit_, 0, int(exit_[-1]), num_envs=4)
    assert win["vector_steps"] == 100 and win["policy_steps"] == 400
    assert win["seconds"] == pytest.approx(100 * (0.010 + 2 * 50e-6))
    assert win["env_steps_per_s"] == pytest.approx(400 / win["seconds"])
    assert win["env_wait_ms_p95"] == pytest.approx(10.0) and win["env_wait_ms_p50"] == pytest.approx(10.0)
    assert win["env_step_share"] == pytest.approx(100e-6 / (0.010 + 100e-6))


def test_a_stall_inside_the_window_lowers_the_rate_and_raises_the_tail():
    steady = [10.0] * 101
    stalled = list(steady)
    for i in range(40, 50):
        stalled[i] = 60.0
    results = []
    for waits in (steady, stalled):
        entry, exit_ = window.vector_steps(_stamps(waits), 1)
        results.append(window.measure(entry, exit_, 0, int(exit_[0]) + int(0.9e9), num_envs=1))
    assert results[1]["env_steps_per_s"] < 0.7 * results[0]["env_steps_per_s"]
    assert results[1]["env_wait_ms_p95"] == pytest.approx(60.0) and results[0]["env_wait_ms_p95"] == pytest.approx(10.0)
    # the deadline cuts the window at the last step that returned before it
    assert results[1]["close_ns"] <= int(exit_[0]) + int(0.9e9) < results[1]["close_ns"] + int(61e6)


def test_a_window_without_steps_is_an_error():
    entry, exit_ = window.vector_steps(_stamps([10.0] * 5), 1)
    with pytest.raises(RuntimeError):
        window.measure(entry, exit_, 4, int(exit_[4]), num_envs=1)


# --------------------------------------------------------------------------- #
# the trace
# --------------------------------------------------------------------------- #


def _synthetic_planes():
    ms = 1e6
    ops = [("fusion.1", 10 * ms, 20 * ms), ("while.2", 40 * ms, 30 * ms), ("fusion.3", 45 * ms, 10 * ms), ("copy.4", 90 * ms, 5 * ms)]
    host = [("perfbench/sync", 0.0, 0.0), ("Time/train_time", 5 * ms, 70 * ms), ("Time/env_interaction_time", 76 * ms, 20 * ms)]
    return {"/device:TPU:0": {"XLA Ops": ops, "Steps": [("0", 0.0, 100 * ms)]}, "/host:CPU": {"python": host}}


def test_trace_reduction_on_a_synthetic_trace():
    ms = 1e6
    env_steps = np.asarray([[1000 * ms + 80 * ms, 1000 * ms + 84 * ms]])
    reduced = trace_reduce.reduce(
        _synthetic_planes(), sync_mono_ns=1000 * ms, window_mono_ns=(1000 * ms, 1100 * ms), env_steps_mono_ns=env_steps
    )
    assert reduced["window_s"] == pytest.approx(0.100)
    assert reduced["busy_s"] == pytest.approx(0.055)  # 20 + 30 (the while covers its body) + 5 ms
    ops = dict(reduced["breakdown"]["device_ops"])
    assert ops["while.2"] == pytest.approx(0.020) and ops["fusion.3"] == pytest.approx(0.010)
    idle = dict(reduced["breakdown"]["idle_gaps"])
    # gaps: 0-10, 30-40, 70-90, 95-100 ms; train span 5-75, interaction 76-96, env step 80-84
    assert idle["Time/train_time"] == pytest.approx(0.020)
    assert idle["env.step"] == pytest.approx(0.004)
    assert idle["Time/env_interaction_time (less env.step)"] == pytest.approx(0.011)
    assert sum(idle.values()) == pytest.approx(0.045)
    assert reduced["longest_gap_ms"] == pytest.approx(20.0)


def test_trace_reduction_without_a_device_plane_reports_no_busy_time():
    planes = _synthetic_planes()
    del planes["/device:TPU:0"]
    reduced = trace_reduce.reduce(planes, sync_mono_ns=0.0, window_mono_ns=(0.0, 1e8), env_steps_mono_ns=np.zeros((0, 2)))
    assert "busy_s" not in reduced and reduced["window_s"] == pytest.approx(0.1)


def test_union_and_self_times():
    cover = trace_reduce.union(np.asarray([[5.0, 7.0], [0.0, 2.0], [1.0, 3.0], [6.0, 6.5]]))
    assert cover.tolist() == [[0.0, 3.0], [5.0, 7.0]]
    assert trace_reduce.overlap(cover, np.asarray([[2.0, 6.0]])) == pytest.approx(2.0)
    own = trace_reduce.self_times([("outer", 0.0, 10e9), ("inner", 1e9, 2e9), ("inner", 4e9, 2e9)])
    assert own == {"outer": pytest.approx(6.0), "inner": pytest.approx(4.0)}


RECORDED = os.path.join(os.path.dirname(__file__), "recorded_trace.json.gz")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace in this checkout")
def test_trace_reduction_on_the_recorded_trace():
    with open(os.path.join(os.path.dirname(__file__), "recorded_trace_expect.json")) as f:
        expect = json.load(f)
    reduced = trace_reduce.reduce(
        trace_reduce.load(RECORDED),
        sync_mono_ns=expect["sync_mono_ns"],
        window_mono_ns=tuple(expect["window_mono_ns"]),
        env_steps_mono_ns=np.asarray(expect["env_steps_mono_ns"], np.float64),
    )
    assert reduced["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-9)
    assert reduced["window_s"] == pytest.approx(expect["window_s"], rel=1e-9)
    assert 0.0 < reduced["busy_s"] <= reduced["window_s"]
    assert [n for n, _ in reduced["breakdown"]["device_ops"]] == expect["top_ops"]
    idle = sum(s for _, s in reduced["breakdown"]["idle_gaps"])
    assert idle == pytest.approx(reduced["window_s"] - reduced["busy_s"], rel=1e-6)


# --------------------------------------------------------------------------- #
# data-driven: new files and entries only
# --------------------------------------------------------------------------- #


def test_a_configuration_a_cell_and_a_metric_are_added_as_new_files(tmp_path):
    root = str(tmp_path / "copy")
    shutil.copytree(os.path.join(ROOT, "perfbench"), os.path.join(root, "perfbench"), ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    files = [p for p in glob.glob(os.path.join(root, "perfbench", "**", "*"), recursive=True) if os.path.isfile(p)]
    before = {p: os.path.getmtime(p) for p in files}
    with open(os.path.join(root, "perfbench", "configs", "dv3_S_walker.json")) as f:
        config = json.load(f)
    config["name"] = "dv3_S_walker_8env"
    with open(os.path.join(root, "perfbench", "configs", "dv3_S_walker_8env.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "perfbench", "workloads", "dv3_S_walker.train.json")) as f:
        workload = json.load(f)
    workload.update({"name": "dv3_S_walker_8env.collect", "config": "dv3_S_walker_8env", "overrides": ["env.num_envs=8"]})
    with open(os.path.join(root, "perfbench", "workloads", "dv3_S_walker_8env.collect.json"), "w") as f:
        json.dump(workload, f)
    with open(os.path.join(root, "perfbench", "layer_metrics", "env_wait_ms_p50.py"), "w") as f:
        f.write('def read(run):\n    return run.window["env_wait_ms_p50"]\n')
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "dv3_S_walker_8env", "source": config["source"], "file": "perfbench/configs/dv3_S_walker_8env.json",
                             "reduced": [], "why": "more envs"})  # fmt: skip
    bench["workloads"].append({"name": "dv3_S_walker_8env.collect", "config": "dv3_S_walker_8env", "traffic": "collect", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "env_wait_ms_p50", "unit": "ms", "better": "lower", "source": "host_clock", "layer": "interaction loop",
                               "moves": "env_wait_ms_p95", "workloads": ["dv3_S_walker_8env.collect"]})  # fmt: skip
    for metric in bench["per_layer"][:-1]:
        metric["workloads"].append("dv3_S_walker_8env.collect")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    cell = loader.Cell("dv3_S_walker_8env.collect", root)
    assert "env.num_envs=8" in cell.overrides("/tmp/x", "/tmp/x/s", 1, False)
    readers = loader.layer_readers(cell)
    assert len(readers) == len(BENCH["per_layer"]) + 1

    class Run:
        window = {"env_wait_ms_p50": 4.5}

    assert readers["env_wait_ms_p50"](Run()) == 4.5
    assert "env_wait_ms_p50" not in loader.layer_readers(loader.Cell(CELLS[0], root))
    assert {"Capture", "installed", "verify"} <= set(dir(loader.algorithm(cell)))
    assert all(os.path.getmtime(p) == t for p, t in before.items()), "an existing file of the benchmark was edited"


# --------------------------------------------------------------------------- #
# no chip, no number
# --------------------------------------------------------------------------- #


def test_a_measurement_without_a_tpu_fails_and_prints_no_result(capsys):
    with pytest.raises(SystemExit) as err:
        run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert err.value.code not in (0, None) and "TPU" in str(err.value.code)
    assert "correct" not in capsys.readouterr().out


def test_the_benchmark_alone_prints_no_result(tmp_path):
    """In a directory that holds only ``BENCHMARK.json`` and the files under
    ``paths`` there is no program to measure."""
    for path in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path, ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )  # fmt: skip
    assert done.returncode != 0
    assert '"correct"' not in done.stdout
