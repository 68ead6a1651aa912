"""The standing proof that the benchmark takes an algorithm that is not
Dreamer-V3 as new files and entries only. From ``second_algorithm/`` a
configuration of the repository's own ``exp=ppo``, a cell, an algorithm module,
a vector-observation env, a tiny rule and a per-layer reader are copied into a
temporary root beside the benchmark's files, and on that root: the benchmark's
own ``test_files.py`` and ``test_device_time.py`` pass as they stand, with the
added pair among their cases (so a test that takes every cell for Dreamer-V3's
cannot come back unseen), the added
cell runs on the CPU through ``sheeprl_tpu.cli.run`` to a result line
(``correct`` true, and false with a fault planted in the rollout's rows), a
trace reduced with the module's tables attributes time to its program, and no
file that was there has been touched."""

import contextlib
import glob
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import perfbench
import perfbench.algorithms
import tests.test_perfbench
from perfbench import device_time, loader, run
from perfbench.loader import ROOT
from tests.test_perfbench import tiny

HERE = os.path.dirname(__file__)
SOURCE = os.path.join(HERE, "second_algorithm")
CELL = "ppo_stub_tiny.train"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    REAL_CELLS = [w["name"] for w in json.load(_f)["workloads"]]
#: where each file of ``second_algorithm/`` goes, as a later PR would add it
PLACES = {
    "configs/ppo_stub_tiny.json": "perfbench/configs",
    "workloads/ppo_stub_tiny.train.json": "perfbench/workloads",
    "algorithms/ppo_stub.py": "perfbench/algorithms",
    "layer_metrics/rollout_interaction_share.py": "perfbench/layer_metrics",
    "vector_env.py": "perfbench",
    "tiny_ppo_stub.py": "tests/test_perfbench",
}
#: what README.md says an algorithm module supplies
README_NAMES = ("Capture", "installed", "verify", "check_stated", "model_flops", "programs", "train_program", "scopes", "leaf_spans")
ADDED_MODULES = ("perfbench.algorithms.ppo_stub", "perfbench.vector_env", "tests.test_perfbench.tiny_ppo_stub")


def _files(root):
    return [p for p in glob.glob(os.path.join(root, "**", "*"), recursive=True) if os.path.isfile(p)]


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    """``(root, modification times before)``: a copy of the benchmark with the
    second algorithm added, and its three packages able to find the new modules
    as they would once the files lay in the repository."""
    root = str(tmp_path_factory.mktemp("second_algorithm"))
    ignore = shutil.ignore_patterns("__pycache__", "second_algorithm")
    for path in ("perfbench", "tests/test_perfbench"):
        shutil.copytree(os.path.join(ROOT, path), os.path.join(root, path), ignore=ignore)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copy(os.path.join(ROOT, "tests", "__init__.py"), os.path.join(root, "tests"))
    before = {p: os.stat(p).st_mtime_ns for p in _files(root) if not p.endswith("BENCHMARK.json")}
    for name, place in PLACES.items():
        target = os.path.join(root, place, os.path.basename(name))
        assert not os.path.exists(target), f"{target} is there already: not a new file"
        shutil.copy(os.path.join(SOURCE, name), target)
    with open(os.path.join(SOURCE, "entries.json")) as f:
        entries = json.load(f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for kind in ("configs", "workloads", "per_layer"):
        bench[kind] += entries[kind]
    for metric in bench["per_layer"]:
        if metric["name"] in entries["reports"]:
            metric["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    packages = {perfbench: "perfbench", perfbench.algorithms: "perfbench/algorithms", tests.test_perfbench: "tests/test_perfbench"}
    paths = {package: list(package.__path__) for package in packages}
    for package, place in packages.items():
        package.__path__.append(os.path.join(root, place))
    try:
        yield root, before
    finally:
        for package, path in paths.items():
            package.__path__[:] = path
        for module in ADDED_MODULES:
            sys.modules.pop(module, None)


@pytest.fixture(scope="module")
def tiny_root(added, tmp_path_factory):
    """The reproduction of ISSUE 27: with a configuration of another algorithm
    listed, ``tiny.make_root`` builds its root, each configuration by its own rule."""
    return tiny.make_root(str(tmp_path_factory.mktemp("second_algorithm_tiny")), source=added[0])


@pytest.mark.parametrize("file", ["test_files.py", "test_device_time.py"])
def test_the_benchmarks_own_tests_pass_with_the_algorithm_listed(added, file):
    """As pytest runs them in a checkout whose ``BENCHMARK.json`` lists the stand-in: the root is that
    checkout (its ``perfbench`` and ``tests`` come first on the path, the program from the repository)."""
    root, _ = added
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    env.update(PYTHONPATH=os.pathsep.join([root, ROOT]), JAX_PLATFORMS="cpu", PYTHONDONTWRITEBYTECODE="1")
    done = subprocess.run([sys.executable, "-m", "pytest", os.path.join("tests", "test_perfbench", file), "-v", "-p", "no:cacheprovider"],
                          cwd=root, env=env, capture_output=True, text=True, timeout=600)  # fmt: skip
    assert done.returncode == 0, done.stdout[-6000:] + done.stderr[-2000:]
    assert f"rootdir: {root}" in done.stdout and " skipped" not in done.stdout.splitlines()[-1]
    if file == "test_files.py":
        for case in (f"test_every_name_leads_to_its_files[{CELL}]", "test_configuration_composes_and_states_the_recipe[ppo_stub_tiny.json]",
                     f"test_workload_names_a_configuration_that_exists[{CELL}.json]"):  # fmt: skip
            assert f"{case} PASSED" in done.stdout, f"{case} did not run"


def test_the_module_supplies_every_name_the_readme_asks(added):
    assert set(README_NAMES) <= set(dir(loader.algorithm(loader.Cell(CELL, added[0]))))
    assert set(README_NAMES) <= set(dir(loader.algorithm(loader.Cell(REAL_CELLS[0], added[0]))))


def test_the_added_recipe_composes_without_a_buffer_to_checkpoint_and_names_its_env(added):
    cell = loader.Cell(CELL, added[0])
    overrides = cell.overrides("/tmp/x", "/tmp/x/stamps", 1, False)
    assert not [o for o in overrides if o.startswith("buffer.")] and "env.wrapper._target_=perfbench.vector_env.make" in overrides
    assert "buffer.checkpoint=False" in loader.Cell(REAL_CELLS[0], added[0]).overrides("/tmp/x", "/tmp/x/stamps", 1, False)


def test_the_real_cells_are_shrunk_as_before(tiny_root):
    """What ``test_run.py`` runs is untouched by the configuration listed beside the real ones."""
    from tests.test_perfbench import tiny_dreamer_v3

    for name in REAL_CELLS:
        cell = loader.Cell(name, tiny_root)
        assert cell.config == tiny_dreamer_v3.tiny_config(cell.config["name"])
        assert cell.workload["limits"] == tiny_dreamer_v3.LIMITS and cell.workload["warm_steps"] == 4
    assert loader.Cell(CELL, tiny_root).workload["warm_steps"] == 24


def _line(result):
    return json.loads(json.dumps(result))


def test_the_added_cell_runs_to_a_result_line_on_the_cpu(tiny_root):
    """Through ``sheeprl_tpu.cli.run``: the window opens with ``learning_starts`` 0,
    and the loop leaves with exit 77 through PPO's preemption drain."""
    line = _line(run.run_cell(CELL, 2**31 + 29, 1.0, True, root=tiny_root, require_tpu=False))
    assert list(line)[:3] == ["correct", "attempted", "failed"] and list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 8 and line["failed"] == 0
    assert set(line["compared"]) == {"rollout_rows", "updates_missing", "program_renamed"}
    # the new reader and the generic ones read on any machine; the device's time needs a chip
    assert {"rollout.interaction_share", "compile.in_window", "env.step_share", "loop.env_interaction_ms"} <= set(line["metrics"])
    assert 0.0 < line["metrics"]["rollout.interaction_share"]["value"] <= 100.0
    assert line["metrics"]["compile.in_window"]["value"] == 0.0
    assert "train_step.device_ms" not in line["metrics"] and "train_step.rssm_scan_device_ms" not in line["metrics"]


@contextlib.contextmanager
def _rows_of_the_other_env():
    """The loop stores each env's observation in the other env's row."""
    from sheeprl_tpu.algos.ppo import ppo as program

    real = program.prepare_obs
    program.prepare_obs = lambda obs, **kwargs: {k: v[::-1] for k, v in real(obs, **kwargs).items()}
    try:
        yield
    finally:
        program.prepare_obs = real


def test_a_fault_in_the_rollouts_rows_is_not_correct(tiny_root):
    line = _line(run.run_cell(CELL, 7, 1.0, False, root=tiny_root, require_tpu=False, program_patch=_rows_of_the_other_env))
    assert line["correct"] is False, line["compared"]
    assert [k for k, v in line["compared"].items() if not v["value"] <= v["limit"]] == ["rollout_rows"]
    assert line["compared"]["rollout_rows"]["value"] == 16.0  # every row of 8 steps x 2 envs
    assert set(line["metrics"]) == {"env_steps_per_s", "env_wait_ms_p95", "setup_s"}


MS = 1e6


def test_a_trace_reduced_with_its_tables_attributes_time_to_its_program(added):
    """A hand-made trace of the stand-in's loop (``jit_local_train`` is what
    ``make_train_fn`` compiles): with its tables the update is the train program,
    with Dreamer-V3's nothing of it is, and the generic reader finds it through the cell."""
    stub = loader.algorithm(loader.Cell(CELL, added[0]))
    neutral = {
        "modules": [["jit__lambda_(3)", 0 * MS, 1 * MS], ["jit_local_train(7)", 2 * MS, 4 * MS], ["jit_local_train(7)", 10 * MS, 6 * MS]],
        "ops": [["%fusion.1", 0 * MS, 1 * MS, "jit(<lambda>)/dot_general:"], ["%while.1", 2 * MS, 4 * MS, "jit(local_train)/while:"],
                ["%fusion.2", 2.5 * MS, 3 * MS, "jit(local_train)/while/body/dot_general:"], ["%while.1", 10 * MS, 6 * MS, "jit(local_train)/while:"]],
        "sync": [0.0, 0.0],
    }  # fmt: skip
    facts = {"sync_mono_ns": 1e9, "window_mono_ns": (1e9, 1e9 + 20 * MS), "spans_mono_ns": np.zeros((0, 2)), "env_steps_mono_ns": np.zeros((0, 2))}
    ours = device_time.reduce(neutral, programs=stub.programs, train_program=stub.train_program, scopes=stub.scopes, **facts)
    assert ours["train_executions"] == 2 and ours["named_busy_s"] == pytest.approx(0.010) and ours["busy_s"] == pytest.approx(0.011)
    assert device_time.program_ms(ours, "local_train") == pytest.approx(5.0)
    assert ours["scopes"] == {} and ours["train_unscoped_s"] == pytest.approx(0.010) and device_time.scope_ms(ours, ()) is None
    from perfbench.algorithms import dreamer_v3

    theirs = device_time.reduce(neutral, programs=dreamer_v3.programs, train_program=dreamer_v3.train_program, scopes=dreamer_v3.scopes, **facts)
    assert theirs["train_executions"] == 0 and theirs["named_busy_s"] == 0.0 and theirs["busy_s"] == ours["busy_s"]

    class Run:
        cell = loader.Cell(CELL, added[0])

    traced = Run()
    traced.__dict__["_device_time"] = ours
    readers = loader.layer_readers(Run.cell)
    assert readers["train_step.device_ms"](traced) == pytest.approx(5.0)


def test_no_file_that_was_there_was_edited(added, tiny_root):
    """Runs last: everything above has been done to the root by then."""
    root, before = added
    assert all(os.stat(p).st_mtime_ns == t for p, t in before.items()), "an existing file of the benchmark was edited"
    new = sorted(os.path.relpath(p, root) for p in _files(root) if p not in before and "__pycache__" not in p and os.path.basename(p) != "BENCHMARK.json")
    assert new == sorted(os.path.join(place, os.path.basename(name)) for name, place in PLACES.items())
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert "ppo_stub" not in f.read(), "the stand-in is no cell of the benchmark"
