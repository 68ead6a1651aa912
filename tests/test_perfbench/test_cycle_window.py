"""A cell whose configuration names a cycle (``algo.rollout_steps``: a rollout of
that many vector steps, then an update in which no step is taken): its window
holds whole cycles and its trace one whole cycle, wherever the deadline falls
in a cycle (ISSUE 36). On synthetic stamps, on the recorded traces, and in a
tiny token cell run on the CPU whose deadline lies over 3 s into an update."""

import contextlib
import json
import re
import time

import numpy as np
import pytest

from perfbench import check_line, device_time, run, trace_reduce, window
from tests.test_perfbench import tiny
from tests.test_perfbench.test_device_time import NEW, RECORDED as RECORDED_DEVICE, _readers, _Run, _span_events
from tests.test_perfbench.test_files import RECORDED as RECORDED_TRACE

MS = 1e6


def _measure_before(entry, exit_, open_index, deadline_ns, num_envs):
    """``window.measure`` as it stood before ISSUE 36 (PR 35's tree), word for word."""
    last = int(np.searchsorted(exit_, deadline_ns, side="right")) - 1
    cycles = last - open_index
    if cycles < 1:
        raise RuntimeError(f"perfbench: the window holds {cycles} vector steps")
    seconds = (exit_[last] - exit_[open_index]) / 1e9
    waits_ms = (entry[open_index + 1 : last + 1] - exit_[open_index:last]) / 1e6
    in_step_s = float((exit_[open_index + 1 : last + 1] - entry[open_index + 1 : last + 1]).sum()) / 1e9
    return {
        "open_ns": int(exit_[open_index]),
        "close_ns": int(exit_[last]),
        "first": open_index + 1,
        "last": last,
        "vector_steps": cycles,
        "policy_steps": cycles * num_envs,
        "seconds": seconds,
        "env_steps_per_s": cycles * num_envs / seconds,
        "env_wait_ms_p95": window.quantile(waits_ms, 0.95),
        "env_wait_ms_p50": window.quantile(waits_ms, 0.50),
        "env_step_share": in_step_s / seconds,
        "longest_waits_ms": [(float(waits_ms[i]), int(open_index + 1 + i)) for i in np.argsort(waits_ms)[::-1][:3]],
    }


def _loop(cycle, cycles, update_s, step_s, rng=None, uneven=0.0, t0=1e12):
    """Env 0's ``(entry_ns, exit_ns)`` of a bulk-synchronous loop: rollouts of
    ``cycle`` vector steps (a wait of 0.7 and a step of 0.3 of ``step_s``),
    each followed by an update of ``update_s`` in which no step is taken; with
    ``uneven`` every cycle is stretched by a factor drawn from ``[1, 1 + uneven]``."""
    entry, exit_, t = [], [], t0
    for _ in range(cycles):
        stretch = 1.0 + (rng.uniform(0.0, uneven) if rng is not None else 0.0)
        for _ in range(cycle):
            t += 0.7 * step_s * 1e9 * stretch
            entry.append(t)
            t += 0.3 * step_s * 1e9 * stretch
            exit_.append(t)
        t += update_s * 1e9 * stretch
    return np.round(entry).astype(np.int64), np.round(exit_).astype(np.int64)  # nanoseconds, as env 0 stamps them


# --------------------------------------------------------------------------- #
# the window
# --------------------------------------------------------------------------- #


def test_whole_cycles_read_the_same_rate_wherever_the_deadline_falls():
    """Twenty deadlines spread over one cycle of Mellum's shape (a rollout of
    160 steps in 1.76 s, an update of 7.7 s): with the cycle the rate is the
    cycle's to 1e-9; without it the function reads as it did before, which
    is up to 12% under the rate in a rollout and over it in none."""
    cycle, envs, update_s, step_s = 160, 64, 7.7, 0.011
    entry, exit_ = _loop(cycle, 8, update_s, step_s)
    open_index = 2 * cycle - 1
    period = (exit_[3 * cycle - 1] - exit_[2 * cycle - 1]) / 1e9
    true_rate = cycle * envs / period
    before = []
    for phase in np.linspace(0.0, 1.0, 20, endpoint=False):
        deadline = exit_[open_index] + (3 + phase) * period * 1e9
        whole = window.measure(entry, exit_, open_index, deadline, envs, cycle)
        assert whole["env_steps_per_s"] == pytest.approx(true_rate, rel=1e-9)
        assert whole["vector_steps"] == 3 * cycle and (whole["last"] + 1) % cycle == 0
        as_before = window.measure(entry, exit_, open_index, deadline, envs)
        assert as_before == _measure_before(entry, exit_, open_index, deadline, envs)
        before.append(as_before["env_steps_per_s"])
    # the trap the cycle closes: in a rollout the reading adds an update's time and part of a rollout's steps
    assert min(before) < 0.90 * true_rate and max(before) == pytest.approx(true_rate, rel=1e-9)


def test_a_window_with_a_cycle_opens_on_a_rollout_end_and_holds_a_whole_cycle():
    entry, exit_ = _loop(16, 4, 1.0, 0.01)
    assert [window.rollout_end(i, 16) for i in (0, 15, 16, 31, 32)] == [15, 15, 31, 31, 47]
    with pytest.raises(RuntimeError, match="ends no rollout"):
        window.measure(entry, exit_, 16, exit_[-1], 4, 16)
    with pytest.raises(RuntimeError, match="no whole cycle"):
        window.measure(entry, exit_, 15, exit_[30], 4, 16)
    assert window.measure(entry, exit_, 15, exit_[31], 4, 16)["last"] == 31


# --------------------------------------------------------------------------- #
# the watcher's rule for where the trace starts
# --------------------------------------------------------------------------- #


def _traced_from(exit_, open_index, cycle, deadline):
    """The watcher's choice, step by step as the stamps come in: each rollout
    end after the opening is judged as the step ``trace_lead`` before it returns."""
    def known(i):
        assert i <= end - run.trace_lead(cycle), "the rule read a step that had not returned"
        return exit_[i]

    end = open_index + cycle
    while end + cycle < len(exit_):
        if run.starts_trace(known, open_index, cycle, end, deadline):
            return end
        end += cycle
    raise AssertionError("no rollout end was chosen")


@pytest.mark.parametrize("uneven", [0.0, 0.05])
@pytest.mark.parametrize("shape", ["glm", "lfm2", "mellum", "mellum_short_update"])
def test_the_trace_starts_where_one_whole_cycle_returns_by_the_deadline(shape, uneven):
    """For every phase of the deadline, with cycles alike or up to 5% apart:
    the traced cycle's last step returns by the deadline, and at most one
    whole cycle lies between it and the deadline."""
    cycle, update_s, step_s = {"glm": (256, 3.63, 0.0077), "lfm2": (224, 4.13, 0.0072), "mellum": (160, 7.7, 0.011),
                               "mellum_short_update": (160, 5.15, 0.011)}[shape]  # fmt: skip
    rng = np.random.default_rng(36)
    for seconds in np.linspace(20.0, 40.0, 41):
        entry, exit_ = _loop(cycle, 12, update_s, step_s, rng, uneven)
        open_index = 2 * cycle - 1
        deadline = exit_[open_index] + seconds * 1e9
        end = _traced_from(exit_, open_index, cycle, deadline)
        assert exit_[end + cycle] <= deadline < exit_[end + 3 * cycle], (shape, seconds)
        # and that cycle is one the window holds whole
        win = window.measure(entry, exit_, open_index, deadline, 64, cycle)
        assert win["open_ns"] <= exit_[end] and exit_[end + cycle] <= win["close_ns"]


# --------------------------------------------------------------------------- #
# the reducers' stretch, on the recorded traces
# --------------------------------------------------------------------------- #


class _Watcher:
    def __init__(self, sync_mono_ns, inside_ns, traced_from=None, cycle=None):
        self.sync = {"before_ns": 2 * sync_mono_ns - inside_ns, "inside_ns": inside_ns}
        self.traced_from, self.cycle = traced_from, cycle


class _Cell:
    chips = 1
    config = {"reference": "dreamer_v3"}


class _TraceRun:
    cell = _Cell()

    def __init__(self, watcher, entry_ns, exit_ns, close_ns):
        self.watcher, self.entry_ns, self.exit_ns = watcher, np.asarray(entry_ns), np.asarray(exit_ns)
        self.window = {"close_ns": close_ns}
        self.stretch_ns = run.trace_stretch(watcher, self.exit_ns, self.window)


def test_the_trace_reduction_cuts_a_cycle_and_a_dreamer_v3_cell_as_before():
    with open(RECORDED_TRACE.replace(".json.gz", "_expect.json")) as f:
        expect = json.load(f)
    planes = trace_reduce.load(RECORDED_TRACE)
    lo, hi = expect["window_mono_ns"]
    steps = np.asarray(expect["env_steps_mono_ns"], np.float64).reshape(-1, 2)
    # no cycle: from the profiler's start to the window's last vector step, as the file was recorded
    as_before = trace_reduce.reduce_run(planes, _TraceRun(_Watcher(expect["sync_mono_ns"], lo), steps[:, 0], steps[:, 1], hi))
    assert as_before["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-9) and as_before["window_s"] == pytest.approx(expect["window_s"])
    assert [n for n, _ in as_before["breakdown"]["device_ops"]] == expect["top_ops"]
    assert [n for n, _ in as_before["breakdown"]["idle_gaps"]] == [n for n, _ in expect["idle_gaps"]]
    assert [s for _, s in as_before["breakdown"]["idle_gaps"]] == pytest.approx([s for _, s in expect["idle_gaps"]])
    # a cycle of one vector step from the rollout end at 20 ms to the next at 65 ms: the stretch is that cycle
    exits = [lo + 20 * MS, lo + 65 * MS, lo + 110 * MS]
    cut = _TraceRun(_Watcher(expect["sync_mono_ns"], lo - 5 * MS, traced_from=0, cycle=1), [e - MS for e in exits], exits, hi)
    assert cut.stretch_ns == [exits[0], exits[1]]
    reduced = trace_reduce.reduce_run(planes, cut)
    assert reduced["window_s"] == pytest.approx(0.045, rel=1e-9) and 0.0 < reduced["busy_s"] <= reduced["window_s"]


def test_the_device_time_reduction_cuts_a_cycle_and_a_dreamer_v3_cell_as_before():
    with open(RECORDED_DEVICE.replace(".json.gz", "_host.json")) as f:
        host = json.load(f)
    with open(RECORDED_DEVICE.replace(".json.gz", "_expect.json")) as f:
        expect = json.load(f)
    neutral = device_time.load(RECORDED_DEVICE)
    lo, hi = host["window_mono_ns"]
    steps = np.asarray(host["env_steps_mono_ns"], np.float64).reshape(-1, 2)
    events = _span_events({k: [tuple(p) for p in v] for k, v in host["spans"].items()})

    def of(watcher, entry_ns, exit_ns):
        # the window opened long before its traced stretch, so the leaf spans that began before the stretch are the window's
        r = _Run(None, events, {"open_ns": int(lo - 1e9), "close_ns": int(hi), "vector_steps": expect["vector_steps"]})
        r.watcher, r.entry_ns, r.exit_ns = watcher, np.asarray(entry_ns), np.asarray(exit_ns)
        r.stretch_ns = run.trace_stretch(watcher, r.exit_ns, r.window)
        r.__dict__["_neutral"] = neutral
        return r

    # no cycle: the trace's readers read what the file was recorded with (the span readers read the window, not the stretch)
    dreamer = of(_Watcher(host["sync_mono_ns"], lo), steps[:, 0], steps[:, 1])
    values = {name: reader(dreamer) for name, reader in _readers().items() if name in NEW}
    for name, value in {**expect["metrics"], **expect["by_reduce"]}.items():
        if name.endswith(("device_ms", "device_mfu", "_share")):
            assert values[name] == pytest.approx(value, rel=1e-6), name
    # a cycle: the stretch runs from one rollout end to the next, and holds the train steps that lie whole in it
    exits = [lo + 10 * MS, lo + 250 * MS, lo + 300 * MS]
    cycle = device_time.of_run(of(_Watcher(host["sync_mono_ns"], lo - MS, traced_from=0, cycle=1), [e - MS for e in exits], exits))
    assert cycle["window_s"] == pytest.approx(0.240, rel=1e-9)
    assert 0 < cycle["train_executions"] <= expect["executions"]["dv3_train_step"]


# --------------------------------------------------------------------------- #
# a tiny token cell whose deadline lies deep in an update
# --------------------------------------------------------------------------- #

CELL = "mellum2_12b_ep8.train"
#: the update that the tiny cell is slowed to: an update of 4 s (its own is some tens of milliseconds)
UPDATE_S = 4.0


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("perfbench_cycle_root")))


def _slow_updates():
    """Each update from the fourth on (the last of the set-up, which the cycle
    that ends as the window opens holds) waits ``UPDATE_S`` before it trains
    (in ``token_sequences``, which every update calls once): a cycle of about 4 s."""

    @contextlib.contextmanager
    def patch():
        from sheeprl_tpu.algos.ppo_recurrent import token_policy as program

        real, calls = program.token_sequences, []

        def slow(*args, **kwargs):
            calls.append(1)
            if len(calls) >= 4:
                time.sleep(UPDATE_S)
            return real(*args, **kwargs)

        program.token_sequences = slow
        try:
            yield
        finally:
            program.token_sequences = real

    return patch


def test_a_deadline_deep_in_an_update_traces_a_whole_cycle(root, capsys):
    """The window opens as the fifth rollout ends (step 59); two cycles of
    about 4.03 s and 3.5 s more put the deadline 3.5 s into the third update,
    where the old harness's 3 s of trace before the deadline held no step
    (its stretch ended at the last step, 0.5 s before it began)."""
    seconds = 2 * (UPDATE_S + 0.03) + 3.5
    line = json.loads(json.dumps(run.run_cell(CELL, 2**31 + 36, seconds, True, root=root, require_tpu=False, program_patch=_slow_updates())))
    out = capsys.readouterr().out
    phase = float(re.search(r"the deadline fell ([0-9.]+)s after the last whole cycle's end", out).group(1))
    traced = int(re.search(r"traced: the cycle from vector step (\d+)", out).group(1))
    assert 3.0 < phase < UPDATE_S, out[-3000:]
    # the profiler stops behind the traced cycle, while the window runs on to its deadline, not after the program has left
    stopped = float(re.search(r"the profiler was stopped (-?[0-9.]+)s after the window's last step", out).group(1))
    assert 0.0 <= stopped < 1.0, out[-3000:]
    assert line["correct"] is True and line["attempted"] == 2 * 12
    # the stretch is the window's last whole cycle, as env 0's stamps read it
    from perfbench import env as bench_env

    stamps = bench_env.open_stamps(f"{root}/logs/perfbench/{CELL}/seed{2**31 + 36}_trace1/stamps.i64")
    _, exit_ = window.vector_steps(stamps, 1)
    assert traced == 59 + 12 and (traced + 1) % 12 == 0
    assert line["device"]["window_s"] == pytest.approx((exit_[traced + 12] - exit_[traced]) / 1e9, rel=1e-9)
    assert line["device"]["window_s"] > UPDATE_S
    # the line check finds nothing wrong with it but what needs a chip: the device's busy time and peak, and the device metrics
    assert {"compile.in_window", "loop.train_block_ms", "moe.held_pair_share", "update.padding_share"} <= set(line["metrics"])
    faults = check_line.faults(json.dumps(line), CELL, True, root)
    assert "device.busy_s is not a number above 0" in faults and "device.window_s is not a number above 0" not in faults
    assert all(f.startswith(("metrics.", "device.busy_s", "device.memory_peak_bytes")) for f in faults), faults
