"""The readers of the spans that tile a loop iteration (``perfbench/span_tree.py``,
``device.idle_unspanned_share``, ``loop.bookkeeping_host_ms``) on a synthetic
run small enough to check by hand, and where they read nothing."""

import json
import os

import numpy as np
import pytest

from perfbench import loader, span_tree
from perfbench.loader import ROOT

MS = 1e6
BASE = 1e9  # the monotonic time read inside the sync annotation; the trace's clock is this less BASE
DV3_CELLS = ["dv3_S_walker.train", "dv3_XL_crafter.train"]
TOKEN_CELLS = ["glm47_flash_ep8.train", "lfm2_24b_a2b_ep8.train", "mellum2_12b_ep8.train"]
NEW = {
    "device.idle_unspanned_share": {"unit": "%", "better": "lower", "source": "device_trace", "layer": "device"},
    "loop.bookkeeping_host_ms": {"unit": "ms", "better": "lower", "source": "program_span", "layer": "interaction loop"},
}
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

# the device busy 0-10, 30-40 and 60-70 ms of a stretch of 100 ms: 70 ms idle
NEUTRAL = {"modules": [], "ops": [["%fusion.1", 0 * MS, 10 * MS, ""], ["%fusion.2", 30 * MS, 10 * MS, ""], ["%fusion.3", 60 * MS, 10 * MS, ""]],
           "sync": [0.0, 0.0]}  # fmt: skip
#: (name, parent, start ms, end ms): a turn as the Dreamer-V3 loop nests it, and a span of another thread
SPANS = [
    ("loop/head", None, 8, 12),
    ("Time/env_interaction_time", None, 12, 35),
    ("player/get_actions", "Time/env_interaction_time", 12, 14),
    ("env/step", "Time/env_interaction_time", 14, 20),
    ("ring/add", "Time/env_interaction_time", 22, 28),
    ("loop/store_step", None, 35, 45),
    ("Time/train_time", None, 45, 105),  # still open as the stretch ends at 100: the race of a profiler's stop
    ("train/dispatch", "Time/train_time", 45, 50),
    ("update/bootstrap", "train/dispatch", 46, 48),
    ("ckpt/write", None, 55, 58),  # another thread's, under no parent of its own
    ("train/block", "Time/train_time", 70, 102),  # a leaf still open as the stretch ends
]
ENV_STEPS = [[19, 21]]  # env 0 in step(): its return lies 1 ms past the loop's env/step span


def _events(spans=SPANS, with_mono=True):
    events = []
    for name, parent, a, b in spans:
        e = {"event": "span", "name": name, "parent": parent, "t_start": 1.7e9 + a / 1e3, "dur": (b - a) / 1e3}
        if with_mono:
            e["t_mono_ns"] = int(BASE + a * MS)
        events.append(e)
    return events


class _Cell:
    def __init__(self, algo):
        self.config = {"algo": algo}


class _Run:
    """What the readers ask of a finished traced run."""

    run_dir = "/nonexistent"

    def __init__(self, events, neutral=NEUTRAL, window=None, algo=None):
        self.telemetry_events = events
        self.watcher = type("W", (), {"sync": {"before_ns": int(BASE), "inside_ns": int(BASE)}})()
        self.stretch_ns = [BASE, BASE + 100 * MS]
        self.entry_ns, self.exit_ns = (np.asarray([BASE + p[i] * MS for p in ENV_STEPS]) for i in (0, 1))
        self.window = window or {"open_ns": int(BASE), "close_ns": int(BASE + 100 * MS), "vector_steps": 1}
        self.cell = _Cell(algo or {})
        self.__dict__["_neutral"] = neutral


def _reader(name):
    return loader.layer_readers(loader.Cell(DV3_CELLS[0]))[name]


def test_the_idle_time_by_innermost_span_by_hand():
    table = span_tree.idle_by_innermost(_Run(_events()))
    expect = {"loop/head": 2, "player/get_actions": 2, "env/step": 6, span_tree.ENV_STEP: 1, "Time/env_interaction_time": 1 + 2, "ring/add": 6,
              "loop/store_step": 5, "train/dispatch": 1 + 2, "update/bootstrap": 2, "Time/train_time": 10 - 3, "ckpt/write": 3,
              "train/block": 30}  # fmt: skip
    assert table.keys() == expect.keys()
    for name, ms in expect.items():
        assert table[name] == pytest.approx(ms / 1e3, abs=1e-12), name
    assert sum(table.values()) == pytest.approx(0.070)
    assert span_tree.unspanned_seconds(table) == pytest.approx(0.010)


def test_a_span_open_past_the_stretchs_end_still_counts():
    """The race of a profiler stopped while a span is open: the file holds the
    span whole, and it counts up to the stretch's end."""
    read = _reader("device.idle_unspanned_share")
    assert read(_Run(_events())) == pytest.approx(100.0 * 10 / 70)
    # with the block left out its 30 ms fall to the window span that holds it: what no span names
    without_block = [s for s in SPANS if s[0] != "train/block"]
    assert read(_Run(_events(without_block))) == pytest.approx(100.0 * 40 / 70)
    # and where no span at all covers an instant it is unspanned too
    table = span_tree.idle_by_innermost(_Run(_events([s for s in SPANS if s[0] != "loop/head"])))
    assert table[span_tree.UNSPANNED] == pytest.approx(0.002)
    assert span_tree.unspanned_seconds(table) == pytest.approx(0.012)


def test_self_time_is_the_duration_less_the_children_that_name_the_span():
    spans = span_tree.of_events(_events())
    own = dict(zip([s.name for s in spans], span_tree.self_seconds(spans)))
    assert own["train/dispatch"] == pytest.approx(0.003) and own["update/bootstrap"] == pytest.approx(0.002)
    assert own["Time/train_time"] == pytest.approx(0.060 - 0.005 - 0.032)  # ckpt/write names no parent: not its child
    assert own["Time/env_interaction_time"] == pytest.approx(0.023 - 0.002 - 0.006 - 0.006)
    assert own["loop/head"] == pytest.approx(0.004)


def test_the_bookkeeping_reader_per_turn_and_per_update():
    read = _reader("loop.bookkeeping_host_ms")
    window = {"open_ns": int(BASE), "close_ns": int(BASE + 100 * MS), "vector_steps": 4}
    spans = [("loop/head", None, 1, 3), ("loop/tail", None, 20, 21), ("loop/head", None, 21, 22), ("loop/tail", None, 60, 64),
             ("loop/head", None, -10, -5), ("loop/tail", None, 99, 120)]  # the last two lie outside the window  # fmt: skip
    assert read(_Run(_events(spans), window=window)) == pytest.approx((2 + 1 + 1 + 4) / 4)
    # where the configuration names a cycle, per update: 4 vector steps of rollouts of 2
    assert read(_Run(_events(spans), window=window, algo={"rollout_steps": 2})) == pytest.approx((2 + 1 + 1 + 4) / 2)


@pytest.mark.parametrize("case", ["no_loop_spans", "no_monotonic_stamp", "no_trace"])
def test_the_readers_read_none_where_there_is_nothing_to_read(case):
    """As on the parent's events (no ``loop/head`` or ``loop/tail``, no
    ``parent``), on a program whose spans have no monotonic stamp, and on a
    run without a device trace."""
    bookkeeping, unspanned = _reader("loop.bookkeeping_host_ms"), _reader("device.idle_unspanned_share")
    if case == "no_loop_spans":
        parents = [{k: v for k, v in e.items() if k != "parent"} for e in _events([s for s in SPANS if s[0] not in ("loop/head", "loop/tail")])]
        assert bookkeeping(_Run(parents)) is None
        assert unspanned(_Run(parents)) == pytest.approx(100.0 * 12 / 70)  # the parent's spans, read whole: the hole as it stands
    elif case == "no_monotonic_stamp":
        assert bookkeeping(_Run(_events(with_mono=False))) is None and unspanned(_Run(_events(with_mono=False))) is None
    else:
        assert unspanned(_Run(_events(), neutral=None)) is None
        assert bookkeeping(_Run(_events(), neutral=None)) == pytest.approx(4.0)


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_entry_lists_the_two_dreamer_v3_cells(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry == {"name": name, **NEW[name], "moves": "env_steps_per_s", "workloads": DV3_CELLS}


@pytest.mark.parametrize("cell", DV3_CELLS + TOKEN_CELLS)
def test_a_cell_reads_the_new_metrics_as_its_entries_list_it(cell):
    """The token cells read neither: their tests count the metrics that list
    them, and those files are a ``benchmark`` PR's to edit (PERF.md section 7)."""
    readers = loader.layer_readers(loader.Cell(cell))
    assert set(readers) == {m["name"] for m in BENCH["per_layer"] if cell in m.get("workloads", [cell])}
    assert set(NEW) & set(readers) == (set(NEW) if cell in DV3_CELLS else set())
