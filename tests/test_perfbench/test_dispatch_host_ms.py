"""``train_step.dispatch_host_ms`` (PR 34): the mean ``train/dispatch`` span of
the window, on the recorded cut of a chip run that the tests hold
(``recorded_device_time_host.json``: S, PR 25, two dispatches), on a synthetic
run, and ``None`` where a run has no such span."""

import json
import os

import pytest

from perfbench import loader
from perfbench.loader import ROOT
from tests.test_perfbench.test_device_time import BASE, MS, _Run, _span_events

NAME = "train_step.dispatch_host_ms"
DV3_CELLS = ["dv3_S_walker.train", "dv3_XL_crafter.train"]
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


def test_the_entry_lists_the_two_dreamer_v3_cells():
    entry = next(m for m in BENCH["per_layer"] if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "ms", "better": "lower", "source": "program_span", "layer": "train step",
                     "moves": "env_steps_per_s", "workloads": DV3_CELLS}  # fmt: skip


@pytest.mark.parametrize("cell", DV3_CELLS)
def test_a_dreamer_v3_cell_reads_it_among_the_metrics_that_list_the_cell(cell):
    readers = loader.layer_readers(loader.Cell(cell))
    assert NAME in readers
    assert set(readers) == {m["name"] for m in BENCH["per_layer"] if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", ["glm47_flash_ep8.train", "lfm2_24b_a2b_ep8.train"])
def test_the_token_cells_do_not_read_it(cell):
    assert NAME not in loader.layer_readers(loader.Cell(cell))


def test_the_reader_on_the_recorded_run():
    with open(os.path.join(os.path.dirname(__file__), "recorded_device_time_host.json")) as f:
        host = json.load(f)
    first, second = host["spans"]["train/dispatch"]
    assert (first[1], second[1]) == (0.005934759000012946, 0.005914268999987371)  # the file as recorded: seconds
    window = {"open_ns": int(host["window_mono_ns"][0]), "close_ns": int(host["window_mono_ns"][1]), "vector_steps": 2}
    run = _Run(None, _span_events({k: [tuple(p) for p in v] for k, v in host["spans"].items()}), window)
    read = loader.layer_readers(loader.Cell(DV3_CELLS[0]))[NAME]
    assert read(run) == pytest.approx(5.9245140000001586, rel=1e-12)  # by hand: (5.934759 + 5.914269) / 2 ms


def test_the_reader_counts_the_windows_spans_only_and_reads_none_without_one():
    read = loader.layer_readers(loader.Cell(DV3_CELLS[1]))[NAME]
    window = {"open_ns": int(BASE), "close_ns": int(BASE + 100 * MS), "vector_steps": 4}
    spans = {"train/dispatch": [(BASE - 5 * MS, 0.5), (BASE + 7 * MS, 0.003), (BASE + 30 * MS, 0.011), (BASE + 120 * MS, 0.5)], "replay/draw": [(BASE + 6 * MS, 0.0008)]}
    assert read(_Run(None, _span_events(spans), window)) == pytest.approx(7.0)
    assert read(_Run(None, _span_events({"replay/draw": [(BASE + 6 * MS, 0.0008)]}), window)) is None
    # a program whose spans carry no monotonic stamp (a commit from before the spans had one): nothing to read, no error
    assert read(_Run(None, _span_events(spans, with_mono=False), window)) is None
