"""``perfbench/device_time.py`` and the fifteen readers over it: the op
metadata out of a hand-encoded ``XSpace``, the arithmetic on a synthetic trace
whose every number is checked by hand, the recorded cut of a chip run of PR 25
(``recorded_device_time*``: S, the last 320 ms of the traced stretch), and
``None`` wherever there is nothing to read."""

import json
import os
import struct

import numpy as np
import pytest

from perfbench import device_time, loader
from perfbench.algorithms import dreamer_v3 as tables
from perfbench.loader import ROOT

HERE = os.path.dirname(__file__)
RECORDED = os.path.join(HERE, "recorded_device_time.json.gz")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
NEW = ["ring.write_device_ms", "replay.gather_device_ms", "player.forward_device_ms", "train_step.device_ms",
       "train_step.world_model_device_ms", "train_step.rssm_scan_device_ms", "train_step.behaviour_device_ms",
       "train_step.optimizer_device_ms", "train_step.device_mfu", "loop.action_fetch_ms", "loop.ring_add_host_ms",
       "loop.env_step_host_ms", "replay.draw_host_ms", "loop.train_block_ms", "device.idle_unattributed_share"]  # fmt: skip
MS = 1e6
#: the names ``reduce`` looks for: Dreamer-V3's, as its cells' readers get them through ``of_run``
TABLES = {"programs": tables.programs, "train_program": tables.train_program, "scopes": tables.scopes}


# --------------------------------------------------------------------------- #
# the file's protobuf: a hand-made XSpace, encoded here field by field
# --------------------------------------------------------------------------- #


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, float):
        return _varint(number << 3 | 1) + struct.pack("<d", value)
    data = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(data)) + data


def _message(*fields):
    return b"".join(_field(n, v) for n, v in fields)


def _xspace():
    stat_names = {1: "tf_op", 2: "program_id", 3: "flops", 4: "jit(ring_write)/a/path/by/reference:"}

    def op(ident, name, program, path=None, ref=None):
        stats = [(5, _message((1, 3), (2, 2.5))), (5, _message((1, 2), (3, program)))]
        stats.append((5, _message((1, 1), (5, path)) if path is not None else _message((1, 1), (7, ref))))
        return (4, _message((1, ident), (2, _message((1, ident), (2, name), (4, name.split(" ")[0]), *stats))))

    events = b"".join(_field(4, _message((1, 1 + k % 3), (2, 1000 * k), (3, 500))) for k in range(50))
    device = _message(
        (1, 7), (2, "/device:TPU:0"), (3, _message((2, "XLA Ops"), (3, 12345)) + events),
        op(1, "%fusion.1 = f32[2]", 13668650761053289554, "jit(dv3_train_step)/jvp(dv3/wm/encode)/add:"),
        op(2, "%fusion.1 = f32[2]", 77, "jit(ring_write)/scatter:"),
        op(3, "%copy.9 = u8[4]", 77, ref=4),
        *[(5, _message((1, k), (2, _message((1, k), (2, v))))) for k, v in stat_names.items()],
    )  # fmt: skip
    host = _message((1, 1), (2, "/host:CPU"), (4, _message((1, 1), (2, _message((1, 1), (2, "python"))))))
    return _message((1, host), (1, _message((2, "/device:TPU:1"))), (1, device))


def test_op_metadata_from_the_wire_format():
    table = device_time.op_metadata(_xspace())
    assert table == {
        "%fusion.1 = f32[2]": [("13668650761053289554", "jit(dv3_train_step)/jvp(dv3/wm/encode)/add:"), ("77", "jit(ring_write)/scatter:")],
        "%copy.9 = u8[4]": [("77", "jit(ring_write)/a/path/by/reference:")],
    }
    assert device_time.op_metadata(_message((1, _message((2, "/host:CPU"))))) == {}
    assert device_time.module_program("jit_dv3_train_step(13668650761053289554)") == ("dv3_train_step", "13668650761053289554")
    assert device_time.module_program("jit__threefry_split(5)") == ("_threefry_split", "5")


@pytest.mark.parametrize(
    "path,scope,backward",
    [
        ("jit(dv3_train_step)/jvp(dv3/wm/rssm_scan)/while/body/closed_call/WorldModel.dynamic/add:", "dv3/wm/rssm_scan", False),
        ("jit(dv3_train_step)/transpose(jvp(dv3/wm/rssm_scan))/while/body/dot_general:", "dv3/wm/rssm_scan", True),
        ("jit(dv3_train_step)/dv3/critic/loss/transpose(jvp())/dot_general:", "dv3/critic/loss", True),
        ("jit(dv3_train_step)/dv3/wm/optimizer/mul:", "dv3/wm/optimizer", False),
        ("jit(dv3_train_step)/jvp(dv3/wm/encode):", "dv3/wm/encode", False),
        ("jit(dv3_train_step)/reduce_sum:", None, False),
        ("jit(dv3_player_step)/dv3/player/rssm/dot_general:", None, False),
        ("", None, False),
    ],
)
def test_scope_of_an_op_name_path(path, scope, backward):
    assert device_time.scope_of(path, tables.scopes) == (scope, backward)


# --------------------------------------------------------------------------- #
# the arithmetic, on a trace small enough to check by hand
# --------------------------------------------------------------------------- #

T = "jit(dv3_train_step)/"


def _synthetic():
    modules = [
        ["jit_dv3_player_step(1)", 0 * MS, 1 * MS],
        ["jit_ring_write(2)", 2 * MS, 10 * MS],
        ["jit_ring_gather_sequences(3)", 12 * MS, 8 * MS],
        ["jit_dv3_train_step(4)", 20 * MS, 30 * MS],
        ["jit__threefry_split(9)", 50 * MS, 0.5 * MS],
        ["jit_dv3_train_step(4)", 60 * MS, 30 * MS],
        ["jit_ring_write(2)", 95 * MS, 15 * MS],  # runs past the end of the stretch: counted as time, not as a sample
    ]
    ops = [
        ["%fusion.1", 0 * MS, 1 * MS, "jit(dv3_player_step)/dv3/player/rssm/dot_general:"],
        ["%copy.9", 2 * MS, 10 * MS, "jit(ring_write)/scatter:"],
        ["%copy.9", 12 * MS, 8 * MS, "jit(ring_gather_sequences)/gather:"],
        ["%convolution.1", 20 * MS, 4 * MS, T + "jvp(dv3/wm/encode)/WorldModel.encode/conv_general_dilated:"],
        ["%while.1", 24 * MS, 10 * MS, T + "jvp(dv3/wm/rssm_scan)/while:"],
        ["%fusion.2", 24.5 * MS, 3.5 * MS, T + "jvp(dv3/wm/rssm_scan)/while/body/closed_call/WorldModel.dynamic/dot_general:"],
        ["%fusion.3", 28 * MS, 5 * MS, ""],  # no metadata of its own: the while it is nested in decides
        ["%convolution.2", 34 * MS, 3 * MS, T + "jvp(dv3/wm/decode)/WorldModel.decode/conv_transpose:"],
        ["%while.2", 37 * MS, 8 * MS, T + "transpose(jvp(dv3/wm/rssm_scan))/while:"],
        ["%fusion.4", 38 * MS, 6 * MS, T + "transpose(jvp(dv3/wm/rssm_scan))/while/body/closed_call/WorldModel.dynamic/dot_general:"],
        ["%fusion.5", 45 * MS, 2 * MS, T + "dv3/wm/optimizer/mul:"],
        ["%reduce.1", 47 * MS, 1 * MS, T + "reduce_sum:"],
        ["%fusion.6", 48 * MS, 2 * MS, T + "dv3/critic/loss/transpose(jvp())/dot_general:"],
        ["%fusion.7", 50 * MS, 0.5 * MS, "jit(_threefry_split)/threefry2x32:"],
        ["%while.3", 60 * MS, 30 * MS, T + "jvp(dv3/behaviour/imagine)/while:"],
        ["%copy.9", 95 * MS, 15 * MS, "jit(ring_write)/scatter:"],
    ]
    return {"modules": modules, "ops": ops, "sync": [0.0, 0.0]}


BASE = 1e9  # the monotonic time read inside the sync annotation


def _reduce_synthetic(neutral=None, leaf_spans=True):
    spans = np.asarray([[BASE + 0.5 * MS, BASE + 1.5 * MS], [BASE + 52 * MS, BASE + 58 * MS]] if leaf_spans else []).reshape(-1, 2)
    env = np.asarray([[BASE + 91 * MS, BASE + 93 * MS]])
    return device_time.reduce(neutral or _synthetic(), **TABLES, sync_mono_ns=BASE, window_mono_ns=(BASE, BASE + 100 * MS), spans_mono_ns=spans, env_steps_mono_ns=env)


def test_reduction_of_a_synthetic_trace_by_hand():
    r = _reduce_synthetic()
    assert r["window_s"] == pytest.approx(0.100)
    # busy: 0-1, 2-50.5, 60-90, 95-100 ms
    assert r["busy_s"] == pytest.approx(0.0845) and r["idle_s"] == pytest.approx(0.0155)
    assert r["programs"]["ring_write"] == {"seconds": pytest.approx(0.015), "whole_seconds": pytest.approx(0.010), "executions": 1}
    assert r["programs"]["dv3_train_step"] == {"seconds": pytest.approx(0.060), "whole_seconds": pytest.approx(0.060), "executions": 2}
    assert r["programs"]["_threefry_split"]["seconds"] == pytest.approx(0.0005)
    # everything busy but the key split's half millisecond lies in a named program
    assert r["named_busy_s"] == pytest.approx(0.084)
    assert r["train_executions"] == 2
    scopes = r["scopes"]
    assert scopes["dv3/wm/encode"] == {"forward": pytest.approx(0.004), "backward": 0.0}
    # the forward while: 1.5 ms of its own, 3.5 of a body op under its scope, 5 of one that inherits it
    assert scopes["dv3/wm/rssm_scan"] == {"forward": pytest.approx(0.010), "backward": pytest.approx(0.008)}
    assert scopes["dv3/wm/decode"]["forward"] == pytest.approx(0.003)
    assert scopes["dv3/wm/optimizer"]["forward"] == pytest.approx(0.002)
    assert scopes["dv3/critic/loss"] == {"forward": 0.0, "backward": pytest.approx(0.002)}
    assert scopes["dv3/behaviour/imagine"]["forward"] == pytest.approx(0.030)
    assert r["train_unscoped_s"] == pytest.approx(0.001)
    scoped = sum(v["forward"] + v["backward"] for v in scopes.values())
    assert scoped + r["train_unscoped_s"] == pytest.approx(r["programs"]["dv3_train_step"]["whole_seconds"])
    # idle gaps 1-2, 50.5-60, 90-95 ms; a leaf span covers 1-1.5 and 52-58, env 0's step 91-93
    assert r["idle_unattributed_s"] == pytest.approx(0.0155 - 0.0085)
    assert device_time.program_ms(r, "ring_write") == pytest.approx(10.0)
    assert device_time.program_ms(r, "dv3_train_step") == pytest.approx(30.0)
    assert device_time.program_ms(r, "ring_amend") is None
    assert device_time.scope_ms(r, tables.WORLD_MODEL) == pytest.approx(12.5)
    assert device_time.scope_ms(r, ("dv3/wm/rssm_scan",)) == pytest.approx(9.0)
    assert device_time.scope_ms(r, tables.BEHAVIOUR) == pytest.approx(16.0)
    assert device_time.scope_ms(r, tables.OPTIMIZER) == pytest.approx(1.0)


def test_a_program_without_the_names_reads_nothing():
    """The parent's programs: generic module names, no scope in any path."""
    neutral = _synthetic()
    rename = {"dv3_train_step": "local_train", "ring_write": "write", "ring_gather_sequences": "gather_sequences", "dv3_player_step": "_step"}
    for m in neutral["modules"]:
        program, ident = device_time.module_program(m[0])
        m[0] = f"jit_{rename.get(program, program)}({ident})"
    for o in neutral["ops"]:
        o[3] = o[3].split("/")[0] + "/mul:" if o[3] else ""
    r = _reduce_synthetic(neutral)
    assert r["busy_s"] == pytest.approx(0.0845) and r["named_busy_s"] == 0.0 and r["train_executions"] == 0
    assert all(device_time.program_ms(r, p) is None for p in tables.programs)
    assert device_time.scope_ms(r, tables.WORLD_MODEL) is None


# --------------------------------------------------------------------------- #
# the readers, on a run that is put together from the pieces
# --------------------------------------------------------------------------- #


class _Cell:
    chips = 1
    config = {"reference": "dreamer_v3", "model_flops_per_grad_step": 1_031_222_067_200}


class _Run:
    """What a reader asks of a finished run."""

    cell = _Cell()
    peak = {"bf16_flops_per_s": 197e12}
    run_dir = "/nonexistent"
    watcher = None

    def __init__(self, reduced, events, window):
        self.telemetry_events, self.window = events, window
        if reduced is not None:
            self.__dict__["_device_time"] = reduced


def _span_events(spans, with_mono=True):
    events = []
    for name, pairs in spans.items():
        for t0, dur in pairs:
            e = {"event": "span", "name": name, "t_start": t0 / 1e9 + 1.7e9, "dur": dur}
            if with_mono:
                e["t_mono_ns"] = int(t0)
            events.append(e)
    return events


def _readers():
    readers = loader.layer_readers(loader.Cell(BENCH["workloads"][0]["name"]))
    assert set(NEW) <= set(readers), "a new metric has no entry or no reader"
    return readers


def test_every_new_metric_has_its_entry_and_both_cells():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    # the cells of PR 25, which these metrics were written for; a cell of another algorithm lists itself where it has something to read
    cells = {"dv3_S_walker.train", "dv3_XL_crafter.train"}
    for name in NEW:
        entry = entries[name]
        assert entry["moves"] == "env_steps_per_s" and cells <= set(entry["workloads"])
        assert entry["better"] == ("higher" if name == "train_step.device_mfu" else "lower")
        assert entry["source"] == ("program_span" if name.startswith(("loop.", "replay.draw")) else "device_trace")


def test_the_readers_on_the_synthetic_run():
    spans = {
        "player/get_actions": [(BASE + 1 * MS, 0.002), (BASE + 40 * MS, 0.004)],
        "ring/add": [(BASE + 3 * MS, 0.001)],
        "env/step": [(BASE + 4 * MS, 0.0005), (BASE + 5 * MS, 0.0015)],
        "replay/draw": [(BASE + 6 * MS, 0.0008)],
        "train/dispatch": [(BASE + 7 * MS, 0.003)],
        "train/block": [(BASE + 10 * MS, 0.040), (BASE + 60 * MS, 0.020), (BASE + 99 * MS, 0.5)],  # the last one ends after the window
    }
    window = {"open_ns": int(BASE), "close_ns": int(BASE + 100 * MS), "vector_steps": 4}
    run = _Run(_reduce_synthetic(), _span_events(spans), window)
    values = {name: reader(run) for name, reader in _readers().items() if name in NEW}
    assert values["ring.write_device_ms"] == pytest.approx(10.0)
    assert values["replay.gather_device_ms"] == pytest.approx(8.0)
    assert values["player.forward_device_ms"] == pytest.approx(1.0)
    assert values["train_step.device_ms"] == pytest.approx(30.0)
    assert values["train_step.world_model_device_ms"] == pytest.approx(12.5)
    assert values["train_step.rssm_scan_device_ms"] == pytest.approx(9.0)
    assert values["train_step.behaviour_device_ms"] == pytest.approx(16.0)
    assert values["train_step.optimizer_device_ms"] == pytest.approx(1.0)
    assert values["train_step.device_mfu"] == pytest.approx(100 * 1_031_222_067_200 / (0.030 * 197e12))
    assert values["loop.action_fetch_ms"] == pytest.approx(3.0)
    assert values["loop.ring_add_host_ms"] == pytest.approx(1.0)
    assert values["loop.env_step_host_ms"] == pytest.approx(1.0)
    assert values["replay.draw_host_ms"] == pytest.approx(0.8)
    assert values["loop.train_block_ms"] == pytest.approx(60.0 / 4)
    assert values["device.idle_unattributed_share"] == pytest.approx(100 * 7.0 / 15.5)


@pytest.mark.parametrize("case", ["no_device_plane", "parent_program"])
def test_every_new_reader_returns_none_where_there_is_nothing_to_read(case, tmp_path):
    window = {"open_ns": int(BASE), "close_ns": int(BASE + 100 * MS), "vector_steps": 4}
    if case == "no_device_plane":
        # a traced run on the CPU: spans there are, a device plane there is not (the file loader's own
        # ``None`` on a real CPU trace is driven by test_run.py's traced run, through every reader)
        run = _Run(None, _span_events({"player/get_actions": [(BASE + MS, 0.002)]}), window)
        run.run_dir = str(tmp_path)
        device = [n for n in NEW if not n.startswith(("loop.", "replay.draw"))]
        values = {name: _readers()[name](run) for name in NEW}
        assert all(values[n] is None for n in device), values
        assert values["loop.action_fetch_ms"] == pytest.approx(2.0) and values["replay.draw_host_ms"] is None
    else:
        # the parent commit under this PR's benchmark files: generic module names, spans without t_mono_ns
        neutral = _synthetic()
        for m in neutral["modules"]:
            m[0] = "jit_local_train(4)" if "train" in m[0] else "jit_write(2)"
        for o in neutral["ops"]:
            o[3] = ""
        run = _Run(_reduce_synthetic(neutral, leaf_spans=False), _span_events({"Time/train_time": [(BASE + MS, 0.05)]}, with_mono=False), window)
        assert [n for n in NEW if _readers()[n](run) is not None] == []


# --------------------------------------------------------------------------- #
# the recorded cut of a chip run
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(RECORDED):
        pytest.skip("no recorded device-time trace in this checkout")
    with open(RECORDED.replace(".json.gz", "_host.json")) as f:
        host = json.load(f)
    with open(RECORDED.replace(".json.gz", "_expect.json")) as f:
        expect = json.load(f)
    neutral = device_time.load(RECORDED)
    leaves = [(t0, t0 + d * 1e9) for pairs in host["spans"].values() for t0, d in pairs]
    reduced = device_time.reduce(
        neutral,
        **TABLES,
        sync_mono_ns=host["sync_mono_ns"],
        window_mono_ns=tuple(host["window_mono_ns"]),
        spans_mono_ns=np.asarray(leaves, np.float64).reshape(-1, 2),
        env_steps_mono_ns=np.asarray(host["env_steps_mono_ns"], np.float64).reshape(-1, 2),
    )
    return neutral, host, reduced, expect


def test_the_recorded_trace_holds_the_modules_line_and_each_ops_scope(recorded):
    neutral, _, _, _ = recorded
    programs = {device_time.module_program(m[0])[0] for m in neutral["modules"]}
    assert {"ring_write", "ring_gather_sequences", "dv3_train_step", "dv3_player_step", "dv3_target_ema"} <= programs
    found = {device_time.scope_of(o[3], tables.scopes)[0] for o in neutral["ops"]}
    assert set(tables.scopes) <= found
    body = [o for o in neutral["ops"] if "dv3/wm/rssm_scan" in o[3] and "/while/body/" in o[3]]
    assert body and any("transpose(jvp(" in o[3] for o in body), "the scan's body ops carry the scope, forward and backward"


def test_the_readers_on_the_recorded_trace(recorded):
    """``recorded_device_time_expect.json``: ``metrics`` were worked out by
    hand from the file's modules line and the host file's spans (plain sums,
    not ``reduce``); ``by_reduce`` is what ``reduce`` read when the cut was
    recorded, held by the identities of the next test."""
    neutral, host, reduced, expect = recorded
    window = {"open_ns": int(host["window_mono_ns"][0]), "close_ns": int(host["window_mono_ns"][1]), "vector_steps": expect["vector_steps"]}
    run = _Run(reduced, _span_events({k: [tuple(p) for p in v] for k, v in host["spans"].items()}), window)
    values = {name: reader(run) for name, reader in _readers().items() if name in NEW}
    assert all(values[name] is not None for name in NEW), values
    for name, value in {**expect["metrics"], **expect["by_reduce"]}.items():
        assert values[name] == pytest.approx(value, rel=1e-6), name
    assert set(expect["metrics"]) | set(expect["by_reduce"]) == set(NEW)
    # by hand: whole executions of each program on the modules line of the cut
    lo = host["window_mono_ns"][0] + (neutral["sync"][0] + neutral["sync"][1] / 2 - host["sync_mono_ns"])
    hi = lo + host["window_mono_ns"][1] - host["window_mono_ns"][0]
    for program, metric in (("ring_write", "ring.write_device_ms"), ("ring_gather_sequences", "replay.gather_device_ms"),
                            ("dv3_train_step", "train_step.device_ms"), ("dv3_player_step", "player.forward_device_ms")):  # fmt: skip
        durs = [m[2] for m in neutral["modules"] if device_time.module_program(m[0])[0] == program and m[1] >= lo and m[1] + m[2] <= hi]
        assert len(durs) == expect["executions"][program] and values[metric] == pytest.approx(sum(durs) / len(durs) / 1e6)


def test_the_identities_hold_on_the_recorded_trace(recorded):
    _, _, r, _ = recorded
    # busy and idle make up the stretch; the named programs hold nearly all that is busy
    assert r["busy_s"] + r["idle_s"] == pytest.approx(r["window_s"], rel=1e-9)
    assert 0.95 * r["busy_s"] <= r["named_busy_s"] <= r["busy_s"] * (1 + 1e-9)
    assert 0.0 <= r["idle_unattributed_s"] <= r["idle_s"]
    # inside the train step: the scopes and what lies under none add up to the ops' time, which the
    # module's time bounds; the scopes hold at least nine tenths of the step
    train = r["programs"]["dv3_train_step"]
    scoped = sum(v["forward"] + v["backward"] for v in r["scopes"].values())
    assert scoped + r["train_unscoped_s"] <= train["whole_seconds"] * (1 + 1e-9)
    assert scoped >= 0.90 * train["whole_seconds"]
    world, behaviour, optimizer = (device_time.scope_ms(r, g) for g in (tables.WORLD_MODEL, tables.BEHAVIOUR, tables.OPTIMIZER))
    step = device_time.program_ms(r, "dv3_train_step")
    assert 0.90 * step <= world + behaviour + optimizer <= step
    assert device_time.scope_ms(r, ("dv3/wm/rssm_scan",)) < world
    # the share of the peak inside the step is a share
    mfu = 100 * _Cell.config["model_flops_per_grad_step"] / (step / 1e3 * 197e12)
    assert 0.0 < mfu < 100.0
