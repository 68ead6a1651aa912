"""The device's time by program and by scope, and the idle time no host span
accounts for: what ``trace_reduce.load`` drops from the profiler's trace.

The program gives every jitted function of a measured path a stable name
(the XLA module is ``jit_<name>``) and the train step's parts a
``jax.named_scope`` each. On the device plane of the trace, the ``XLA Modules``
line holds one event per program execution, named ``jit_<name>(<program id>)``,
and each event of the ``XLA Ops`` line has an ``XEventMetadata`` whose ``tf_op``
stat is the op's ``op_name`` path, scopes included
(``jit(<train program>)/jvp(<scope>)/while/body/...``; found on the chip, PR
25). ``jax.profiler.ProfileData`` gives events with their own stats only, not
their metadata's, so the metadata table is read from the file's protobuf wire
format here (a few thousand entries; the events are skipped).

Which names those are is the algorithm's to say: ``reduce`` takes the tables
(``programs``, ``train_program``, ``scopes``), and ``of_run`` finds them, with
``leaf_spans``, in the cell's ``algorithms/<reference>.py``.

One neutral form, checked against a small recorded trace under the tests
directory, so the arithmetic does not depend on the profiler's reader::

    {"modules": [[name, start_ns, dur_ns], ...],       # XLA Modules, trace clock
     "ops":     [[name, start_ns, dur_ns, path], ...], # XLA Ops with each op's op_name path
     "sync":    [start_ns, dur_ns]}                    # the perfbench/sync annotation

Host spans come from the program's ``telemetry.jsonl`` (``t_mono_ns``, the
host's monotonic clock, which the harness's stamps and its sync annotation are
on too). Every reader returns ``None`` where there is nothing to read (no TPU
plane, a program without the names or the spans) and raises nothing.
"""

from __future__ import annotations

import functools
import glob
import gzip
import json
import os
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from perfbench import loader
from perfbench.trace_reduce import DEVICE_PLANE, OPS_LINE, SYNC, clip, overlap, short_name, union

MODULES_LINE = "XLA Modules"
#: the stat of an op's metadata that holds its op_name path (TPU profiler, jax 0.9)
PATH_STAT = "tf_op"
PROGRAM_STAT = "program_id"


Neutral = Dict[str, Any]


# --------------------------------------------------------------------------- #
# the file: events through ProfileData, op metadata from the wire format
# --------------------------------------------------------------------------- #


def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    value, shift = 0, 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def _fields(buf: bytes, start: int = 0, end: Optional[int] = None) -> Iterator[Tuple[int, int, Any]]:
    """``(field number, wire type, value)`` of one protobuf message; a
    length-delimited value is its ``(start, end)`` in ``buf``, not a copy."""
    pos, end = start, len(buf) if end is None else end
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 1:
            value, pos = buf[pos : pos + 8], pos + 8
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value, pos = (pos, pos + size), pos + size
        elif wire == 5:
            value, pos = buf[pos : pos + 4], pos + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield number, wire, value


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0] : span[1]].decode("utf-8", "replace")


def op_metadata(buf: bytes, plane_prefix: str = DEVICE_PLANE) -> Dict[str, List[Tuple[str, str]]]:
    """``op name -> [(program id, op_name path), ...]`` from the event metadata
    of the first device plane of a serialized ``XSpace``. Schema
    (tsl/profiler/protobuf/xplane.proto): ``XSpace.planes = 1``; ``XPlane``:
    ``name = 2``, ``event_metadata = 4`` and ``stat_metadata = 5`` (maps: key 1,
    value 2); ``XEventMetadata``: ``name = 2``, ``stats = 5``; ``XStatMetadata``:
    ``name = 2``; ``XStat``: ``metadata_id = 1``, ``uint64_value = 3``,
    ``int64_value = 4``, ``str_value = 5``, ``ref_value = 7``."""
    planes = []
    for number, wire, span in _fields(buf):
        if number == 1 and wire == 2:
            name = next((_text(buf, v) for n, w, v in _fields(buf, *span) if n == 2 and w == 2), "")
            if name.startswith(plane_prefix):
                planes.append((name, span))
    if not planes:
        return {}
    _, plane = sorted(planes)[0]
    stat_names: Dict[int, str] = {}
    events: List[Tuple[int, int]] = []
    for number, wire, span in _fields(buf, *plane):
        if wire != 2 or number not in (4, 5):
            continue
        entry = {n: v for n, w, v in _fields(buf, *span) if n in (1, 2)}
        if 2 not in entry:
            continue
        if number == 4:
            events.append(entry[2])
        else:
            stat_names[entry.get(1, 0)] = next((_text(buf, v) for n, w, v in _fields(buf, *entry[2]) if n == 2 and w == 2), "")
    out: Dict[str, List[Tuple[str, str]]] = {}
    for span in events:
        name, program, path = "", "", ""
        for number, wire, value in _fields(buf, *span):
            if number == 2 and wire == 2:
                name = _text(buf, value)
            elif number == 5 and wire == 2:
                stat = {n: v for n, w, v in _fields(buf, *value)}
                which = stat_names.get(stat.get(1, -1))
                if which == PATH_STAT:
                    path = _text(buf, stat[5]) if 5 in stat else stat_names.get(stat.get(7, -1), "")
                elif which == PROGRAM_STAT:
                    program = str(stat.get(3, stat.get(4, "")))
        if name:
            out.setdefault(name, []).append((program, path))
    return out


def module_program(name: str) -> Tuple[str, str]:
    """``("train_step", "1366...")`` from ``jit_train_step(1366...)``."""
    head, _, rest = name.partition("(")
    return (head[4:] if head.startswith("jit_") else head), rest.rstrip(")")


def load(path: str) -> Optional[Neutral]:
    """The neutral form from an ``.xplane.pb`` or from its saved ``.json.gz``;
    ``None`` where the trace has no device plane or no sync annotation."""
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            return json.load(f)
    from jax.profiler import ProfileData

    sync, device = None, None
    data = ProfileData.from_file(path)
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            if device is None or plane.name < device.name:
                device = plane
        elif sync is None:
            for line in plane.lines:
                sync = next(([float(e.start_ns), float(e.duration_ns)] for e in line.events if e.name == SYNC), None)
                if sync is not None:
                    break
    if device is None or sync is None:
        return None
    lines = {line.name: line for line in device.lines}
    if MODULES_LINE not in lines or OPS_LINE not in lines:
        return None
    modules = sorted(([e.name, float(e.start_ns), float(e.duration_ns)] for e in lines[MODULES_LINE].events), key=lambda m: m[1])
    with open(path, "rb") as f:
        metadata = op_metadata(f.read())
    starts = np.asarray([m[1] for m in modules])
    ends = np.asarray([m[1] + m[2] for m in modules])
    programs = [module_program(m[0])[1] for m in modules]
    ops = []
    for e in lines[OPS_LINE].events:
        start = float(e.start_ns)
        known = metadata.get(e.name, ())
        path_of = known[0][1] if known else ""
        if len(known) > 1:
            # the same instruction text in two programs: the module the op runs in decides
            i = int(np.searchsorted(starts, start, side="right")) - 1
            program = programs[i] if i >= 0 and start < ends[i] else ""
            path_of = next((p for prog, p in known if prog == program), path_of)
        ops.append([short_name(e.name), start, float(e.duration_ns), path_of])
    return {"modules": modules, "ops": ops, "sync": sync}


def save(neutral: Neutral, path: str, keep: Optional[Tuple[float, float]] = None) -> None:
    """The neutral form, cut to ``keep`` (trace ns)."""

    def kept(events):
        return [e for e in events if keep is None or (e[1] + e[2] >= keep[0] and e[1] <= keep[1])]

    with gzip.open(path, "wt") as f:
        json.dump({"modules": kept(neutral["modules"]), "ops": kept(neutral["ops"]), "sync": neutral["sync"]}, f)


# --------------------------------------------------------------------------- #
# the arithmetic
# --------------------------------------------------------------------------- #


@functools.lru_cache(maxsize=65536)  # a trace has some thousands of distinct paths over its hundreds of thousands of ops
def scope_of(path: str, scopes: Tuple[str, ...]) -> Tuple[Optional[str], bool]:
    """Which of the train step's ``scopes`` an op_name path lies in, and whether
    the op is of the backward pass (``transpose(jvp(<scope>))``, or
    ``<scope>/transpose(jvp())`` where the scope is around the ``value_and_grad``)."""
    for scope in scopes:
        at = path.find(scope)
        if at >= 0 and path[at + len(scope) : at + len(scope) + 1] in ("/", ")", ":", ""):
            return scope, "transpose(" in path
    return None, False


def _intervals(events: Sequence[Sequence[Any]]) -> np.ndarray:
    return np.asarray([(e[1], e[1] + e[2]) for e in events], np.float64).reshape(-1, 2)


def reduce(neutral: Neutral, *, programs: Sequence[str], train_program: str, scopes: Sequence[str], sync_mono_ns: float,
           window_mono_ns: Tuple[float, float], spans_mono_ns: np.ndarray, env_steps_mono_ns: np.ndarray) -> Dict[str, Any]:  # fmt: skip
    """Over the traced stretch ``window_mono_ns``: device seconds and
    executions by program (``programs``: the ones the algorithm names, whose
    cover is ``named_busy_s``), the self time by scope inside executions of
    ``train_program``, and the idle time under no leaf span (``spans_mono_ns``,
    ``[n, 2]``) nor env 0's ``step()``. All ``*_mono_ns`` are on the host's
    monotonic clock, as is ``sync_mono_ns``, the time read inside the sync
    annotation."""
    scopes = tuple(scopes)
    shift = neutral["sync"][0] + neutral["sync"][1] / 2.0 - sync_mono_ns  # monotonic -> trace
    lo, hi = window_mono_ns[0] + shift, window_mono_ns[1] + shift
    by_program: Dict[str, Dict[str, float]] = {}
    named, train_runs = [], []
    for name, start, dur in neutral["modules"]:
        end = start + dur
        if end <= lo or start >= hi:
            continue
        program = module_program(name)[0]
        entry = by_program.setdefault(program, {"seconds": 0.0, "whole_seconds": 0.0, "executions": 0})
        entry["seconds"] += (min(end, hi) - max(start, lo)) / 1e9
        if program in programs:
            named.append((start, end))
        if start >= lo and end <= hi:  # an execution cut by the stretch's edge is not a sample
            entry["whole_seconds"] += dur / 1e9
            entry["executions"] += 1
            if program == train_program:
                train_runs.append((start, end))
    ops = [e for e in neutral["ops"] if e[1] + e[2] > lo and e[1] < hi]
    cover = clip(union(_intervals(ops)), lo, hi)
    busy_s = float((cover[:, 1] - cover[:, 0]).sum()) / 1e9
    named_cover = clip(union(np.asarray(named, np.float64).reshape(-1, 2)), lo, hi)

    # self time by scope inside whole train-step executions: an op without a
    # scope of its own (a body op whose metadata has no path) takes the scope
    # of the op it is nested in, as a ``while`` spans the ops of its body
    by_scope: Dict[str, Dict[str, float]] = {s: {"forward": 0.0, "backward": 0.0} for s in scopes}
    unscoped = 0.0
    runs = np.asarray(train_runs, np.float64).reshape(-1, 2)
    stack: List[List[Any]] = []  # [end, self_ns, scope, backward]

    def close(upto: float) -> None:
        nonlocal unscoped
        while stack and stack[-1][0] <= upto:
            _, own, scope, backward = stack.pop()
            if scope is None:
                unscoped += max(own, 0.0) / 1e9
            else:
                by_scope[scope]["backward" if backward else "forward"] += max(own, 0.0) / 1e9

    if len(runs):
        for _, start, dur, path in sorted(ops, key=lambda e: (e[1], -e[2])):
            i = int(np.searchsorted(runs[:, 0], start, side="right")) - 1
            if i < 0 or start + dur > runs[i, 1]:
                continue
            close(start)
            scope, backward = scope_of(path, scopes)
            if scope is None and stack:
                scope, backward = stack[-1][2], stack[-1][3]
            if stack:
                stack[-1][1] -= dur
            stack.append([start + dur, dur, scope, backward])
        close(float("inf"))

    edges = np.concatenate([[lo], cover.reshape(-1), [hi]]).reshape(-1, 2)
    gaps = edges[edges[:, 1] > edges[:, 0]]
    idle_s = float((gaps[:, 1] - gaps[:, 0]).sum()) / 1e9
    host = np.concatenate([np.asarray(spans_mono_ns, np.float64).reshape(-1, 2), np.asarray(env_steps_mono_ns, np.float64).reshape(-1, 2)])
    accounted = overlap(gaps, clip(union(host + shift), lo, hi)) / 1e9
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_s,
        "programs": by_program,
        "named_busy_s": overlap(cover, named_cover) / 1e9,
        "train_executions": len(train_runs),
        "scopes": by_scope,
        "train_unscoped_s": unscoped,
        "idle_s": idle_s,
        "idle_unattributed_s": max(idle_s - accounted, 0.0),
        "leaf_spans": len(spans_mono_ns),
    }


# --------------------------------------------------------------------------- #
# a finished run
# --------------------------------------------------------------------------- #


def spans(run: Any, name: str) -> List[Tuple[int, float]]:
    """``(t_mono_ns, dur seconds)`` of the program's ``span`` events of ``name``
    that lie in the window, by their own monotonic stamp (a program without
    ``t_mono_ns`` has none)."""
    lo, hi = run.window["open_ns"], run.window["close_ns"] + int(5e6)
    out = []
    for e in run.telemetry_events:
        if e.get("event") == "span" and e.get("name") == name and e.get("t_mono_ns") is not None:
            t0 = int(e["t_mono_ns"])
            if lo <= t0 and t0 + int(float(e["dur"]) * 1e9) <= hi:
                out.append((t0, float(e["dur"])))
    return out


def span_mean_ms(run: Any, name: str) -> Optional[float]:
    found = spans(run, name)
    if not found:
        return None
    return 1e3 * sum(d for _, d in found) / len(found)


def _trace_file(run: Any) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(run.run_dir, "trace", "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def neutral_of_run(run: Any) -> Optional[Neutral]:
    """The neutral form of a finished run's trace (the trace directory is
    ``<run.run_dir>/trace``), loaded once per run; ``None`` where it has no
    trace, no sync, no traced stretch or no device plane."""
    if "_neutral" not in run.__dict__:
        path = _trace_file(run)
        ready = path is not None and getattr(getattr(run, "watcher", None), "sync", None) is not None
        run.__dict__["_neutral"] = load(path) if ready and getattr(run, "stretch_ns", None) is not None else None
    return run.__dict__["_neutral"]


def reduce_run(run: Any, scopes: Sequence[str], leaves: bool = True) -> Optional[Dict[str, Any]]:
    """``reduce`` of a finished run's trace over its traced stretch
    (``run.stretch_ns``: ``run.trace_stretch``) with the tables of the cell's
    algorithm and these ``scopes``; with ``leaves`` the idle time is held
    against the algorithm's leaf spans and env 0's ``step()``."""
    neutral = neutral_of_run(run)
    if neutral is None:
        return None
    tables = loader.algorithm(run.cell)
    sync = run.watcher.sync
    found = [(t0, t0 + d * 1e9) for name in tables.leaf_spans for t0, d in spans(run, name)] if leaves else []
    return reduce(
        neutral,
        programs=tables.programs,
        train_program=tables.train_program,
        scopes=scopes,
        sync_mono_ns=(sync["before_ns"] + sync["inside_ns"]) / 2.0,
        window_mono_ns=(float(run.stretch_ns[0]), float(run.stretch_ns[1])),
        spans_mono_ns=np.asarray(found, np.float64).reshape(-1, 2),
        env_steps_mono_ns=np.stack([run.entry_ns, run.exit_ns], 1) if leaves else np.zeros((0, 2), np.float64),
    )


def of_run(run: Any) -> Optional[Dict[str, Any]]:
    """The reduction of a finished traced run by the tables of the cell's
    algorithm, made once per run; ``None`` where it has no trace, no sync or
    no device plane."""
    if "_device_time" not in run.__dict__:
        run.__dict__["_device_time"] = reduce_run(run, loader.algorithm(run.cell).scopes)
    return run.__dict__["_device_time"]


def program_ms(reduced: Optional[Dict[str, Any]], program: str) -> Optional[float]:
    """Device milliseconds per execution of ``program``, over its executions
    that lie whole inside the traced stretch."""
    entry = (reduced or {}).get("programs", {}).get(program)
    if not entry or not entry["executions"]:
        return None
    return 1e3 * entry["whole_seconds"] / entry["executions"]


def train_ms(run: Any) -> Optional[float]:
    """Device milliseconds per execution of the train program of the cell's algorithm."""
    return program_ms(of_run(run), loader.algorithm(run.cell).train_program)


def scope_ms(reduced: Optional[Dict[str, Any]], scopes: Sequence[str]) -> Optional[float]:
    """Device milliseconds per train-step execution under ``scopes``, forward
    and backward; ``None`` where no op of the step carries any scope."""
    if not reduced or not reduced["train_executions"]:
        return None
    if not any(v["forward"] + v["backward"] for v in reduced["scopes"].values()):
        return None
    total = sum(reduced["scopes"][s]["forward"] + reduced["scopes"][s]["backward"] for s in scopes)
    return 1e3 * total / reduced["train_executions"]


def record(run: Any, path: str, ms: float = 320.0) -> None:
    """The last ``ms`` of a finished run's trace in the neutral form, with the
    host's side of the same cut beside it: the recorded trace of the tests."""
    neutral = load(_trace_file(run))
    sync = (run.watcher.sync["before_ns"] + run.watcher.sync["inside_ns"]) / 2.0
    shift = neutral["sync"][0] + neutral["sync"][1] / 2.0 - sync
    end = float(run.window["close_ns"])
    cut = (end - ms * 1e6, end)
    save(neutral, path, keep=(cut[0] + shift, cut[1] + shift))
    leaf_spans = loader.algorithm(run.cell).leaf_spans
    leaves = {name: [[t0, d] for t0, d in spans(run, name) if t0 + d * 1e9 >= cut[0] and t0 <= cut[1]] for name in leaf_spans}
    steps = np.stack([run.entry_ns, run.exit_ns], 1)
    steps = steps[(steps[:, 1] >= cut[0]) & (steps[:, 0] <= cut[1])]
    with open(path.replace(".json.gz", "_host.json"), "w") as f:
        json.dump({"sync_mono_ns": sync, "window_mono_ns": list(cut), "spans": leaves, "env_steps_mono_ns": steps.tolist()}, f)
