"""From the profiler's trace to device busy time, the operations that took
most of it, and the idle gaps by what the host was doing in them.

One file, checked against a small recorded trace under the tests directory.
It works on a neutral form of the trace, ``{plane: {line: [(name, start_ns,
dur_ns), ...]}}``, so the arithmetic does not depend on the profiler's reader.

Clocks: trace times count from the profiler's start. The harness writes one
``perfbench/sync`` annotation while it reads the host's monotonic clock, which
puts env 0's timestamps and the window on the trace's clock. Host spans are
the ``TraceAnnotation``s the program's timed sections write
(``Time/train_time``, ``Time/env_interaction_time``); env 0 may live in a
forked worker that the profiler cannot see, so its ``step()`` intervals come
from the benchmark's own timestamps.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

Events = List[Tuple[str, float, float]]
Planes = Dict[str, Dict[str, Events]]

SYNC = "perfbench/sync"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_SPANS = ("Time/train_time", "Time/env_interaction_time")
TOP = 10


def load(path: str) -> Planes:
    """An ``.xplane.pb`` through ``jax.profiler.ProfileData``, or the neutral
    form saved as ``.json.gz``."""
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            raw = json.load(f)
        return {p: {l: [tuple(e) for e in ev] for l, ev in lines.items()} for p, lines in raw.items()}
    from jax.profiler import ProfileData

    planes: Planes = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend((e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events)
    return planes


def save(planes: Planes, path: str, keep: Optional[Tuple[float, float]] = None, lines: Optional[Sequence[str]] = None) -> None:
    """The neutral form, cut to ``keep`` (trace ns) and to the named lines."""
    out: Dict[str, Dict[str, list]] = {}
    for plane, plane_lines in planes.items():
        for line, events in plane_lines.items():
            if lines is not None and line not in lines and not any(n in (SYNC, *HOST_SPANS) for n, _, _ in events[:2000]):
                continue
            kept = [list(e) for e in events if keep is None or (e[1] + e[2] >= keep[0] and e[1] <= keep[1]) or e[0] == SYNC]
            if kept:
                out.setdefault(plane, {})[line] = kept
    with gzip.open(path, "wt") as f:
        json.dump(out, f)


def union(intervals: np.ndarray) -> np.ndarray:
    """Sorted, disjoint ``[n, 2]`` cover of ``intervals``."""
    if len(intervals) == 0:
        return np.zeros((0, 2))
    intervals = intervals[np.argsort(intervals[:, 0])]
    ends = np.maximum.accumulate(intervals[:, 1])
    starts_new = np.concatenate([[True], intervals[1:, 0] > ends[:-1]])
    starts = intervals[starts_new, 0]
    last = np.concatenate([np.nonzero(starts_new)[0][1:] - 1, [len(intervals) - 1]])
    return np.stack([starts, ends[last]], 1)


def clip(intervals: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(intervals) == 0:
        return intervals
    out = np.stack([np.maximum(intervals[:, 0], lo), np.minimum(intervals[:, 1], hi)], 1)
    return out[out[:, 1] > out[:, 0]]


def overlap(a: np.ndarray, b: np.ndarray) -> float:
    """Total length in which both disjoint sorted covers hold."""
    total, j = 0.0, 0
    for lo, hi in a:
        while j < len(b) and b[j, 1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k, 0] < hi:
            total += min(hi, b[k, 1]) - max(lo, b[k, 0])
            k += 1
    return total


def short_name(name: str) -> str:
    """``%copy.9 u8[4,62501,64,64,3]`` from the trace's full HLO line."""
    head, _, rest = name.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0].lstrip("(") if rest else ""
    return f"{head} {shape}".strip()[:96]


def self_times(events: Events) -> Dict[str, float]:
    """Seconds by name, each event less the events nested inside it (a
    ``while`` on the ops line spans the ops of its body)."""
    totals: Dict[str, float] = {}
    stack: List[List[Any]] = []  # [name, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            totals[name] = totals.get(name, 0.0) + max(own, 0.0) / 1e9

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return totals


def _as_intervals(events: Events, names: Optional[Sequence[str]] = None) -> np.ndarray:
    rows = [(s, s + d) for n, s, d in events if names is None or n in names]
    return np.asarray(rows, np.float64).reshape(-1, 2)


def reduce(planes: Planes, *, sync_mono_ns: float, window_mono_ns: Tuple[float, float], env_steps_mono_ns: np.ndarray,
           chips: int = 1) -> Dict[str, Any]:  # fmt: skip
    """Busy seconds (mean over the chips used), the traced window's length,
    and the breakdown. ``window_mono_ns`` and ``env_steps_mono_ns`` (``[n,
    2]``) are on the host's monotonic clock, as is ``sync_mono_ns``, the time
    read inside the ``perfbench/sync`` annotation."""
    host_events: Events = [e for lines in planes.values() for events in lines.values() for e in events
                           if e[0] == SYNC or e[0] in HOST_SPANS]  # fmt: skip
    syncs = [e for e in host_events if e[0] == SYNC]
    if not syncs:
        return {}
    shift = syncs[0][1] + syncs[0][2] / 2.0 - sync_mono_ns  # monotonic -> trace
    lo, hi = window_mono_ns[0] + shift, window_mono_ns[1] + shift
    device_planes = sorted(p for p in planes if p.startswith(DEVICE_PLANE))[:chips]
    if not device_planes or hi <= lo:
        return {"window_s": max(hi - lo, 0.0) / 1e9}
    busy, ops, covers = [], {}, []
    for plane in device_planes:
        events = [e for e in planes[plane].get(OPS_LINE, []) if e[1] + e[2] > lo and e[1] < hi]
        cover = clip(union(_as_intervals(events)), lo, hi)
        covers.append(cover)
        busy.append(float((cover[:, 1] - cover[:, 0]).sum()) / 1e9)
        for name, seconds in self_times(events).items():
            ops[short_name(name)] = ops.get(short_name(name), 0.0) + seconds / len(device_planes)
    cover = covers[0]
    edges = np.concatenate([[lo], cover.reshape(-1), [hi]]).reshape(-1, 2)
    gaps = edges[edges[:, 1] > edges[:, 0]]
    env = clip(union(env_steps_mono_ns.astype(np.float64) + shift), lo, hi)
    spans = {name: clip(union(_as_intervals(host_events, (name,))), lo, hi) for name in HOST_SPANS}
    idle_total = float((gaps[:, 1] - gaps[:, 0]).sum())
    in_env = overlap(gaps, env)
    in_train = overlap(gaps, spans["Time/train_time"])
    in_interaction = max(overlap(gaps, spans["Time/env_interaction_time"]) - in_env, 0.0)
    idle = {
        "env.step": in_env,
        "Time/train_time": in_train,
        "Time/env_interaction_time (less env.step)": in_interaction,
        "agent_loop": max(idle_total - in_env - in_train - in_interaction, 0.0),
    }
    return {
        "busy_s": float(np.mean(busy)),
        "window_s": (hi - lo) / 1e9,
        "longest_gap_ms": float((gaps[:, 1] - gaps[:, 0]).max() / 1e6) if len(gaps) else 0.0,
        "breakdown": {
            "device_ops": [[n, s] for n, s in sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": [[n, s / 1e9] for n, s in sorted(idle.items(), key=lambda kv: -kv[1]) if s > 0.0][:TOP],
        },
    }


def reduce_dir(trace_dir: str, run: Any) -> Dict[str, Any]:
    """The reduction of a finished run's trace directory."""
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found or run.watcher.sync is None or run.stretch_ns is None:
        return {}
    return reduce_run(load(found[-1]), run)


def reduce_run(planes: Planes, run: Any) -> Dict[str, Any]:
    """``reduce`` of a finished run's trace over its traced stretch
    (``run.stretch_ns``: ``run.trace_stretch``)."""
    sync = (run.watcher.sync["before_ns"] + run.watcher.sync["inside_ns"]) / 2.0
    window = (float(run.stretch_ns[0]), float(run.stretch_ns[1]))
    steps = np.stack([run.entry_ns, run.exit_ns], 1)
    keep = os.environ.get("PERFBENCH_KEEP_TRACE")
    if keep:
        record(planes, keep, sync, window[1], steps)
    return reduce(planes, sync_mono_ns=sync, window_mono_ns=window, env_steps_mono_ns=steps, chips=run.cell.chips)


def record(planes: Planes, path: str, sync_mono_ns: float, end_mono_ns: float, steps_mono_ns: np.ndarray, ms: float = 60.0) -> None:
    """The last ``ms`` of a trace in the neutral form, with what ``reduce``
    makes of that cut beside it: the recorded trace of the tests."""
    at = [e for lines in planes.values() for events in lines.values() for e in events if e[0] == SYNC][0]
    shift = at[1] + at[2] / 2.0 - sync_mono_ns
    cut = (end_mono_ns - ms * 1e6, end_mono_ns)
    save(planes, path, keep=(cut[0] + shift, cut[1] + shift), lines=(OPS_LINE,))
    steps = steps_mono_ns[(steps_mono_ns[:, 1] >= cut[0]) & (steps_mono_ns[:, 0] <= cut[1])]
    reduced = reduce(load(path), sync_mono_ns=sync_mono_ns, window_mono_ns=cut, env_steps_mono_ns=steps)
    expect = {
        "sync_mono_ns": sync_mono_ns,
        "window_mono_ns": list(cut),
        "env_steps_mono_ns": steps.tolist(),
        "busy_s": reduced["busy_s"],
        "window_s": reduced["window_s"],
        "top_ops": [n for n, _ in reduced["breakdown"]["device_ops"]],
        "idle_gaps": reduced["breakdown"]["idle_gaps"],
    }
    with open(path.replace(".json.gz", "_expect.json"), "w") as f:
        json.dump(expect, f)
