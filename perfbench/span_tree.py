"""The program's spans as a tree, and the traced stretch's idle time by the
innermost span that held the host in it.

Every ``span`` event of the program's ``telemetry.jsonl`` carries its start
on the host's monotonic clock (``t_mono_ns``) and its ``dur``; since the spans
name their parent it also carries ``parent``, the innermost span open on the
same thread when it began (``None`` at the top). A span's self time is its
duration less what its children cover (``self_seconds``).

``idle_by_innermost`` puts every idle instant of the device in the traced
stretch (``device_time``'s neutral form of the trace, on the monotonic clock
through the harness's ``perfbench/sync`` annotation) under what the host was
doing then: the innermost span that covers the instant, of every span the run
emitted on any thread, not a fixed list; where only a window span covers it
(``Time/*``), env 0's ``step()`` first and the window span's own name after;
``UNSPANNED`` where nothing does. The spans are taken from the file, not from
the profiler's trace, so a span still open when the profiler stopped counts,
clipped to the stretch. Every reader returns ``None`` where there is nothing
to read (no trace, no sync, a program without monotonic stamps) and raises
nothing.
"""

from __future__ import annotations

import bisect
from typing import Any, Dict, List, NamedTuple, Optional

import numpy as np

from perfbench import device_time
from perfbench.trace_reduce import clip, union

#: names of the two window spans begin with this; they hold the leaves and are no leaf themselves
WINDOW_PREFIX = "Time/"
ENV_STEP = "env.step"
UNSPANNED = "(no span)"


class Span(NamedTuple):
    name: str
    parent: Optional[str]
    start: float  # ns, host monotonic
    end: float


def of_events(events: List[Dict[str, Any]]) -> List[Span]:
    """The ``span`` events that carry a monotonic stamp, by start."""
    found = [Span(e["name"], e.get("parent"), float(e["t_mono_ns"]), float(e["t_mono_ns"]) + float(e["dur"]) * 1e9)
             for e in events if e.get("event") == "span" and e.get("t_mono_ns") is not None]  # fmt: skip
    return sorted(found, key=lambda s: (s.start, -s.end))


def of_run(run: Any) -> List[Span]:
    if "_span_tree" not in run.__dict__:
        run.__dict__["_span_tree"] = of_events(run.telemetry_events)
    return run.__dict__["_span_tree"]


def self_seconds(spans: List[Span]) -> List[float]:
    """Each span's duration less what its children cover. A child is a span
    that names it as ``parent`` and begins while it is open: the one of that
    name begun last before the child."""
    own = [(s.end - s.start) / 1e9 for s in spans]
    starts: Dict[str, List[float]] = {}
    index: Dict[str, List[int]] = {}
    for i, s in enumerate(spans):  # by start already
        starts.setdefault(s.name, []).append(s.start)
        index.setdefault(s.name, []).append(i)
    for s in spans:
        if s.parent is None or s.parent not in starts:
            continue
        k = bisect.bisect_right(starts[s.parent], s.start) - 1
        if k < 0:
            continue
        p = index[s.parent][k]
        if spans[p].end >= s.start:
            own[p] -= (min(s.end, spans[p].end) - s.start) / 1e9
    return own


def _before(cover: np.ndarray, x: np.ndarray) -> np.ndarray:
    """Length of the sorted, disjoint ``cover`` that lies before each ``x``."""
    if len(cover) == 0:
        return np.zeros(len(x))
    lengths = cover[:, 1] - cover[:, 0]
    cum = np.concatenate([[0.0], np.cumsum(lengths)])
    k = np.searchsorted(cover[:, 0], x, side="right") - 1
    inside = np.clip(x - cover[np.maximum(k, 0), 0], 0.0, lengths[np.maximum(k, 0)])
    return np.where(k >= 0, cum[np.maximum(k, 0)] + inside, 0.0)


def _intersect(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j, 1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k, 0] < hi:
            out.append((max(lo, b[k, 0]), min(hi, b[k, 1])))
            k += 1
    return np.asarray(out, np.float64).reshape(-1, 2)


def idle_gaps(run: Any) -> Optional[np.ndarray]:
    """The device's idle intervals in the traced stretch, ``[n, 2]`` on the
    host's monotonic clock; ``None`` where the run has no trace to read."""
    if "_idle_gaps" not in run.__dict__:
        neutral = device_time.neutral_of_run(run)
        gaps = None
        if neutral is not None:
            sync = run.watcher.sync
            shift = neutral["sync"][0] + neutral["sync"][1] / 2.0 - (sync["before_ns"] + sync["inside_ns"]) / 2.0
            lo, hi = float(run.stretch_ns[0]) + shift, float(run.stretch_ns[1]) + shift
            ops = np.asarray([(e[1], e[1] + e[2]) for e in neutral["ops"] if e[1] + e[2] > lo and e[1] < hi], np.float64).reshape(-1, 2)
            edges = np.concatenate([[lo], clip(union(ops), lo, hi).reshape(-1), [hi]]).reshape(-1, 2)
            gaps = edges[edges[:, 1] > edges[:, 0]] - shift
        run.__dict__["_idle_gaps"] = gaps
    return run.__dict__["_idle_gaps"]


def innermost(gaps: np.ndarray, spans: List[Span], env_steps: np.ndarray, stretch: List[float]) -> Dict[str, float]:
    """Seconds of ``gaps`` (idle, sorted and disjoint, inside ``stretch``) by
    what held the host: the innermost span other than a window span that
    covers the instant (the one begun last), else ``ENV_STEP`` where
    ``env_steps`` (``[n, 2]``) cover it, else the innermost window span, else
    ``UNSPANNED``. All on one clock (ns)."""
    lo, hi = float(stretch[0]), float(stretch[1])
    spans = [s for s in spans if s.end > lo and s.start < hi]
    in_env = _intersect(gaps, clip(union(np.asarray(env_steps, np.float64).reshape(-1, 2)), lo, hi))
    points = np.unique(np.clip([lo, hi, *[t for s in spans for t in (s.start, s.end)]], lo, hi))
    idle = np.diff(_before(gaps, points))
    env = np.diff(_before(in_env, points))
    table: Dict[str, float] = {}
    active: List[Span] = []
    nxt = 0
    for i, (a, b) in enumerate(zip(points[:-1], points[1:])):
        active = [s for s in active if s.end > a]
        while nxt < len(spans) and spans[nxt].start <= a:
            if spans[nxt].end > a:
                active.append(spans[nxt])
            nxt += 1
        if idle[i] <= 0.0:
            continue
        leaves = [s for s in active if not s.name.startswith(WINDOW_PREFIX)]
        if leaves:
            who, seconds = max(leaves, key=lambda s: s.start).name, idle[i]
        else:
            windows = [s for s in active if s.name.startswith(WINDOW_PREFIX)]
            table[ENV_STEP] = table.get(ENV_STEP, 0.0) + env[i] / 1e9
            who, seconds = (max(windows, key=lambda s: s.start).name if windows else UNSPANNED), idle[i] - env[i]
        table[who] = table.get(who, 0.0) + seconds / 1e9
    return table


def idle_by_innermost(run: Any) -> Optional[Dict[str, float]]:
    """``innermost`` over a finished traced run: every span it emitted, env
    0's ``step()`` intervals, its traced stretch. ``None`` where the run has
    no trace, or no span with a monotonic stamp."""
    if "_idle_by_innermost" not in run.__dict__:
        gaps, spans = idle_gaps(run), of_run(run)
        table = None
        if gaps is not None and spans:
            table = innermost(gaps, spans, np.stack([run.entry_ns, run.exit_ns], 1), run.stretch_ns)
        run.__dict__["_idle_by_innermost"] = table
    return run.__dict__["_idle_by_innermost"]


def unspanned_seconds(table: Dict[str, float]) -> float:
    """Of such a table, the idle time under no span but a window span, and out of env 0's ``step()``."""
    return table.get(UNSPANNED, 0.0) + sum(v for k, v in table.items() if k.startswith(WINDOW_PREFIX))
