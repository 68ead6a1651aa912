"""Trace collector/merger: join per-process trace streams into causal timelines.

The emitting side lives in :mod:`sheeprl_tpu.obs.trace`: every process of a
run (learner, actor children, the serve CLI) writes ``trace_handshake`` and
``trace`` records into its own JSONL stream — the learner/serve processes
ride their ``telemetry.jsonl`` (buffered, rotated to ``.1``), actor children
write standalone flush-per-event ``trace.actor<i>.jsonl`` files. The run's
full file set is recorded in its RUNS.jsonl record (``telemetry_files``), so
no globbing is needed to find them.

This module is the read side, pure stdlib (``tools/report.py --trace``
loads it by file path):

- **clock alignment** — each stream's handshake carries ``clock_offset =
  time.time() - time.monotonic()`` measured in the emitting process. Events
  are ordered by ``t_mono + clock_offset`` (the monotonic clock is steady;
  the epoch clock can step mid-run), falling back to the raw epoch ``t``
  stamp for events with no aligned handshake.
- **merge** — :func:`merge` reads every stream (rotated ``.1`` segments
  oldest-first), groups ``trace`` events by ``trace_id`` into end-to-end
  timelines, and expands batched carriers (a ``request_reroute`` names its
  victims in a ``trace_ids`` list) into per-trace events. ``trace_id == 0``
  events are process-scoped and land on the ``untraced`` timeline.
- **critical-path attribution** — :func:`summarize` decomposes each slab's
  lag (collect → ring-wait → admission → train) and each request's latency
  (queue-wait → batch-assembly → compute), classifies terminals (trained /
  torn / dropped-stale, done / expired / blackholed) and dedupes hedged
  requests (the ``request_done`` replica is the winner; routed losers are
  listed, never double-counted).
- **Perfetto export** — :func:`perfetto` writes the merged timelines as a
  Chrome/Perfetto trace-event JSON: one track per process (role + pid),
  duration slices for the measured phases, instants for the rest.

CLI::

    python -m tools.trace merge    <stream.jsonl ...> [--out merged.json]
    python -m tools.trace summary  <stream.jsonl ...>
    python -m tools.trace perfetto <stream.jsonl ...> --out trace.json
    python -m tools.trace --self-test

``--from-registry RUNS.jsonl`` replaces explicit paths with the newest
registry record's ``telemetry_files`` set.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

# ---------------------------------------------------------------- clocks ----


def mono_to_epoch(t_mono: float, clock_offset: float) -> float:
    """Align one process's monotonic stamp onto the shared epoch timeline."""
    return float(t_mono) + float(clock_offset)


def epoch_to_mono(t: float, clock_offset: float) -> float:
    return float(t) - float(clock_offset)


# ---------------------------------------------------------------- reading ----


def segments(path: str) -> List[str]:
    """The stream's on-disk segments, oldest first (``.1`` before current) —
    the same rotation contract as ``TelemetryWriter.segments``."""
    return [p for p in (path + ".1", path) if os.path.exists(p)]


def read_events(path: str) -> List[Dict[str, Any]]:
    """Parse one JSONL file; a torn final line (process killed mid-write) is
    dropped, not fatal."""
    events: List[Dict[str, Any]] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict):
                events.append(rec)
    return events


def expand_stream_paths(paths: Sequence[str]) -> List[str]:
    """Resolve each base path to its rotated segment set, oldest first,
    deduplicated (a caller may pass both ``telemetry.jsonl`` and its ``.1``)."""
    out: List[str] = []
    seen = set()
    for path in paths:
        segs = [path] if path.endswith(".1") else (segments(path) or [path])
        for seg in segs:
            key = os.path.abspath(seg)
            if key not in seen:
                seen.add(key)
                out.append(seg)
    return out


def registry_stream_paths(runs_path: str) -> List[str]:
    """The newest RUNS.jsonl record's declared per-process file set
    (``telemetry_files``: own segments oldest-first + child trace files)."""
    newest: Optional[Dict[str, Any]] = None
    for rec in read_events(runs_path):
        if rec.get("telemetry_files"):
            newest = rec
    if newest is None:
        raise SystemExit(
            f"no record in {runs_path} declares telemetry_files "
            "(runs registered before the trace plane, or telemetry disabled)"
        )
    return [str(p) for p in newest["telemetry_files"]]


# ---------------------------------------------------------------- merging ----

_CARRIER_FIELDS = ("event", "kind", "trace_id", "trace_ids", "t", "t_mono", "step", "process_index")


def _normalize(raw: Dict[str, Any], stream: str, role: str, pid: Any, offset: Optional[float], t: float) -> Dict[str, Any]:
    ev = {
        "t": t,
        "kind": raw.get("kind", "?"),
        "role": raw.get("role", role),
        "pid": raw.get("pid", pid),
        "stream": stream,
    }
    for k, v in raw.items():
        if k not in _CARRIER_FIELDS and k not in ("role", "pid"):
            ev[k] = v
    return ev


def _wall_skew_corrections(
    observations: Dict[Tuple[str, str], List[float]], root_order: Sequence[str]
) -> Dict[str, float]:
    """Per-role epoch-clock corrections from transport-handshake skew
    observations.

    ``observations[(a, b)]`` holds ``skew_s = a_wall - b_wall`` samples
    measured when role ``a`` received role ``b``'s HELLO/ACK (carrying ``b``'s
    ``t_wall`` stamp), so an event stamped ``t`` on ``b``'s clock happened at
    ``t + skew_s`` on ``a``'s. Corrections are additive along a BFS from the
    first present root in ``root_order`` (every connected component gets its
    own root; the per-edge skew is the sample median, since one-way latency
    inflates individual samples). Roles with no observations stay at 0.0."""
    import statistics

    adj: Dict[str, List[Tuple[str, float]]] = {}
    for (a, b), vals in observations.items():
        if a == b or not vals:
            continue
        s = float(statistics.median(vals))
        adj.setdefault(a, []).append((b, s))  # correction(b) = correction(a) + s
        adj.setdefault(b, []).append((a, -s))
    corrections: Dict[str, float] = {}
    roots = [r for r in root_order if r in adj] + sorted(adj)
    for root in roots:
        if root in corrections:
            continue
        corrections[root] = 0.0
        queue = [root]
        while queue:
            a = queue.pop(0)
            for b, s in adj.get(a, ()):
                if b not in corrections:
                    corrections[b] = corrections[a] + s
                    queue.append(b)
    return corrections


def merge_streams(streams: Sequence[Tuple[str, Sequence[Dict[str, Any]]]]) -> Dict[str, Any]:
    """Join named per-process event streams into one causal view.

    Returns ``{"processes": [...], "traces": {trace_id: [events]}, "untraced":
    [events], "clock_skews": {role: skew_s}}`` with every event list sorted by
    the aligned epoch time. Alignment is two-level: within a process,
    ``t_mono + clock_offset`` (steady against epoch-clock steps); across
    processes, ``net_handshake`` skew observations from the TCP transports
    (each handshake carries the sender's wall stamp, so the receiver logs
    ``skew_s = my_wall - peer_wall``) shift every peer stream onto the
    learner/serve host's timeline — without this, a cross-host slab or
    request chain decomposes against unrelated clocks."""
    processes: List[Dict[str, Any]] = []
    pending: List[Tuple[str, List[Tuple[Dict[str, Any], Any, int]]]] = []
    skew_obs: Dict[Tuple[str, str], List[float]] = {}
    first_role: Optional[str] = None

    for stream, events in streams:
        offset: Optional[float] = None
        role, pid = "proc", None
        proc_rec: Optional[Dict[str, Any]] = None
        count = 0
        stream_events: List[Tuple[Dict[str, Any], Any, int]] = []
        for raw in events:
            etype = raw.get("event")
            if etype == "trace_handshake":
                role = str(raw.get("role", role))
                pid = raw.get("pid", pid)
                if raw.get("clock_offset") is not None:
                    offset = float(raw["clock_offset"])
                if proc_rec is None:
                    proc_rec = {"stream": stream, "role": role, "pid": pid, "clock_offset": offset}
                    processes.append(proc_rec)
                else:  # re-handshake (role rename): the newest wins
                    proc_rec.update(role=role, pid=pid, clock_offset=offset)
                continue
            if etype != "trace":
                continue
            count += 1
            t_mono = raw.get("t_mono")
            if t_mono is not None and offset is not None:
                t = mono_to_epoch(t_mono, offset)
            else:
                t = float(raw.get("t", 0.0))
            ev = _normalize(raw, stream, role, pid, offset, t)
            if (
                ev.get("kind") == "net_handshake"
                and ev.get("peer") is not None
                and isinstance(ev.get("skew_s"), (int, float))
            ):
                skew_obs.setdefault((str(ev["role"]), str(ev["peer"])), []).append(float(ev["skew_s"]))
            tids = raw.get("trace_ids")
            tid = int(raw.get("trace_id", 0) or 0)
            stream_events.append((ev, tids, tid))
        if proc_rec is not None:
            proc_rec["trace_events"] = count
        elif events:
            # a stream with events but no handshake still shows up, flagged
            proc_rec = {"stream": stream, "role": role, "pid": pid, "clock_offset": None, "trace_events": count}
            processes.append(proc_rec)
        stream_role = str(proc_rec["role"]) if proc_rec else role
        if first_role is None and stream_events:
            first_role = stream_role
        pending.append((stream_role, stream_events))

    root_order = ["learner", "serve", "fleet"] + ([first_role] if first_role else [])
    corrections = _wall_skew_corrections(skew_obs, root_order)
    for proc_rec in processes:
        skew = corrections.get(str(proc_rec.get("role")))
        if skew:
            proc_rec["wall_skew_s"] = skew

    traces: Dict[int, List[Dict[str, Any]]] = {}
    untraced: List[Dict[str, Any]] = []
    for stream_role, stream_events in pending:
        correction = corrections.get(stream_role, 0.0)
        for ev, tids, tid in stream_events:
            if correction:
                ev["t"] = ev["t"] + correction
            if tids:  # batched carrier (request_reroute): one event per victim
                for one in tids:
                    traces.setdefault(int(one), []).append(dict(ev))
                continue
            if tid:
                traces.setdefault(tid, []).append(ev)
            else:
                untraced.append(ev)

    for evs in traces.values():
        evs.sort(key=lambda e: e["t"])
    untraced.sort(key=lambda e: e["t"])
    return {
        "processes": processes,
        "traces": traces,
        "untraced": untraced,
        "clock_skews": {k: v for k, v in corrections.items() if v},
    }


def merge(paths: Sequence[str]) -> Dict[str, Any]:
    """Read + join the given streams (rotated segments handled, missing
    files skipped with a note in ``missing``)."""
    streams: List[Tuple[str, List[Dict[str, Any]]]] = []
    missing: List[str] = []
    for seg in expand_stream_paths(paths):
        if not os.path.exists(seg):
            missing.append(seg)
            continue
        streams.append((seg, read_events(seg)))
    merged = merge_streams(streams)
    if missing:
        merged["missing"] = missing
    return merged


# ----------------------------------------------------------- attribution ----


def _pct(sorted_values: List[float], q: float) -> Optional[float]:
    """Nearest-rank percentile over an already-sorted list (q in [0, 1])."""
    if not sorted_values:
        return None
    idx = min(len(sorted_values) - 1, int(round(q * (len(sorted_values) - 1))))
    return float(sorted_values[idx])


def _pct_block(values: List[float]) -> Dict[str, float]:
    values = sorted(values)
    return {"p50": round(_pct(values, 0.50), 3), "p95": round(_pct(values, 0.95), 3)}


_SLAB_KINDS = {"slab_collect", "slab_commit", "slab_admit", "slab_train", "slab_drop_stale", "torn"}
_REQUEST_KINDS = {
    "request_admit",
    "request_route",
    "request_hedge",
    "request_hedge_drop",
    "request_reroute",
    "request_blackholed",
    "request_expired",
    "request_done",
}


def trace_kinds(events: Iterable[Dict[str, Any]]) -> List[str]:
    return [e["kind"] for e in events]


def slab_terminal(events: Sequence[Dict[str, Any]]) -> str:
    kinds = set(trace_kinds(events))
    for terminal in ("torn", "slab_drop_stale", "slab_train"):
        if terminal in kinds:
            return terminal
    return "dangling"


def request_terminal(events: Sequence[Dict[str, Any]]) -> str:
    kinds = set(trace_kinds(events))
    for terminal in ("request_done", "request_expired", "request_blackholed"):
        if terminal in kinds:
            return terminal
    return "dangling"


def summarize(merged: Dict[str, Any]) -> Dict[str, Any]:
    """Critical-path attribution over a merged view: the per-slab lag
    decomposition, the per-request latency decomposition, terminal counts
    and hedge dedup (winner replica vs routed losers)."""
    traces = merged.get("traces", {})
    out: Dict[str, Any] = {
        "processes": [
            {k: p.get(k) for k in ("stream", "role", "pid", "trace_events")}
            for p in merged.get("processes", [])
        ],
        "traces": len(traces),
    }
    if merged.get("clock_skews"):
        out["clock_skews"] = dict(merged["clock_skews"])

    # -- slabs: collect -> ring-wait -> admission -> train ------------------
    slab_traces = {
        tid: evs for tid, evs in traces.items() if any(e["kind"] in _SLAB_KINDS for e in evs)
    }
    terminals: Dict[str, int] = {}
    complete = 0
    ages, collects, ring_waits, trains = [], [], [], []
    for evs in slab_traces.values():
        term = slab_terminal(evs)
        terminals[term] = terminals.get(term, 0) + 1
        kinds = set(trace_kinds(evs))
        if {"slab_collect", "slab_admit", "slab_train"} <= kinds:
            complete += 1
        if term != "slab_train":
            continue
        by_kind = {e["kind"]: e for e in evs}
        collect_us = float(by_kind.get("slab_collect", {}).get("collect_us", 0) or 0)
        ring_wait_us = float(by_kind.get("slab_admit", {}).get("ring_wait_us", 0) or 0)
        train_us = float(by_kind.get("slab_train", {}).get("train_us", 0) or 0)
        collects.append(collect_us / 1e3)
        ring_waits.append(ring_wait_us / 1e3)
        trains.append(train_us / 1e3)
        ages.append((collect_us + ring_wait_us + train_us) / 1e3)
    slabs: Dict[str, Any] = {
        "traces": len(slab_traces),
        "complete_chains": complete,
        "terminals": terminals,
    }
    if ages:
        slabs["age_ms"] = _pct_block(ages)
        slabs["collect_ms"] = _pct_block(collects)
        slabs["ring_wait_ms"] = _pct_block(ring_waits)
        slabs["train_ms"] = _pct_block(trains)
    out["slabs"] = slabs

    # -- requests: queue-wait -> assembly -> compute (+ hedge dedup) --------
    req_traces = {
        tid: evs for tid, evs in traces.items() if any(e["kind"] in _REQUEST_KINDS for e in evs)
    }
    req_terminals: Dict[str, int] = {}
    totals, queues, assemblies, computes = [], [], [], []
    hedged = rerouted = hedge_drops = hedge_winner_dupes = 0
    for evs in req_traces.values():
        term = request_terminal(evs)
        req_terminals[term] = req_terminals.get(term, 0) + 1
        kinds = trace_kinds(evs)
        was_hedged = "request_hedge" in kinds
        if was_hedged:
            hedged += 1
        if "request_reroute" in kinds:
            rerouted += 1
        hedge_drops += kinds.count("request_hedge_drop")
        dones = [e for e in evs if e["kind"] == "request_done"]
        if len(dones) > 1:
            # first-completion-wins: a correct run delivers exactly once —
            # anything past the first is a dedup violation, surfaced loudly
            hedge_winner_dupes += len(dones) - 1
        if not dones:
            continue
        done = dones[0]
        q = float(done.get("queue_wait_ms", 0) or 0)
        a = float(done.get("assembly_ms", 0) or 0)
        c = float(done.get("compute_ms", 0) or 0)
        queues.append(q)
        assemblies.append(a)
        computes.append(c)
        totals.append(q + a + c)
        if was_hedged:
            winner = done.get("replica")
            losers = sorted(
                {
                    e.get("replica")
                    for e in evs
                    if e["kind"] == "request_route" and e.get("replica") != winner
                }
            )
            done["hedge_winner"], done["hedge_losers"] = winner, losers
    requests: Dict[str, Any] = {
        "traces": len(req_traces),
        "terminals": req_terminals,
        "hedged": hedged,
        "hedge_drops": hedge_drops,
        "rerouted": rerouted,
    }
    if hedge_winner_dupes:
        requests["hedge_winner_dupes"] = hedge_winner_dupes
    if totals:
        requests["total_ms"] = _pct_block(totals)
        requests["queue_wait_ms"] = _pct_block(queues)
        requests["assembly_ms"] = _pct_block(assemblies)
        requests["compute_ms"] = _pct_block(computes)
    out["requests"] = requests
    return out


# ----------------------------------------------------------- perfetto -------

# measured-duration phases: kind -> (duration field, unit divisor to µs, name)
_SPAN_FIELDS = {
    "slab_collect": (("collect_us", 1.0),),
    "slab_admit": (("ring_wait_us", 1.0),),
    "slab_train": (("train_us", 1.0),),
    "request_done": (("queue_wait_ms", 1e3), ("assembly_ms", 1e3), ("compute_ms", 1e3)),
}


def perfetto(merged: Dict[str, Any], out_path: str) -> int:
    """Write the merged view as Chrome/Perfetto trace-event JSON: one track
    (pid) per process, ``X`` duration slices for the measured phases
    (ending at the event's aligned stamp), ``i`` instants for everything
    else. Returns the number of trace events written."""
    trace_events: List[Dict[str, Any]] = []
    pids = {}
    for proc in merged.get("processes", []):
        pid = proc.get("pid") or (1000 + len(pids))
        pids[(proc.get("role"), proc.get("pid"))] = pid
        trace_events.append(
            {
                "name": "process_name",
                "ph": "M",
                "pid": pid,
                "args": {"name": f"{proc.get('role', 'proc')} (pid {proc.get('pid')})"},
            }
        )

    def track(ev: Dict[str, Any]) -> int:
        return pids.get((ev.get("role"), ev.get("pid")), ev.get("pid") or 0)

    def add(ev: Dict[str, Any], tid_label: Any) -> None:
        ts_us = ev["t"] * 1e6
        spans = _SPAN_FIELDS.get(ev["kind"], ())
        args = {k: v for k, v in ev.items() if k not in ("t", "stream")}
        args["trace"] = str(tid_label)
        emitted_span = False
        # phases stack back from the event stamp: [... queue | assembly |
        # compute ]<- t  (each slice ends where the next begins)
        end = ts_us
        for field, to_us in reversed(spans):
            dur = float(ev.get(field, 0) or 0) * to_us
            if dur <= 0:
                continue
            trace_events.append(
                {
                    "name": f"{ev['kind']}:{field.rsplit('_', 1)[0]}" if len(spans) > 1 else ev["kind"],
                    "ph": "X",
                    "ts": end - dur,
                    "dur": dur,
                    "pid": track(ev),
                    "tid": 1,
                    "args": args,
                }
            )
            end -= dur
            emitted_span = True
        if not emitted_span:
            trace_events.append(
                {
                    "name": ev["kind"],
                    "ph": "i",
                    "ts": ts_us,
                    "pid": track(ev),
                    "tid": 1,
                    "s": "p",
                    "args": args,
                }
            )

    for tid, evs in merged.get("traces", {}).items():
        for ev in evs:
            add(ev, tid)
    for ev in merged.get("untraced", []):
        add(ev, 0)

    doc = {"traceEvents": trace_events, "displayTimeUnit": "ms"}
    with open(out_path, "w") as f:
        json.dump(doc, f)
    return len(trace_events)


# ----------------------------------------------------------- self-test ------


def _hs(role: str, pid: int, offset: float, t_mono: float) -> Dict[str, Any]:
    return {
        "event": "trace_handshake",
        "role": role,
        "pid": pid,
        "clock_offset": offset,
        "t": t_mono + offset,
        "t_mono": t_mono,
    }


def _ev(kind: str, tid: int, role: str, pid: int, t_mono: float, offset: float, **fields: Any) -> Dict[str, Any]:
    return {
        "event": "trace",
        "kind": kind,
        "trace_id": tid,
        "role": role,
        "pid": pid,
        "t": t_mono + offset,
        "t_mono": t_mono,
        **fields,
    }


def self_test() -> int:
    """Inline fixtures covering the merger's contracts; returns 0 on pass."""
    failures: List[str] = []

    def check(name: str, cond: bool) -> None:
        if not cond:
            failures.append(name)

    # 1. clock offset round-trip
    off = 1.7e9
    check("clock_round_trip", abs(epoch_to_mono(mono_to_epoch(12.5, off), off) - 12.5) < 1e-9)

    # 2. skewed-clock merge ordering: actor's epoch clock stepped +100s after
    # its handshake, so raw `t` orders its event AFTER the learner's — the
    # aligned t_mono + offset order must win
    tid = 42
    actor = [
        _hs("actor0", 100, 1000.0, 1.0),
        {**_ev("slab_collect", tid, "actor0", 100, 2.0, 1000.0), "t": 2.0 + 1000.0 + 100.0},
    ]
    learner = [
        _hs("learner", 101, 1000.0, 1.0),
        _ev("slab_admit", tid, "learner", 101, 5.0, 1000.0),
    ]
    merged = merge_streams([("actor0.jsonl", actor), ("learner.jsonl", learner)])
    evs = merged["traces"][tid]
    check("skewed_clock_order", trace_kinds(evs) == ["slab_collect", "slab_admit"])
    check("skewed_clock_alignment", abs(evs[0]["t"] - 1002.0) < 1e-6)

    # 2b. cross-HOST wall skew: the remote actor's whole epoch timeline runs
    # +100s ahead (its clock_offset includes the skew — offsets only fix
    # same-host epoch steps), so only the learner's net_handshake skew
    # observation can pull its events back onto the learner's timeline
    tid = 43
    remote = [
        _hs("actor0", 110, 1100.0, 1.0),
        _ev("slab_collect", tid, "actor0", 110, 2.0, 1100.0),
    ]
    learner = [
        _hs("learner", 111, 1000.0, 1.0),
        _ev("net_handshake", 0, "learner", 111, 1.5, 1000.0, peer="actor0", skew_s=-100.0, transport="tcp"),
        _ev("slab_admit", tid, "learner", 111, 5.0, 1000.0),
    ]
    merged = merge_streams([("remote.jsonl", remote), ("learner.jsonl", learner)])
    evs = merged["traces"][tid]
    check("wall_skew_order", trace_kinds(evs) == ["slab_collect", "slab_admit"])
    check("wall_skew_alignment", abs(evs[0]["t"] - 1002.0) < 1e-6)
    check("wall_skew_reported", abs(merged["clock_skews"].get("actor0", 0.0) + 100.0) < 1e-6)
    check(
        "wall_skew_on_process",
        any(abs(p.get("wall_skew_s", 0.0) + 100.0) < 1e-6 for p in merged["processes"] if p["role"] == "actor0"),
    )

    # 3. cross-process join: 2 actors + learner, one full chain per slab
    t1, t2 = 7, 8
    a0 = [
        _hs("actor0", 200, 50.0, 1.0),
        _ev("slab_collect", t1, "actor0", 200, 1.0, 50.0, collect_us=4000),
        _ev("slab_commit", t1, "actor0", 200, 1.2, 50.0),
    ]
    a1 = [
        _hs("actor1", 201, 60.0, 1.0),
        _ev("slab_collect", t2, "actor1", 201, 1.1, 60.0, collect_us=5000),
        _ev("slab_commit", t2, "actor1", 201, 1.3, 60.0),
    ]
    lrn = [
        _hs("learner", 202, 55.0, 1.0),
        _ev("slab_admit", t1, "learner", 202, 1.5, 55.0, ring_wait_us=2000),
        _ev("slab_train", t1, "learner", 202, 1.9, 55.0, train_us=3000),
        _ev("slab_admit", t2, "learner", 202, 2.0, 55.0, ring_wait_us=2500),
        _ev("slab_train", t2, "learner", 202, 2.4, 55.0, train_us=3500),
    ]
    merged = merge_streams([("a0", a0), ("a1", a1), ("lrn", lrn)])
    summary = summarize(merged)
    check("join_traces", summary["slabs"]["traces"] == 2)
    check("join_complete_chains", summary["slabs"]["complete_chains"] == 2)
    check("join_terminals", summary["slabs"]["terminals"] == {"slab_train": 2})
    check(
        "join_chain_order",
        trace_kinds(merged["traces"][t1])
        == ["slab_collect", "slab_commit", "slab_admit", "slab_train"],
    )
    check("join_age", summary["slabs"]["age_ms"]["p50"] in (9.0, 11.0))

    # 4. hedged-request dedup: first completion wins, the loser is marked
    rid = 9
    serve = [
        _hs("serve", 300, 10.0, 1.0),
        _ev("request_admit", rid, "serve", 300, 1.0, 10.0),
        _ev("request_route", rid, "serve", 300, 1.01, 10.0, replica=0),
        _ev("request_hedge", rid, "serve", 300, 1.05, 10.0, replica=1),
        _ev("request_route", rid, "serve", 300, 1.05, 10.0, replica=1),
        _ev(
            "request_done", rid, "serve", 300, 1.09, 10.0,
            replica=1, queue_wait_ms=80.0, assembly_ms=1.0, compute_ms=9.0,
        ),
        _ev("request_hedge_drop", rid, "serve", 300, 1.10, 10.0),
    ]
    merged = merge_streams([("serve", serve)])
    summary = summarize(merged)
    req = summary["requests"]
    check("hedge_one_trace", summary["traces"] == 1)
    check("hedge_terminal", req["terminals"] == {"request_done": 1})
    check("hedge_counted", req["hedged"] == 1 and req["hedge_drops"] == 1)
    check("hedge_no_dupes", "hedge_winner_dupes" not in req)
    done = [e for e in merged["traces"][rid] if e["kind"] == "request_done"][0]
    check("hedge_winner", done.get("hedge_winner") == 1 and done.get("hedge_losers") == [0])
    check("hedge_decomposition", req["total_ms"]["p50"] == 90.0)

    # 5. torn slab terminates at `torn`, never `trained`; reroute carrier
    # expansion files the event on every victim's trace
    t3, t4 = 11, 12
    a = [
        _hs("actor0", 400, 5.0, 1.0),
        _ev("slab_collect", t3, "actor0", 400, 1.0, 5.0, collect_us=1000),
    ]
    l = [
        _hs("learner", 401, 5.0, 1.0),
        _ev("torn", t3, "learner", 401, 2.0, 5.0, source="ring"),
        {
            **_ev("request_reroute", 0, "learner", 401, 3.0, 5.0, replica=2, reason="dead"),
            "trace_ids": [t4],
        },
    ]
    merged = merge_streams([("a", a), ("l", l)])
    summary = summarize(merged)
    check("torn_terminal", slab_terminal(merged["traces"][t3]) == "torn")
    check("torn_not_trained", summary["slabs"]["terminals"] == {"torn": 1})
    check("torn_keeps_actor_span", trace_kinds(merged["traces"][t3]) == ["slab_collect", "torn"])
    check("reroute_expanded", trace_kinds(merged["traces"][t4]) == ["request_reroute"])

    # perfetto smoke: the export writes loadable JSON with per-process tracks
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        out = os.path.join(d, "trace.json")
        n = perfetto(merged, out)
        with open(out) as f:
            doc = json.load(f)
        names = {e["args"]["name"] for e in doc["traceEvents"] if e["ph"] == "M"}
        check("perfetto_events", n == len(doc["traceEvents"]) and n > 0)
        check("perfetto_tracks", names == {"actor0 (pid 400)", "learner (pid 401)"})

    if failures:
        print(f"trace --self-test FAILED: {', '.join(failures)}", file=sys.stderr)
        return 1
    print("trace --self-test: ok (5 fixtures)")
    return 0


# ----------------------------------------------------------------- CLI ------


def _encode_merged(merged: Dict[str, Any]) -> Dict[str, Any]:
    doc = dict(merged)
    doc["traces"] = {str(tid): evs for tid, evs in merged.get("traces", {}).items()}
    return doc


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="tools/trace.py", description="merge per-process trace streams into causal timelines"
    )
    parser.add_argument("--self-test", action="store_true", help="run the inline merger fixtures and exit")
    sub = parser.add_subparsers(dest="cmd")
    for name, help_ in (
        ("merge", "join streams by trace id; print (or --out) the merged JSON"),
        ("summary", "critical-path attribution: slab lag + request latency decompositions"),
        ("perfetto", "export the merged timelines as a Perfetto-loadable trace (--out)"),
    ):
        p = sub.add_parser(name, help=help_)
        p.add_argument("paths", nargs="*", help="trace/telemetry JSONL streams (rotated .1 segments auto-included)")
        p.add_argument("--from-registry", metavar="RUNS", help="use the newest RUNS.jsonl record's telemetry_files")
        p.add_argument("--out", help="write to this path instead of stdout" + (" (required)" if name == "perfetto" else ""))
    args = parser.parse_args(argv)

    if args.self_test:
        return self_test()
    if not args.cmd:
        parser.print_help()
        return 2
    paths = list(args.paths)
    if args.from_registry:
        paths += registry_stream_paths(args.from_registry)
    if not paths:
        parser.error(f"{args.cmd}: no streams given (paths or --from-registry)")
    merged = merge(paths)
    if args.cmd == "merge":
        doc = json.dumps(_encode_merged(merged), indent=1)
        if args.out:
            with open(args.out, "w") as f:
                f.write(doc + "\n")
        else:
            print(doc)
    elif args.cmd == "summary":
        print(json.dumps(summarize(merged), indent=1))
    elif args.cmd == "perfetto":
        if not args.out:
            parser.error("perfetto requires --out")
        n = perfetto(merged, args.out)
        print(json.dumps({"out": args.out, "trace_events": n, "processes": len(merged.get("processes", []))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
