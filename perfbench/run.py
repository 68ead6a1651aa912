"""``python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``

One process: it finds the cell's files by name, calls the program's own entry
(``sheeprl_tpu.cli.run``) with the recipe and times it from the environment's
side, then decides ``correct`` against the plain reference. The last line of
standard output is the result; README.md says what goes where.
"""

from __future__ import annotations

import time

_T0_NS = time.monotonic_ns()  # process start, as near as Python can take it

import argparse  # noqa: E402
import contextlib  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import window  # noqa: E402

#: seconds of device trace, taken at the end of a traced run's window where the cell names no cycle
TRACE_SECONDS = 3.0
#: a run whose window has not opened by then is given up
SETUP_LIMIT_S = 1500.0
PREEMPTED = 77


def say(msg: str) -> None:
    print(msg, file=sys.stdout, flush=True)


class CompileLog:
    """Every trace, lowering and backend compile that JAX reports, with the
    host time at which it ended: the harness's own listener."""

    EVENTS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
        "/jax/core/compile/backend_compile_duration": "backend_compile",
    }

    def __init__(self) -> None:
        self.events: List[Dict[str, Any]] = []
        self.cache_hits = 0
        self.cache_misses = 0
        self._on = False

    def start(self) -> "CompileLog":
        import jax.monitoring as monitoring

        self._on = True
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)
        return self

    def stop(self) -> None:
        self._on = False  # JAX has no public way to take one listener out

    def _duration(self, event: str, seconds: float, **extra: Any) -> None:
        if self._on and event in self.EVENTS:
            self.events.append({"phase": self.EVENTS[event], "end_ns": time.monotonic_ns(), "dur": float(seconds),
                                "name": str(extra.get("fun_name", "?"))})  # fmt: skip

    def _event(self, event: str, **_: Any) -> None:
        if not self._on:
            return
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def last_end_ns(self) -> int:
        return self.events[-1]["end_ns"] if self.events else 0

    def between(self, a_ns: int, b_ns: int) -> List[Dict[str, Any]]:
        return [e for e in self.events if a_ns < e["end_ns"] <= b_ns]

    def seconds(self, until_ns: int) -> float:
        return sum(e["dur"] for e in self.events if e["end_ns"] <= until_ns)


#: how far the longest cycle seen may be outrun by the next two (a cycle cell's trace)
CYCLE_MARGIN = 1.05


def trace_lead(cycle: int) -> int:
    """Vector steps before a rollout's end at which a cycle cell's profiler
    starts (a fifth of a second in the three token cells): the update's first
    train step then runs whole inside the trace."""
    return max(4, cycle // 8)


def starts_trace(exit_ns: Callable[[int], float], open_index: int, cycle: int, end: int, deadline_ns: float) -> bool:
    """Whether a cycle cell traces the cycle that begins at rollout end
    ``end``, judged as vector step ``end - trace_lead(cycle)`` returns
    (``exit_ns(i)``: when step ``i`` returned, known up to that step). That
    end is reckoned by the longest lead seen and the cycle by the longest seen
    (both from the cycle that ends as the window opens), each ``CYCLE_MARGIN``
    times over: the cycle is traced unless the next one also returns by the
    deadline. So the traced cycle returns by the deadline wherever in
    a cycle the deadline falls, as long as no cycle outruns the longest seen
    before it by more than the margin."""
    lead = trace_lead(cycle)
    ends = [j for j in range(open_index - cycle, end, cycle) if j - lead >= 0]
    if len(ends) < 2:
        return True
    longest = max(exit_ns(b) - exit_ns(a) for a, b in zip(ends, ends[1:]))
    lead_ns = max(exit_ns(j) - exit_ns(j - lead) for j in ends)
    return deadline_ns < exit_ns(end - lead) + CYCLE_MARGIN * (lead_ns + 2.0 * longest)


class Watcher(threading.Thread):
    """Opens the window, takes the trace, and closes the window with the
    signal the program treats as a preemption. With ``cycle`` (vector steps a
    rollout) the window opens on a rollout's end, and the trace holds the
    window's last whole cycle: the watcher starts the profiler a little
    before the rollout end that begins it (``traced_from``) and, as the next
    rollout end returns, stops it in a thread of its own (``stopper``):
    collecting a cycle's trace takes tens of seconds, which neither the
    window's end at its deadline nor the comparison after it waits for."""

    def __init__(self, stamps, compiles: CompileLog, *, action_repeat: int, open_after: int, quiet_steps: int,
                 seconds: float, trace_dir: Optional[str], cycle: Optional[int] = None) -> None:  # fmt: skip
        super().__init__(name="perfbench-watcher", daemon=True)
        self.stamps, self.compiles = stamps, compiles
        self.action_repeat, self.open_after, self.quiet_steps = action_repeat, open_after, quiet_steps
        self.seconds, self.trace_dir, self.cycle = seconds, trace_dir, cycle
        self.open_index: Optional[int] = None
        self.deadline_ns: Optional[int] = None
        self.traced_from: Optional[int] = None
        self.tracing = False
        self.stopper: Optional[threading.Thread] = None
        self.stop_ns: Optional[List[int]] = None
        self.sync: Optional[Dict[str, int]] = None
        self.gave_up = False
        self.ended = False
        self.cancel = threading.Event()

    def _exit_ns(self, index: int) -> int:
        return int(self.stamps[2 + 2 * ((index + 1) * self.action_repeat - 1)])

    def _done(self) -> int:
        return int(self.stamps[0]) // self.action_repeat

    def _sleep_until(self, t_ns: int) -> None:
        while not self.cancel.is_set():
            left = (t_ns - time.monotonic_ns()) / 1e9
            if left <= 0:
                return
            time.sleep(min(left, 0.05))

    def _end_window(self) -> None:
        self.ended = True
        os.kill(os.getpid(), signal.SIGTERM)

    def _await_step(self, index: int) -> bool:
        """Until vector step ``index`` has returned, ending the window at its
        deadline meanwhile; ``False`` where the program left first."""
        while not self.cancel.is_set():
            if self._done() > index:
                return True
            if self.deadline_ns is not None and not self.ended and time.monotonic_ns() >= self.deadline_ns:
                self._end_window()
            time.sleep(0.002)
        return False

    def _start_trace(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # Python's own calls are not wanted and cost the loop time
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self.tracing = True
        t_a = time.monotonic_ns()
        with jax.profiler.TraceAnnotation("perfbench/sync"):
            t_b = time.monotonic_ns()
        self.sync = {"before_ns": t_a, "inside_ns": t_b}

    def stop_trace(self) -> None:
        """Stops the profiler once: from ``stopper``, or from the run's end
        where the watcher made none."""
        if self.tracing:
            import jax

            t_stop = time.monotonic_ns()
            jax.profiler.stop_trace()
            self.tracing = False
            self.stop_ns = [t_stop, time.monotonic_ns()]

    def _trace_a_cycle(self) -> None:
        """Starts the profiler ``trace_lead`` vector steps before the rollout
        end that begins the window's last whole cycle (``starts_trace``) and
        stops it behind that cycle's last step."""
        cycle, lead = self.cycle, trace_lead(self.cycle)
        end = self.open_index + cycle
        while True:
            if not self._await_step(end - lead):
                return
            if self._done() - 1 < end and starts_trace(self._exit_ns, self.open_index, cycle, end, self.deadline_ns):
                break
            end += cycle  # not this cycle, or the watcher fell behind its lead
        self._start_trace()
        self.traced_from = end
        if self._await_step(end + cycle):
            self.stopper = threading.Thread(target=self.stop_trace, name="perfbench-trace-stop", daemon=True)
            self.stopper.start()

    def run(self) -> None:
        give_up_ns = _T0_NS + int(SETUP_LIMIT_S * 1e9)
        while not self.cancel.is_set():
            done = self._done()
            # quiet: no compile since the vector step `quiet_steps` back returned
            if done > self.open_after and self.compiles.last_end_ns() < self._exit_ns(done - 1 - self.quiet_steps):
                self.open_index = done - 1 if self.cycle is None else window.rollout_end(done - 1, self.cycle)
                break
            if time.monotonic_ns() > give_up_ns:
                self.gave_up = True
                self._end_window()
                return
            time.sleep(0.002)
        if self.open_index is None or not self._await_step(self.open_index):
            return
        self.deadline_ns = self._exit_ns(self.open_index) + int(self.seconds * 1e9)
        if self.trace_dir is not None and self.cycle is not None:
            self._trace_a_cycle()
        elif self.trace_dir is not None:
            self._sleep_until(self.deadline_ns - int(min(TRACE_SECONDS, self.seconds / 2) * 1e9))
            if self.cancel.is_set():
                return
            self._start_trace()
        self._sleep_until(self.deadline_ns)
        if not self.cancel.is_set() and not self.ended:
            self._end_window()


def _device_line(devices, peak_bytes: Optional[int]) -> Dict[str, Any]:
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak_bytes,
    }


def _peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def _configure_cache(root: str) -> str:
    """The program's own rule (``parallel/fabric.configure_compilation_cache``),
    applied before anything compiles: ``JAX_COMPILATION_CACHE_DIR`` if set,
    else ``<checkout>/.jax_cache``. The checkout's own cache is never evicted
    from: with a size limit in the environment JAX's eviction looks for a
    time stamp beside every entry, and one entry without it (seen once, in
    XL's first run in a fresh directory) makes every later write fail."""
    import jax

    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    path = os.path.join(root, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *, root: str = ROOT, require_tpu: bool = True,
             warm_steps: Optional[int] = None, program_patch=None, verify=None) -> Dict[str, Any]:  # fmt: skip
    """One run of one cell; returns the result line as a dict. ``require_tpu``
    off and ``program_patch`` (a context manager entered around the program's
    run) are for the tests, which drive this same path tiny on the CPU and
    with the timed path broken; ``verify`` takes the algorithm's own place
    for ``calibrate.py``, which reads more than a run compares."""
    from perfbench import env as bench_env, loader

    cell = loader.Cell(workload, root)
    algorithm = loader.algorithm(cell)
    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < cell.chips):
        raise SystemExit(
            f"perfbench: {workload} needs {cell.chips} TPU chip(s); JAX reports {len(devices)} {devices[0].platform!r} device(s)"
        )
    devices = devices[: cell.chips]
    peak = cell.peaks.get(devices[0].device_kind)
    if require_tpu and peak is None:
        raise SystemExit(f"perfbench: no peaks for device kind {devices[0].device_kind!r} in peaks.json")
    cache_dir = _configure_cache(root)
    t_imported_ns = time.monotonic_ns()

    run_dir = os.path.join(root, "logs", "perfbench", workload, f"seed{seed}_trace{int(trace)}")
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    stamp_path = os.path.join(run_dir, "stamps.i64")
    stamps = bench_env.create_stamps(stamp_path)
    program_seed = int(seed) % (2**31 - 1024)
    overrides = cell.overrides(run_dir, stamp_path, program_seed, trace)
    algo = cell.config["algo"]
    num_envs, action_repeat = int(algo["num_envs"]), int(algo["action_repeat"])
    learning_starts = int(algo["learning_starts"]) // num_envs
    cycle = int(algo["rollout_steps"]) if "rollout_steps" in algo else None
    warm = int(cell.workload["warm_steps"] if warm_steps is None else warm_steps)
    say(f"[perfbench] {workload} seed={seed} seconds={seconds} trace={int(trace)} device={devices[0].device_kind} x{len(devices)}")
    say(f"[perfbench] compile cache {cache_dir}; run dir {os.path.relpath(run_dir, root)}")
    say("[perfbench] program command: " + " ".join(overrides))

    compiles = CompileLog().start()
    capture = algorithm.Capture(cell.config, program_seed)
    trace_dir = os.path.join(run_dir, "trace") if trace else None
    watcher = Watcher(stamps, compiles, action_repeat=action_repeat, open_after=learning_starts + warm,
                      quiet_steps=warm, seconds=float(seconds), trace_dir=trace_dir, cycle=cycle)  # fmt: skip
    from sheeprl_tpu.cli import run as program_run

    exit_code: Any = None
    watcher.start()
    try:
        with (program_patch() if program_patch else contextlib.nullcontext()), algorithm.installed(capture):
            program_run(overrides)
    except SystemExit as err:
        exit_code = err.code
    finally:
        t_left_ns = time.monotonic_ns()
        watcher.cancel.set()
        watcher.join()
        if watcher.stopper is None:
            watcher.stop_trace()  # a cycle's trace is being collected by the watcher's stopper, which the comparison need not wait for
        compiles.stop()
    if watcher.gave_up or watcher.open_index is None or exit_code != PREEMPTED:
        raise SystemExit(f"perfbench: the program left with {exit_code!r} and the window "
                         f"{'never opened' if watcher.open_index is None else 'was open'}: no result")  # fmt: skip
    gc.collect()
    peak_bytes = _peak_bytes(devices)

    entry, exit_ = window.vector_steps(bench_env.open_stamps(stamp_path), action_repeat)
    win = window.measure(entry, exit_, watcher.open_index, watcher.deadline_ns, num_envs, cycle)
    setup_s = (win["open_ns"] - _T0_NS) / 1e9
    in_window = compiles.between(win["open_ns"], win["close_ns"])
    first_ns = int(entry[0])
    starts_ns = int(exit_[learning_starts - 1]) if learning_starts >= 1 else first_ns
    say(
        "[perfbench] set-up {:.2f}s = import {:.2f} + build (agent, ring, envs) {:.2f} + prefill {:.2f} + first steps and warm-up {:.2f}; "
        "of it trace+lower+compile {:.2f}s in {} events; persistent cache {} hits, {} misses".format(
            setup_s, (t_imported_ns - _T0_NS) / 1e9, (first_ns - t_imported_ns) / 1e9, (starts_ns - first_ns) / 1e9,
            (win["open_ns"] - starts_ns) / 1e9, compiles.seconds(win["open_ns"]),
            len(compiles.between(0, win["open_ns"])), compiles.cache_hits, compiles.cache_misses,
        )  # fmt: skip
    )
    before = compiles.between(0, win["open_ns"])
    say("[perfbench] longest compile events of set-up: " + ", ".join(
        f"{e['phase']} {e['name']} {e['dur']:.1f}s" for e in sorted(before, key=lambda e: -e["dur"])[:6]))  # fmt: skip
    last_compile_step = int(np.searchsorted(exit_, before[-1]["end_ns"])) if before else -1
    say(f"[perfbench] learning starts at vector step {learning_starts}; the last compile event before the window ended during "
        f"vector step {last_compile_step}; the window opened after step {watcher.open_index} (warm_steps {warm})")  # fmt: skip
    say(
        f"[perfbench] window: vector steps {win['first']}..{win['last']} ({win['vector_steps']} cycles, {win['policy_steps']} policy steps) "
        f"in {win['seconds']:.3f}s; wait p50 {win['env_wait_ms_p50']:.3f} ms p95 {win['env_wait_ms_p95']:.3f} ms, longest {win['longest_waits_ms']}; "
        f"compiles inside {len(in_window)}; train calls {capture.calls}; after the window {(t_left_ns - win['close_ns']) / 1e9:.2f}s to leave"
    )
    if cycle is not None:
        say(f"[perfbench] the deadline fell {(watcher.deadline_ns - win['close_ns']) / 1e9:.3f}s after the last whole cycle's end "
            f"(cycles of {cycle} vector steps: {win['vector_steps'] // cycle} whole, the longest {_longest_cycle_s(exit_, win, cycle):.3f}s)")  # fmt: skip
        if watcher.traced_from is not None:
            lo, hi = trace_stretch(watcher, exit_, win)
            say(f"[perfbench] traced: the cycle from vector step {watcher.traced_from}, {(hi - lo) / 1e9:.3f}s "
                f"(the profiler began {(lo - watcher.sync['inside_ns']) / 1e9:.3f}s before it)")  # fmt: skip
    record = _run_record(os.path.join(run_dir, "RUNS.jsonl"))
    resolved = record.get("resolved") or {}
    say("[perfbench] placement: " + json.dumps(capture.placement, default=str))
    if resolved:
        say("[perfbench] resolved: " + json.dumps({k: (v.get("value") if isinstance(v, dict) else v) for k, v in resolved.items()}, default=str))
    if require_tpu:
        _check_placement(cell, capture.placement)

    t_ref = time.monotonic()
    ok, compared, not_compared = (verify or algorithm.verify)(cell.config, program_seed, capture, cell.workload["limits"], stamp_path)
    say(f"[perfbench] reference and comparison took {time.monotonic() - t_ref:.2f}s")
    say("[perfbench] read and not compared (no limit holds, PERF.md section 2): " + json.dumps(not_compared))

    run = RunFacts(cell=cell, run_dir=run_dir, window=win, setup_s=setup_s, compiles=compiles, watcher=watcher,
                   entry_ns=entry, exit_ns=exit_, peak=peak, peak_bytes=peak_bytes, capture=capture, record=record,
                   stretch_ns=trace_stretch(watcher, exit_, win))  # fmt: skip
    device = _device_line(devices, peak_bytes)
    result: Dict[str, Any] = {"correct": bool(ok), "attempted": win["vector_steps"], "failed": 0}
    if trace:
        from perfbench import trace_reduce

        if watcher.stopper is not None:
            watcher.stopper.join()
        if watcher.stop_ns is not None:
            t_stop, t_stopped = watcher.stop_ns
            say(f"[perfbench] the profiler was stopped {(t_stop - win['close_ns']) / 1e9:.3f}s after the window's last step; collecting "
                f"the trace took {(t_stopped - t_stop) / 1e9:.2f}s and ended {(t_stopped - t_left_ns) / 1e9:.2f}s after the program left")  # fmt: skip
        t_reduce = time.monotonic()
        reduced = trace_reduce.reduce_dir(trace_dir, run)
        run.trace = reduced
        metrics = {}
        readers = loader.layer_readers(cell)
        for spec in cell.per_layer:
            value = readers[spec["name"]](run)
            if value is not None:
                metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
        from perfbench import device_time

        whole = (device_time.of_run(run) or {}).get("train_executions")
        say(f"[perfbench] the trace's reduction and the per-layer readers took {time.monotonic() - t_reduce:.2f}s; "
            f"the traced stretch {reduced.get('window_s')}s holds {whole} whole executions of the train program")  # fmt: skip
        device.update({"busy_s": reduced.get("busy_s"), "window_s": reduced.get("window_s")})
        result.update({"metrics": metrics, "device": device, "breakdown": reduced.get("breakdown", {})})
    else:
        values = {"env_steps_per_s": win["env_steps_per_s"], "env_wait_ms_p95": win["env_wait_ms_p95"], "setup_s": setup_s}
        result.update({"metrics": {m["name"]: {"value": values[m["name"]], "unit": m["unit"]} for m in cell.end_to_end},
                       "device": device})  # fmt: skip
    result["compared"] = {k: {"value": v["value"], "limit": v["limit"]} for k, v in compared.items()}
    for name, v in compared.items():
        print(f"[compared] {name} {v['value']} limit {v['limit']} {'ok' if v['ok'] else 'NOT OK'}", file=sys.stderr, flush=True)
    return result


def _longest_cycle_s(exit_: np.ndarray, win: Dict[str, Any], cycle: int) -> float:
    ends = exit_[win["first"] - 1 : win["last"] + 1 : cycle]
    return float(np.diff(ends).max()) / 1e9


def trace_stretch(watcher: Watcher, exit_ns: np.ndarray, win: Dict[str, Any]) -> Optional[List[float]]:
    """The traced stretch that the trace's readers reduce, on the host's
    monotonic clock: the cycle from the rollout end ``traced_from`` to the next
    one (or to the last step the program took, where it left first), or, in a
    cell that names no cycle, from the profiler's start to the window's last
    vector step. ``None`` where nothing was traced."""
    if watcher.sync is None:
        return None
    if watcher.traced_from is None:
        return [float(watcher.sync["inside_ns"]), float(win["close_ns"])]
    end = min(watcher.traced_from + watcher.cycle, len(exit_ns) - 1)
    return [float(exit_ns[watcher.traced_from]), float(exit_ns[end])]


class RunFacts:
    """What a finished run left for the per-layer readers."""

    trace: Dict[str, Any] = {}

    def __init__(self, **facts: Any) -> None:
        self.__dict__.update(facts)

    @functools.cached_property
    def telemetry_events(self) -> List[Dict[str, Any]]:
        path = self.record.get("telemetry_jsonl")
        if not path or not os.path.exists(path):
            return []
        with open(path) as f:
            return [json.loads(line) for line in f if line.strip()]

    def spans(self, name: str) -> List[Dict[str, Any]]:
        """The program's ``span`` events of ``name`` that lie in the window,
        on the host's monotonic clock (``t0_ns``, ``dur``)."""
        wall_to_mono = time.monotonic_ns() - time.time_ns()
        out = []
        for e in self.telemetry_events:
            if e.get("event") == "span" and e.get("name") == name and e.get("t_start") is not None:
                t0 = int(e["t_start"] * 1e9) + wall_to_mono
                if self.window["open_ns"] <= t0 and t0 + int(e["dur"] * 1e9) <= self.window["close_ns"] + int(5e6):
                    out.append({"t0_ns": t0, "dur": float(e["dur"])})
        return out

    @property
    def gradient_steps(self) -> float:
        """Gradient steps of the window: its policy steps times the recipe's
        replay ratio (the program's ``Ratio`` is deterministic)."""
        return self.window["policy_steps"] * float(self.cell.config["algo"]["replay_ratio"])


def _run_record(path: str) -> Dict[str, Any]:
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        lines = [json.loads(line) for line in f if line.strip()]
    return lines[-1] if lines else {}


def _check_placement(cell, placement: Dict[str, Any]) -> None:
    """Every key of the configuration's ``expect`` (but ``platform``, which the
    look for a chip has held) against what the algorithm's capture read of the
    program, such as where its replay and its player resolved to."""
    for key, want in cell.config["expect"].items():
        if key != "platform" and placement.get(key) != want:
            raise SystemExit(f"perfbench: {key} resolved to {placement.get(key)!r}; the configuration's file expects {want!r}")


def main(argv: Optional[List[str]] = None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    say(f"[perfbench] the whole run took {(time.monotonic_ns() - _T0_NS) / 1e9:.1f}s")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
