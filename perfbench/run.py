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
from typing import Any, Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: seconds of device trace, taken at the end of a traced run's window
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


class Watcher(threading.Thread):
    """Opens the window, takes the trace, and closes the window with the
    signal the program treats as a preemption."""

    def __init__(self, stamps, compiles: CompileLog, *, action_repeat: int, open_after: int, quiet_steps: int,
                 seconds: float, trace_dir: Optional[str]) -> None:  # fmt: skip
        super().__init__(name="perfbench-watcher", daemon=True)
        self.stamps, self.compiles = stamps, compiles
        self.action_repeat, self.open_after, self.quiet_steps = action_repeat, open_after, quiet_steps
        self.seconds, self.trace_dir = seconds, trace_dir
        self.open_index: Optional[int] = None
        self.deadline_ns: Optional[int] = None
        self.trace_span_ns: Optional[List[int]] = None
        self.sync: Optional[Dict[str, int]] = None
        self.gave_up = False
        self.cancel = threading.Event()

    def _exit_ns(self, index: int) -> int:
        return int(self.stamps[2 + 2 * ((index + 1) * self.action_repeat - 1)])

    def _sleep_until(self, t_ns: int) -> None:
        while not self.cancel.is_set():
            left = (t_ns - time.monotonic_ns()) / 1e9
            if left <= 0:
                return
            time.sleep(min(left, 0.05))

    def run(self) -> None:
        give_up_ns = _T0_NS + int(SETUP_LIMIT_S * 1e9)
        while not self.cancel.is_set():
            done = int(self.stamps[0]) // self.action_repeat
            # quiet: no compile since the vector step `quiet_steps` back returned
            if done > self.open_after and self.compiles.last_end_ns() < self._exit_ns(done - 1 - self.quiet_steps):
                self.open_index = done - 1
                break
            if time.monotonic_ns() > give_up_ns:
                self.gave_up = True
                os.kill(os.getpid(), signal.SIGTERM)
                return
            time.sleep(0.002)
        if self.open_index is None:
            return
        open_ns = self._exit_ns(self.open_index)
        self.deadline_ns = open_ns + int(self.seconds * 1e9)
        if self.trace_dir is not None:
            import jax

            self._sleep_until(self.deadline_ns - int(min(TRACE_SECONDS, self.seconds / 2) * 1e9))
            if self.cancel.is_set():
                return
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # Python's own calls are not wanted and cost the loop time
            options.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            t_a = time.monotonic_ns()
            with jax.profiler.TraceAnnotation("perfbench/sync"):
                t_b = time.monotonic_ns()
            self.sync = {"before_ns": t_a, "inside_ns": t_b}
            self.trace_span_ns = [t_b, self.deadline_ns]
        self._sleep_until(self.deadline_ns)
        if not self.cancel.is_set():
            os.kill(os.getpid(), signal.SIGTERM)


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
    from perfbench import env as bench_env, loader, window

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
    warm = int(cell.workload["warm_steps"] if warm_steps is None else warm_steps)
    say(f"[perfbench] {workload} seed={seed} seconds={seconds} trace={int(trace)} device={devices[0].device_kind} x{len(devices)}")
    say(f"[perfbench] compile cache {cache_dir}; run dir {os.path.relpath(run_dir, root)}")
    say("[perfbench] program command: " + " ".join(overrides))

    compiles = CompileLog().start()
    capture = algorithm.Capture(cell.config, program_seed)
    trace_dir = os.path.join(run_dir, "trace") if trace else None
    watcher = Watcher(stamps, compiles, action_repeat=action_repeat, open_after=learning_starts + warm,
                      quiet_steps=warm, seconds=float(seconds), trace_dir=trace_dir)  # fmt: skip
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
        if trace_dir is not None and watcher.trace_span_ns is not None:
            jax.profiler.stop_trace()
        compiles.stop()
    if watcher.gave_up or watcher.open_index is None or exit_code != PREEMPTED:
        raise SystemExit(f"perfbench: the program left with {exit_code!r} and the window "
                         f"{'never opened' if watcher.open_index is None else 'was open'}: no result")  # fmt: skip
    gc.collect()
    peak_bytes = _peak_bytes(devices)

    entry, exit_ = window.vector_steps(bench_env.open_stamps(stamp_path), action_repeat)
    win = window.measure(entry, exit_, watcher.open_index, watcher.deadline_ns, num_envs)
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
                   entry_ns=entry, exit_ns=exit_, peak=peak, peak_bytes=peak_bytes, capture=capture, record=record)  # fmt: skip
    device = _device_line(devices, peak_bytes)
    result: Dict[str, Any] = {"correct": bool(ok), "attempted": win["vector_steps"], "failed": 0}
    if trace:
        from perfbench import trace_reduce

        reduced = trace_reduce.reduce_dir(trace_dir, run)
        run.trace = reduced
        metrics = {}
        readers = loader.layer_readers(cell)
        for spec in cell.per_layer:
            value = readers[spec["name"]](run)
            if value is not None:
                metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
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
