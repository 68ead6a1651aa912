"""Span events: one section name, three sinks.

``span`` subsumes the old ``utils.timer.timer`` context-decorator (same class
attributes, same ``TimerError`` semantics — ``utils/timer.py`` is now a shim
over this class) and, when run telemetry is configured, additionally:

- wraps the block in ``jax.profiler.TraceAnnotation(name)`` so the section
  shows up by the same name in the XLA/Perfetto trace, and
- emits one ``span`` JSON event per close to the per-process
  ``telemetry.jsonl`` (name, parent, t_start, t_mono_ns, dur, step,
  process_index, attrs). ``t_mono_ns`` is ``time.monotonic_ns()`` at entry:
  the clock a device trace is aligned to, which a wall-clock step cannot
  move. ``parent`` is the name of the innermost span open on the same thread
  when this one began (``None`` at the top), so a reader takes a span's self
  time as its duration less what its children cover.

Spans nest: the loops put leaf spans (``player/get_actions``, ``ring/add``,
``env/step``, ...; howto/telemetry.md has the vocabulary) inside and beside
their two window spans. A span never waits for the device: it times the host,
and the device's own time is read from the profiler's trace by program name.

With telemetry off the hot path is byte-for-byte the old timer plus a single
module-global read, so ``metric.telemetry.enabled=False`` costs nothing: the
stack of open spans is kept only while telemetry is on.
"""

from __future__ import annotations

import threading
import time
from contextlib import ContextDecorator
from typing import Any, Dict, List, Optional

from sheeprl_tpu.utils.metric import Metric, SumMetric, make_metric


class TimerError(Exception):
    pass


#: per thread, the spans open on it while telemetry is on, innermost last
_open = threading.local()


class span(ContextDecorator):
    """Context-decorator that accumulates wall-clock seconds per ``name`` in a
    class-level :class:`Metric` registry and mirrors the section into the XLA
    trace and the telemetry JSONL stream when telemetry is active.

    ``disabled`` only silences the metric registry (the old ``timer.disabled``
    contract, driven by ``metric.log_level`` / ``metric.disable_timer``);
    telemetry emission is governed independently by
    ``metric.telemetry.enabled`` so a low log level still yields JSONL spans.
    """

    disabled: bool = False
    timers: Dict[str, Metric] = {}

    def __init__(self, name: str, metric: Optional[object] = None, **attrs: Any) -> None:
        self.name = name
        self.attrs = attrs
        self._start_time: Optional[float] = None
        self._wall_start: Optional[float] = None
        self._mono_start_ns: Optional[int] = None
        self._annotation = None
        self._stack: Optional[List["span"]] = None
        self._parent: Optional[str] = None
        if not span.disabled and name is not None and name not in span.timers:
            span.timers[name] = make_metric(metric) if metric is not None else SumMetric()

    def start(self) -> None:
        if self._start_time is not None:
            raise TimerError("timer is running. Use .stop() to stop it")
        self._start_time = time.perf_counter()

    def stop(self) -> float:
        if self._start_time is None:
            raise TimerError("timer is not running. Use .start() to start it")
        elapsed = time.perf_counter() - self._start_time
        self._start_time = None
        if self.name and not span.disabled and self.name in span.timers:
            span.timers[self.name].update(elapsed)
        return elapsed

    @classmethod
    def reset(cls) -> None:
        for m in cls.timers.values():
            m.reset()

    @classmethod
    def compute(cls) -> Dict[str, float]:
        return {k: v.compute() for k, v in cls.timers.items()}

    def __enter__(self) -> "span":
        from sheeprl_tpu.obs.telemetry import get_telemetry

        tel = get_telemetry()
        if tel is not None:
            stack = _open.__dict__.setdefault("stack", [])
            self._parent = stack[-1].name if stack else None
            stack.append(self)
            self._stack = stack
            self._wall_start = time.time()
            self._mono_start_ns = time.monotonic_ns()
            self._annotation = tel.trace_annotation(self.name)
            if self._annotation is not None:
                self._annotation.__enter__()
        if not span.disabled or tel is not None:
            # When only telemetry wants the span, still run the clock; stop()
            # skips the registry for names registered while disabled.
            if self.name is not None and not span.disabled and self.name not in span.timers:
                span.timers[self.name] = SumMetric()
            self.start()
        return self

    def __exit__(self, *exc_info) -> None:
        from sheeprl_tpu.obs.telemetry import get_telemetry

        tel = get_telemetry()
        elapsed: Optional[float] = None
        if self._start_time is not None:
            elapsed = self.stop()
        if self._annotation is not None:
            self._annotation.__exit__(*exc_info)
            self._annotation = None
        if self._stack is not None:
            self._stack.remove(self)  # the innermost, where spans close as they nest
            self._stack = None
        if tel is not None and elapsed is not None:
            tel.emit_span(self.name, self._wall_start, elapsed, self.attrs, t_mono_ns=self._mono_start_ns, parent=self._parent)
        self._wall_start = None
        self._mono_start_ns = None
        self._parent = None
