"""Recompile watchdog: turn silent retracing into loud, counted events.

``jax.monitoring`` fires duration events for every trace/lower/compile.  The
robust "a new computation variant exists" signal is
``/jax/core/compile/jaxpr_to_mlir_module_duration``: it fires exactly once per
traced-and-lowered variant even when the persistent compilation cache
satisfies the backend compile (``backend_compile_duration`` can be skipped or
be near-zero on cache hits, so it is emitted as a secondary ``phase`` only).

jax.monitoring passes no function names, so while the watchdog is active the
``jax._src.interpreters.pxla`` logger is lowered to DEBUG and a capture
handler parses the "Compiling <name> with global shapes and types" line that
immediately precedes lowering; the original level is restored on ``stop()``.

After :meth:`mark_warm` (each training loop calls it at its own steady-state
point; the off-policy and Dreamer loops share :meth:`mark_warm_after_warmup`),
every further lowering is a *recompile*:
it increments the ``Counters/recompiles`` counter, is tagged
``post_warm=true`` in the JSONL stream, and raises a ``RecompileWarning`` —
silent retracing is the #1 TPU perf killer.
"""

from __future__ import annotations

import logging
import threading
import warnings
from contextlib import contextmanager
from typing import Any, Dict, Optional

_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
# persistent-compilation-cache outcomes (plain events, no duration): one per
# backend-compile request while the persistent cache is on
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_PXLA_LOGGER = "jax._src.interpreters.pxla"


class RecompileWarning(UserWarning):
    """A jitted function was re-traced/re-lowered after the warmup point."""


class _NameCaptureHandler(logging.Handler):
    """Grabs the function name from pxla's 'Compiling <name> with global
    shapes and types ...' DEBUG line, emitted just before lowering."""

    def __init__(self) -> None:
        super().__init__(level=logging.DEBUG)
        self.last_name: Optional[str] = None

    def emit(self, record: logging.LogRecord) -> None:
        try:
            msg = record.getMessage()
        except Exception:
            return
        if msg.startswith("Compiling "):
            self.last_name = msg[len("Compiling ") :].split(" ", 1)[0]


class CompileWatchdog:
    """Subscriber for jax.monitoring compile-duration events.

    Lifecycle is owned by :class:`~sheeprl_tpu.obs.telemetry.RunTelemetry`:
    ``start()`` on configure, ``mark_warm()`` at the steady-state point,
    ``stop()`` on shutdown (unregisters the listener and restores the pxla
    logger).  ``emit`` is the telemetry event sink.
    """

    def __init__(self, emit) -> None:
        self._emit = emit
        self.compiles = 0
        self.recompiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        # deliberate (allowlisted) post-warmup compiles by reason — AOT cost
        # analysis, the serve batch ladder, hot-swap revalidation
        self.deliberate_compiles: Dict[str, int] = {}
        # executable-cache loads by tag: work XLA does while deserializing a
        # cached executable (ops/aotcache) is neither a compile nor a
        # recompile — a third category, counted separately
        self.aot_loads: Dict[str, int] = {}
        self.warm = False
        self._first_update: Optional[int] = None
        # compiles fire on the compiling thread (serve AOT on the server's
        # caller, revalidation on watcher threads), so the allowlist flag
        # must be thread-local: one thread's deliberate window must not
        # silence a real retrace racing on another thread
        self._deliberate = threading.local()
        # same thread-locality argument for aot-load windows: the fleet
        # deserializes per-replica ladders concurrently with live traffic
        self._aot_load = threading.local()
        self._started = False
        self._handler = _NameCaptureHandler()
        self._logger = logging.getLogger(_PXLA_LOGGER)
        self._saved_level: Optional[int] = None
        self._saved_propagate: Optional[bool] = None

    def start(self) -> None:
        if self._started:
            return
        import jax

        self._saved_level = self._logger.level
        self._logger.addHandler(self._handler)
        if self._logger.getEffectiveLevel() > logging.DEBUG:
            self._logger.setLevel(logging.DEBUG)
            # the DEBUG records exist only for the capture handler — don't
            # spray them through the root handler for the watchdog's lifetime
            self._saved_propagate = self._logger.propagate
            self._logger.propagate = False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_plain_event)
        self._started = True

    def stop(self) -> None:
        if not self._started:
            return
        self._started = False
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_event)
        jax.monitoring.unregister_event_listener(self._on_plain_event)
        self._logger.removeHandler(self._handler)
        if self._saved_level is not None:
            self._logger.setLevel(self._saved_level)
            self._saved_level = None
        if self._saved_propagate is not None:
            self._logger.propagate = self._saved_propagate
            self._saved_propagate = None

    def mark_warm(self) -> None:
        if not self.warm:
            self.warm = True
            # one event at the flip, so a reader of the stream can tell "no
            # recompile" from "the warm point was never reached"
            self._emit("warm", compiles=self.compiles)

    #: updates past the first train event before a loop counts as warm —
    #: enough for every gradient-path compile (incl. the chunked-scan
    #: variants) to have happened, shared by all off-policy loops
    WARMUP_UPDATES = 64

    def mark_warm_after_warmup(self, update: int, learning_starts: int) -> None:
        """The one warm-point rule of the off-policy/Dreamer loops, called
        every update. Two conditions, both required:

        - ``learning_starts + WARMUP_UPDATES``: past the first train event's
          compiles (the fresh-run rule);
        - ``first observed update + WARMUP_UPDATES``: a RESUMED run whose
          start update is already beyond the fresh-run warm point still does
          its gradient-path compiles on its first update — going warm there
          would count every one of them as a recompile.
        """
        if self._first_update is None:
            self._first_update = update
        if update >= learning_starts + self.WARMUP_UPDATES and update >= self._first_update + self.WARMUP_UPDATES:
            self.mark_warm()

    @contextmanager
    def deliberate(self, reason: str):
        """Allowlist window: compiles on THIS thread while the context is
        open are deliberate (counted per ``reason``, tagged in the event
        stream) and never raise :class:`RecompileWarning`, even after
        :meth:`mark_warm` — the carve-out for AOT cost analysis, the serve
        tier's batch-ladder warmup and hot-swap revalidation."""
        prev = getattr(self._deliberate, "reason", None)
        self._deliberate.reason = str(reason)
        try:
            yield
        finally:
            self._deliberate.reason = prev

    @contextmanager
    def aot_load(self, tag: str):
        """Executable-cache load window: monitoring events fired on THIS
        thread while a serialized executable deserializes are classified as
        ``aot_load`` — neither a (re)compile nor a ``deliberate:`` compile.
        A cache hit must leave ``compiles``/``recompiles`` untouched or the
        'recompiles 0 after resume' acceptance signal would be noise."""
        prev = getattr(self._aot_load, "tag", None)
        self._aot_load.tag = str(tag)
        try:
            yield
        finally:
            self._aot_load.tag = prev

    def _on_plain_event(self, event: str, **kwargs: Any) -> None:
        """Persistent-compilation-cache outcome: one ``compile_cache`` event
        per backend-compile request, so a resumed run can show its retraces
        were served from the persistent compilation cache."""
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1
            hit = True
        elif event == _CACHE_MISS_EVENT:
            self.cache_misses += 1
            hit = False
        else:
            return
        try:
            self._emit("compile_cache", name=self._handler.last_name or "<unknown>", hit=hit)
        except Exception:
            pass

    def _on_event(self, event: str, duration: float, **kwargs: Any) -> None:
        if event == _LOWER_EVENT:
            phase = "lower"
        elif event == _BACKEND_EVENT:
            phase = "backend"
        else:
            return
        name = self._handler.last_name or "<unknown>"
        aot_tag = getattr(self._aot_load, "tag", None)
        if aot_tag is not None:
            if phase == "lower":
                self.aot_loads[aot_tag] = self.aot_loads.get(aot_tag, 0) + 1
            try:
                self._emit("compile", name=name, phase=phase, dur=duration, post_warm=False, aot_load=aot_tag)
            except Exception:
                pass
            return
        reason = getattr(self._deliberate, "reason", None)
        post_warm = self.warm and reason is None
        if phase == "lower":
            self.compiles += 1
            if reason is not None:
                self.deliberate_compiles[reason] = self.deliberate_compiles.get(reason, 0) + 1
            elif post_warm:
                self.recompiles += 1
                # a dedicated event carrying the offending function's
                # qualified name, so runtime retraces can be cross-referenced
                # against jaxcheck's static JX05 findings (tools/jaxcheck,
                # howto/static_analysis.md) — the `compile` stream below is
                # shared with warmup and deliberate compiles
                try:
                    self._emit("recompile", name=name, qualname=name, dur=duration, count=self.recompiles)
                except Exception:
                    pass
                warnings.warn(
                    f"recompile after warmup: {name} was re-traced/re-lowered "
                    f"({duration:.3f}s). Check for weak-type or shape drift in its inputs. "
                    f"Static complement: python -m tools.jaxcheck (JX05).",
                    RecompileWarning,
                    stacklevel=2,
                )
        extra = {"deliberate": reason} if reason is not None else {}
        try:
            self._emit("compile", name=name, phase=phase, dur=duration, post_warm=post_warm, **extra)
        except Exception:
            pass
