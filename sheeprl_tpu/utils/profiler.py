"""Optional XLA profiler hook (SURVEY.md §5 tracing: "same wall-clock timers
plus optional ``jax.profiler.trace`` hooks").

The reference has no torch-profiler integration; on TPU the XLA trace is the
native tool — it records HLO timelines, per-op device time, and HBM traffic
viewable in TensorBoard's profile plugin or Perfetto.  Enabled via config:

    metric.profiler.enabled=True [metric.profiler.trace_dir=...]

and wrapped around the whole training entrypoint by the CLI, so one run
yields one trace directory next to the run's logs.
"""

from __future__ import annotations

import os
from contextlib import contextmanager
from typing import Any, Dict, Iterator, Mapping, Optional


@contextmanager
def maybe_profile(cfg: Mapping[str, Any], log_dir: Optional[str] = None) -> Iterator[Optional[str]]:
    """Start a ``jax.profiler`` trace when ``metric.profiler.enabled`` is set;
    no-op (yields None) otherwise. Only process 0 traces — each host tracing
    its own devices would do, but one trace is what the tooling expects."""
    prof_cfg = (cfg.get("metric") or {}).get("profiler") or {}
    enabled = bool(prof_cfg.get("enabled", False))
    if not enabled:
        yield None
        return

    import jax

    if jax.process_index() != 0:
        yield None
        return
    trace_dir = prof_cfg.get("trace_dir") or os.path.join(log_dir or ".", "profile")
    os.makedirs(trace_dir, exist_ok=True)
    jax.profiler.start_trace(trace_dir)
    try:
        yield trace_dir
    finally:
        jax.profiler.stop_trace()


# Published peaks of known chips (jax device_kind -> per chip), for MFU and
# roofline claims. Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s
# bf16, 819 GB/s HBM) and "TPU v5p" (459 TFLOP/s bf16). A chip that is not in
# the tables gets no MFU claim from the heartbeat, and is an error for any
# script that needs a peak (:func:`device_peaks`).
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12, "TPU v5e": 197e12, "TPU v5p": 459e12}
PEAK_HBM_BYTES_PER_S = {"TPU v5 lite": 819e9, "TPU v5e": 819e9}


def device_peaks(device_kind: str) -> Dict[str, float]:
    """``{"bf16_flops", "hbm_bytes_per_s"}`` of ``device_kind``; raises for a
    kind the tables do not hold — a default peak would turn a run on another
    device into a wrong roofline share."""
    if device_kind not in PEAK_BF16_FLOPS or device_kind not in PEAK_HBM_BYTES_PER_S:
        raise ValueError(
            f"no published peaks recorded for device kind {device_kind!r} "
            f"(known: {sorted(set(PEAK_BF16_FLOPS) & set(PEAK_HBM_BYTES_PER_S))}); add it to "
            "sheeprl_tpu/utils/profiler.py with its source"
        )
    return {"bf16_flops": PEAK_BF16_FLOPS[device_kind], "hbm_bytes_per_s": PEAK_HBM_BYTES_PER_S[device_kind]}


def tiny_op_rtt_seconds() -> float:
    """Best-of-5 dispatch + materializing-fetch round trip of a tiny jitted
    op (fetching the value is what guarantees the device has finished)."""
    import time

    import jax
    import numpy as np

    f = jax.jit(lambda x: x + 1)
    x = jax.device_put(np.ones((8, 8), np.float32))
    np.asarray(f(x))  # compile + warm
    rtts = []
    for _ in range(5):
        t0 = time.perf_counter()
        np.asarray(f(x))
        rtts.append(time.perf_counter() - t0)
    return min(rtts)


def compiled_flops(jitted_fn: Any, *args: Any) -> Optional[float]:
    """FLOPs of ONE invocation of ``jitted_fn`` at the shapes of ``args``,
    read from XLA's cost analysis of an AOT compile built from
    ``ShapeDtypeStruct``s — no data moves, but one extra compile is paid, so
    callers run this outside any measured window. The number feeds the
    heartbeat's MFU: flops x steps / seconds / chip peak."""
    import jax

    def as_shape(x: Any) -> Any:
        if isinstance(x, jax.ShapeDtypeStruct) or not (hasattr(x, "shape") and hasattr(x, "dtype")):
            return x  # already a spec (its sharding, if any, is kept)
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    try:
        compiled = jitted_fn.lower(*jax.tree.map(as_shape, args)).compile()
        analysis = compiled.cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0] if analysis else {}
        flops = float(analysis.get("flops", 0.0))
        return flops or None
    except Exception:
        return None
