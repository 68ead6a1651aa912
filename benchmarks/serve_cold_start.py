"""Replica cold-start A/B for the AOT executable cache (ISSUE 17).

Measures **process spawn -> first request served** for a PolicyServer over a
deliberately compile-heavy synthetic policy (a deep tanh MLP whose long
serial graph makes XLA work for its answer), once per run:

- run 0 starts with an EMPTY ``serve.aot_cache_dir`` — every batch-ladder
  rung pays the full ``jit().lower().compile()`` — and populates the cache,
- runs 1..N boot against the now-warm cache and deserialize every rung
  (``jax.experimental.serialize_executable``), which is the fleet
  scale-up / replica-restart path howto/aot_cache.md describes.

The parent is stdlib-only (no jax import): each run is a fresh
``subprocess`` so the measurement includes interpreter + jax import +
backend init — the real cold-start a preempted replica pays. The child
prints a ``COLD_START_DONE {json}`` marker the moment the first inference
result is in hand; the parent's clock stops there, so server shutdown never
pollutes the number.

``--record`` folds one registry line per *cached* run into RUNS.jsonl
(kind=serve, algo=synthetic_mlp, env=cold_start, variant=cold_start,
metric ``cold_start_s`` lower-is-better) so ``tools/regress.py`` gates the
cold boot alongside the throughput cells.

Usage:
  python benchmarks/serve_cold_start.py [--repeats 3] [--depth 384]
      [--width 64] [--rungs 1,2,4,8,16,32,64,128] [--record] [--runs PATH]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

# repo root on sys.path: the timed children run this file by absolute path,
# which puts benchmarks/ (not the root) at sys.path[0]
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MARKER = "COLD_START_DONE "


# ----------------------------------------------------------------- child ----


def build_deep_policy(depth: int, width: int):
    """A ServedPolicy over a ``depth``-layer tanh MLP. The graph is one long
    serial chain, so compile time grows with depth while deserialize time
    stays O(bytes) — exactly the regime the executable cache targets."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu.serve.model import ServedPolicy

    rng = np.random.default_rng(0)
    scale = 1.0 / np.sqrt(width)
    params = {
        "layers": [
            {
                "w": jnp.asarray(rng.normal(0.0, scale, (width, width)), jnp.float32),
                "b": jnp.zeros((width,), jnp.float32),
            }
            for _ in range(depth)
        ]
    }

    def apply(p, obs):
        x = obs["vector"]
        for layer in p["layers"]:
            x = jnp.tanh(x @ layer["w"] + layer["b"])
        return x

    obs_spec = {"vector": jax.ShapeDtypeStruct((width,), jnp.float32)}
    return ServedPolicy(
        name="synthetic_mlp",
        apply=apply,
        params=params,
        obs_spec=obs_spec,
        params_from_state=lambda state: state,
    )


def run_child(cache_dir: str, depth: int, width: int, rungs) -> None:
    """Boot a server with ``aot_cache_dir``, serve ONE request, print the
    marker. Everything before the marker is the measured cold start."""
    import numpy as np

    from sheeprl_tpu.serve.config import serve_config_from_cfg
    from sheeprl_tpu.serve.server import PolicyServer

    import jax

    policy = build_deep_policy(depth, width)
    cfg = serve_config_from_cfg(
        {
            "serve": {
                "batch_ladder": list(rungs),
                "slo_ms": 1000.0,
                "num_replicas": 1,
                "monitor_interval_s": 0.05,
                "aot_cache_dir": cache_dir,
            }
        }
    )
    server = PolicyServer(policy, cfg, step=0, path="<synthetic>").start()
    try:
        obs = {"vector": np.ones((width,), np.float32)}
        result = server.infer(obs, deadline_s=60.0)
        snap = server.snapshot()
        print(
            MARKER
            + json.dumps(
                {
                    "backend": jax.default_backend(),
                    "from_cache": snap.get("ladder_from_cache") or {},
                    "aot_cache": snap.get("aot_cache") or {},
                    "action_sum": float(np.asarray(result).sum()),
                }
            ),
            flush=True,
        )
    finally:
        server.close()


# ---------------------------------------------------------------- parent ----


def _spawn_once(cache_dir: str, depth: int, width: int, rungs, timeout_s: float) -> dict:
    """One timed child: Popen -> marker line. Returns the child's marker
    payload plus ``elapsed_s``; raises on child failure or missing marker."""
    argv = [
        sys.executable,
        os.path.abspath(__file__),
        "--child",
        "--cache-dir",
        cache_dir,
        "--depth",
        str(depth),
        "--width",
        str(width),
        "--rungs",
        ",".join(str(r) for r in rungs),
    ]
    t0 = time.monotonic()
    proc = subprocess.Popen(
        argv,
        stdout=subprocess.PIPE,
        stderr=sys.stderr,
        text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    payload = None
    elapsed = None
    try:
        assert proc.stdout is not None
        deadline = t0 + timeout_s
        for line in proc.stdout:
            if line.startswith(MARKER):
                elapsed = time.monotonic() - t0  # clock stops at first served request
                payload = json.loads(line[len(MARKER):])
                break
            if time.monotonic() > deadline:
                break
        proc.wait(timeout=max(5.0, deadline - time.monotonic()))
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if payload is None or elapsed is None:
        raise RuntimeError(f"cold-start child produced no marker (rc={proc.returncode})")
    payload["elapsed_s"] = round(elapsed, 3)
    return payload


def measure(
    repeats: int = 3,
    depth: int = 384,
    width: int = 64,
    rungs=(1, 2, 4, 8, 16, 32, 64, 128),
    cache_dir: str | None = None,
    timeout_s: float = 900.0,
) -> dict:
    """Run the A/B: one compile-path boot on an empty cache, then
    ``repeats`` cached boots. Returns the summary record (stdlib-only)."""
    from statistics import median

    owned = None
    if cache_dir is None:
        owned = tempfile.TemporaryDirectory(prefix="sheeprl-coldstart-")
        cache_dir = owned.name
    try:
        compile_run = _spawn_once(cache_dir, depth, width, rungs, timeout_s)
        cached_runs = [
            _spawn_once(cache_dir, depth, width, rungs, timeout_s) for _ in range(repeats)
        ]
    finally:
        if owned is not None:
            owned.cleanup()
    cold_starts = [r["elapsed_s"] for r in cached_runs]
    all_cached = all(
        all(bool(v) for v in (r.get("from_cache") or {}).values()) and r.get("from_cache")
        for r in cached_runs
    )
    rec = {
        "workload": "serve_cold_start",
        "backend": compile_run.get("backend", "cpu"),
        "depth": depth,
        "width": width,
        "rungs": list(rungs),
        "compile_s": compile_run["elapsed_s"],
        "cached_s": cold_starts,
        "cold_start_s": round(median(cold_starts), 3),
        "speedup": round(compile_run["elapsed_s"] / max(median(cold_starts), 1e-9), 1),
        "all_rungs_from_cache": all_cached,
        "compile_run": compile_run,
        "cached_runs": cached_runs,
    }
    return rec


def append_runs(rec: dict, runs_path: str) -> int:
    """Fold one registry line per CACHED boot into the run registry, keyed
    ``serve:synthetic_mlp:cold_start:<backend>x1p1:cold_start`` so
    tools/regress.py gates ``cold_start_s`` (lower-better, 20% band) on its
    own history. The compile-path boot rides along as context fields, not
    as a gated record."""
    written = 0
    with open(runs_path, "a") as f:
        for run in rec.get("cached_runs") or []:
            f.write(
                json.dumps(
                    {
                        "schema": 1,
                        "t": time.time(),
                        "kind": "serve",
                        "algo": "synthetic_mlp",
                        "env": "cold_start",
                        "backend": rec.get("backend", "cpu"),
                        "local_device_count": 1,
                        "process_count": 1,
                        "outcome": "completed",
                        "variant": "cold_start",
                        "cold_start_s": float(run["elapsed_s"]),
                        "compile_s": rec.get("compile_s"),
                        "speedup": rec.get("speedup"),
                        "depth": rec.get("depth"),
                        "width": rec.get("width"),
                        "rungs": rec.get("rungs"),
                    }
                )
                + "\n"
            )
            written += 1
    return written


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    p.add_argument("--cache-dir", default=None, help="AOT cache dir (default: fresh tempdir)")
    p.add_argument("--depth", type=int, default=384, help="MLP layers (compile cost knob)")
    p.add_argument("--width", type=int, default=64, help="MLP width")
    p.add_argument("--rungs", default="1,2,4,8,16,32,64,128", help="batch ladder, comma-separated")
    p.add_argument("--repeats", type=int, default=3, help="cached boots after the compile boot")
    p.add_argument("--timeout", type=float, default=900.0, help="per-boot budget (s)")
    p.add_argument("--record", action="store_true", help="append registry lines for --regress")
    p.add_argument("--runs", default="RUNS.jsonl", help="run-registry path for --record")
    args = p.parse_args()
    rungs = tuple(int(r) for r in args.rungs.split(",") if r)

    if args.child:
        run_child(args.cache_dir, args.depth, args.width, rungs)
        return

    rec = measure(
        repeats=args.repeats,
        depth=args.depth,
        width=args.width,
        rungs=rungs,
        cache_dir=args.cache_dir,
        timeout_s=args.timeout,
    )
    if args.record:
        rec["registry_records"] = append_runs(rec, args.runs)
        rec["runs_path"] = args.runs
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
