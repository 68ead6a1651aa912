"""Localhost multi-host drills → `*:p2` registry cells (ISSUE 18 acceptance).

Three drills, each spanning TWO processes on this host exactly the way a
two-host deployment would span two machines — the localhost socket / gloo
link stands in for the DCN:

- ``actor_learner``: the decoupled PPO entrypoint with
  ``algo.actor_learner.transport=tcp`` — a real actor process dials the
  learner over 127.0.0.1, trains to completion with zero torn slabs trained
  on and zero admitted slabs dropped. The run's own registry record (sps,
  overlap, slab/net totals) is re-keyed to the data-plane process span.
  → ``train:ppo_decoupled:CartPole-v1:cpux1p2:actor_learner``
- ``serve``: a replica-agent process (``net/agent.py``) serving the linear
  policy over an ephemeral TCP port, adopted by a FleetServer as a remote
  replica; a closed-loop client measures qps/p95 and the fleet-side
  transport counters are recorded.
  → ``serve:linear:remote_drill:cpux1p2:fleet_remote``
- ``mesh``: the ``cpux8p2`` training-parity cell — two ``jax.distributed``
  processes (4 virtual CPU devices each) form one global ``(data=2,
  model=4)`` mesh and run the two-window fused-superstep case
  (``tests/test_parallel``: ``run_2d_superstep_case``); the leaves must
  match a single-device run of the same case, and the in-child assert
  proves window 2 reused window 1's executable (``recompiles=0`` is the
  gated metric). → ``train:superstep2d:parity:cpux8p2:mesh``

Usage::

    python benchmarks/multihost_drill.py --rounds 3 --record --runs RUNS.jsonl
    python benchmarks/multihost_drill.py --drills serve mesh   # subset, print-only

Records carry ``process_count=2`` explicitly: the drills' whole point is the
cross-process data plane, so the cell reports the span of that plane (the
mesh drill likewise reports the GLOBAL device count, naming the mesh).
``tools/regress.py`` gates the cells like any other — net counters
(checksum rejects, torn frames) are lower-better with zero slack.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

SCHEMA_VERSION = 1


# ------------------------------------------------------------------ children


def child_serve() -> None:
    """Fleet + one remote agent process, closed-loop load, JSON on stdout."""
    import multiprocessing

    import cloudpickle
    import numpy as np

    from sheeprl_tpu.net.agent import agent_child_main
    from sheeprl_tpu.net.stats import net_stats_snapshot
    from sheeprl_tpu.resilience.manifest import build_manifest
    from sheeprl_tpu.serve.config import serve_config_from_cfg
    from sheeprl_tpu.serve.fleet import REMOTE, FleetServer
    from sheeprl_tpu.serve.policy import build_linear_policy, make_linear_state
    from sheeprl_tpu.utils.checkpoint import save_checkpoint

    tmp = tempfile.mkdtemp(prefix="multihost_drill_serve_")
    ckpt_dir = os.path.join(tmp, "checkpoint")
    os.makedirs(ckpt_dir, exist_ok=True)
    state = make_linear_state(seed=0)
    man = build_manifest(step=100, backend="pickle", world_size=1, state=state)
    path = os.path.join(ckpt_dir, "ckpt_100_0.ckpt")
    save_checkpoint(path, state, backend="pickle", manifest=man)

    ctx = multiprocessing.get_context("spawn")
    blob = cloudpickle.dumps({"cfg": {"algo": {"name": "linear"}}, "state": state, "rungs": [1, 2, 4]})
    pipe, child_pipe = ctx.Pipe(duplex=True)
    agent = ctx.Process(target=agent_child_main, args=(child_pipe, blob), daemon=True)
    agent.start()
    child_pipe.close()
    if not pipe.poll(120):
        raise SystemExit("agent never became ready")
    msg = pipe.recv()
    if msg[0] != "ready":
        raise SystemExit(f"agent boot failed: {msg}")
    addr = f"{msg[1]}:{msg[2]}"

    node = {
        "batch_ladder": [1, 2, 4],
        "slo_ms": 200.0,
        "monitor_interval_s": 0.01,
        "backoff_base_s": 0.01,
        "backoff_max_s": 0.05,
        "replica_timeout_s": 5.0,
        "fleet": {
            "enabled": True,
            "num_replicas": 1,
            "min_replicas": 1,
            "max_replicas": 1,
            "backlog_per_replica": 64,
            "hedge_scan_ms": 2.0,
            "autoscale_interval_s": 0.05,
            "remote_agents": [addr],
        },
    }
    cfg = serve_config_from_cfg({"serve": node})
    policy = build_linear_policy({"algo": {"name": "linear"}}, state)
    server = FleetServer(policy, cfg, step=100, path=path, ckpt_dir=ckpt_dir)

    n = 200
    obs = {"vector": np.full((4,), 1.0, dtype=np.float32)}
    lat = []
    with server:
        remote_slots = [s for s in server.slots if s.kind == REMOTE]
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and not all(s.alive for s in remote_slots):
            time.sleep(0.02)
        if not all(s.alive for s in remote_slots):
            raise SystemExit("remote replica never connected")
        # open-loop bursts: with requests queued, the router spreads load
        # across local AND remote replicas (closed-loop one-at-a-time would
        # always find the local replica idle and never exercise the socket)
        burst = 20
        t_start = time.perf_counter()
        for _ in range(n // burst):
            inflight = []
            for _ in range(burst):
                inflight.append((server.submit(obs, deadline_s=10.0), time.perf_counter()))
            for req, t0 in inflight:
                server.wait(req)
                lat.append((time.perf_counter() - t0) * 1e3)
        elapsed = time.perf_counter() - t_start
        served_remote = sum(
            s.total_requests + (s.stats.requests if s.stats is not None else 0)
            for s in remote_slots
        )
        snap = server.snapshot()

    pipe.send(("close",))
    agent.join(5)
    if agent.is_alive():
        agent.kill()

    lat.sort()
    out = {
        "qps": n / elapsed,
        "p50_ms": lat[len(lat) // 2],
        "p95_ms": lat[min(len(lat) - 1, int(round(0.95 * (len(lat) - 1))))],
        "slo_ms": 200.0,
        "completed": snap["completed"],
        "failed": snap["failed"],
        "served_remote": served_remote,
        "net": net_stats_snapshot(),
    }
    print("DRILL_JSON " + json.dumps(out), flush=True)


# the mesh workers reuse the p2 parity case body shipped with the test suite
# (tests/ is a package in this repo precisely so drills and tests share one
# definition of the case — drift between them would un-prove the parity)
_MESH_WORKER = """
import json, os, sys, time
import jax
from sheeprl_tpu.parallel.fabric import Fabric
from tests.test_parallel.test_sharded_superstep import run_2d_superstep_case
fabric = Fabric(
    devices=8, precision="fp32", mesh_axes=("data", "model"), mesh_shape=(2, 4),
    distributed_coordinator=os.environ["DRILL_COORD"],
    num_processes=int(os.environ["DRILL_NPROC"]),
    process_id=int(os.environ["DRILL_PID"]),
)
assert fabric.num_processes == 2 and fabric.world_size == 8
t0 = time.perf_counter()
run_2d_superstep_case(fabric, True, sys.argv[1])
elapsed = time.perf_counter() - t0
if jax.process_index() == 0:
    print("DRILL_JSON " + json.dumps({"elapsed_s": elapsed}), flush=True)
"""

_SINGLE_WORKER = """
import sys
from tests.test_parallel.test_sharded_superstep import superstep_equivalence_case_2d
superstep_equivalence_case_2d(1, sys.argv[1])
"""


def _spawn_worker(code, argv, extra_env, device_count, timeout):
    env = dict(os.environ)
    env.pop("SHEEPRL_TPU_COORDINATOR", None)
    env.pop("SHEEPRL_TPU_NUM_PROCESSES", None)
    env.pop("SHEEPRL_TPU_PROCESS_ID", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={device_count}"
    # the persistent trace cache is topology-poisoned across process-group
    # sizes (see fabric.configure_compilation_cache) — switch it off rather
    # than risk a single-process executable in the p2 group
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    env["PYTHONPATH"] = os.pathsep.join(p for p in (REPO_ROOT, env.get("PYTHONPATH")) if p)
    env.update(extra_env)
    return subprocess.Popen(
        [sys.executable, "-c", code, *argv],
        env=env,
        cwd=REPO_ROOT,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
    )


def drill_mesh(timeout: float = 540.0) -> dict:
    """Run the cpux8p2 parity case: 2 jax.distributed processes vs 1 device."""
    import numpy as np

    tmp = tempfile.mkdtemp(prefix="multihost_drill_mesh_")
    p2_out = os.path.join(tmp, "p2.npz")
    single_out = os.path.join(tmp, "single.npz")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    workers = [
        _spawn_worker(
            _MESH_WORKER,
            [p2_out],
            {
                "DRILL_COORD": f"127.0.0.1:{port}",
                "DRILL_NPROC": "2",
                "DRILL_PID": str(pid),
            },
            device_count=4,
            timeout=timeout,
        )
        for pid in range(2)
    ]
    outs = []
    try:
        for w in workers:
            outs.append(w.communicate(timeout=timeout)[0])
    finally:
        for w in workers:
            if w.poll() is None:
                w.kill()
                w.wait()
    for pid, (w, out) in enumerate(zip(workers, outs)):
        if w.returncode != 0:
            raise SystemExit(f"mesh worker {pid} failed:\n{out[-4000:]}")
    single = _spawn_worker(_SINGLE_WORKER, [single_out], {}, device_count=1, timeout=timeout)
    out, _ = single.communicate(timeout=timeout)
    if single.returncode != 0:
        raise SystemExit(f"single-device worker failed:\n{out[-4000:]}")

    got, want = np.load(p2_out), np.load(single_out)
    parity = set(got.files) == set(want.files) and bool(got.files)
    max_err = 0.0
    for name in got.files:
        if not np.allclose(got[name], want[name], rtol=1e-5, atol=1e-6):
            parity = False
        diff = np.max(np.abs(np.asarray(got[name], dtype=np.float64) - np.asarray(want[name], dtype=np.float64)))
        max_err = max(max_err, float(diff))
    stamped = next(
        json.loads(line.split("DRILL_JSON ", 1)[1])
        for o in outs
        for line in o.splitlines()
        if line.startswith("DRILL_JSON ")
    )
    return {"parity": parity, "max_abs_err": max_err, "elapsed_s": stamped["elapsed_s"]}


def drill_actor_learner(timeout: float = 540.0) -> dict:
    """One decoupled-PPO TCP run in a subprocess; returns its registry record."""
    tmp = tempfile.mkdtemp(prefix="multihost_drill_al_")
    runs_tmp = os.path.join(tmp, "RUNS.jsonl")
    args = [
        "exp=ppo_decoupled",
        # a real (short) run, not dry_run: 8 update rounds → 8 admitted slabs,
        # so sps_env reflects the steady-state ring rather than compile noise
        "dry_run=False",
        "algo.total_steps=512",
        "env.capture_video=False",
        "buffer.memmap=False",
        "algo.rollout_steps=32",
        "algo.per_rank_batch_size=8",
        "algo.update_epochs=2",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.encoder.cnn_features_dim=16",
        "algo.encoder.mlp_features_dim=8",
        "env.num_envs=2",
        "algo.run_test=False",
        "checkpoint.save_last=True",
        "metric.log_level=1",
        "metric.telemetry.enabled=True",
        "metric.telemetry.poll_interval=0.0",
        "algo.actor_learner.num_actors=1",
        "algo.actor_learner.slots_per_actor=2",
        "algo.actor_learner.transport=tcp",
        f"log_base_dir={tmp}/logs",
        f"metric.telemetry.runs_jsonl={runs_tmp}",
    ]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(p for p in (REPO_ROOT, env.get("PYTHONPATH")) if p)
    proc = subprocess.run(
        [sys.executable, "-c", "import sys; from sheeprl_tpu.cli import run; run(sys.argv[1:])", *args],
        env=env,
        cwd=tmp,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        timeout=timeout,
    )
    if proc.returncode != 0:
        raise SystemExit(f"actor_learner drill failed:\n{proc.stdout[-4000:]}")
    with open(runs_tmp) as f:
        records = [json.loads(line) for line in f if line.strip()]
    (rec,) = records
    if rec.get("outcome") != "completed":
        raise SystemExit(f"actor_learner drill outcome={rec.get('outcome')}")
    # torn slabs are data corruption — never acceptable. Stale-slab drops are
    # the ring's deliberate flow-control policy in a real multi-update run;
    # they are recorded but only sanity-bounded here.
    if rec.get("torn_slabs", 0) != 0:
        raise SystemExit(f"zero-torn invariant violated: {rec}")
    if rec.get("dropped_stale_slabs", 0) >= rec.get("slabs_admitted", 0):
        raise SystemExit(f"ring dropped as many slabs as it admitted: {rec}")
    return rec


# ------------------------------------------------------------------ records


def _append(record: dict, runs_path: str) -> None:
    from sheeprl_tpu.obs.registry import append_run_record, runs_jsonl_path

    path = runs_jsonl_path(None, runs_path)
    if path is None:
        print("run registry disabled; record dropped", flush=True)
        return
    append_run_record(record, path)
    print(f"recorded {record['kind']}:{record['algo']} p2 cell -> {path}", flush=True)


def record_actor_learner(rec: dict) -> dict:
    out = dict(rec)
    out.pop("telemetry_files", None)  # drill tmp paths, gone after the run
    out.update(
        t=time.time(),
        # the data-plane span: learner + 1 TCP actor process (the registry's
        # own process_count is jax.process_count(), which cannot see the
        # actor on the far side of the socket)
        process_count=2,
        drill="localhost_tcp",
    )
    return out


def record_serve(out: dict) -> dict:
    ok = out["failed"] == 0 and out["served_remote"] >= 1
    return {
        "schema": SCHEMA_VERSION,
        "t": time.time(),
        "kind": "serve",
        "algo": "linear",
        "env": "remote_drill",
        "backend": "cpu",
        "local_device_count": 1,
        "process_count": 2,
        "variant": "fleet_remote",
        "outcome": "completed" if ok else "crashed",
        "serve_stats": {"qps": out["qps"], "p95_ms": out["p95_ms"], "slo_ms": out["slo_ms"]},
        "completed_requests": out["completed"],
        "failed_requests": out["failed"],
        "served_remote": out["served_remote"],
        "net": {"transports": out["net"]},
        "drill": "localhost_tcp",
    }


def record_mesh(out: dict) -> dict:
    return {
        "schema": SCHEMA_VERSION,
        "t": time.time(),
        "kind": "train",
        "algo": "superstep2d",
        "env": "parity",
        "backend": "cpu",
        "local_device_count": 8,  # GLOBAL mesh size: the cell names the mesh
        "process_count": 2,
        "variant": "mesh",
        "outcome": "completed" if out["parity"] else "crashed",
        # the in-child assert proved window 2 reused window 1's executable
        # across the process boundary; gate it staying that way
        "recompiles": 0,
        "parity": out["parity"],
        "max_abs_err": out["max_abs_err"],
        "elapsed_s": out["elapsed_s"],
        "drill": "localhost_gloo",
    }


DRILLS = ("actor_learner", "serve", "mesh")


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--child", choices=("serve",), help=argparse.SUPPRESS)
    p.add_argument("--drills", nargs="+", choices=DRILLS, default=list(DRILLS))
    p.add_argument("--rounds", type=int, default=1, help="records per cell")
    p.add_argument("--record", action="store_true", help="append registry lines for --regress")
    p.add_argument("--runs", default="RUNS.jsonl", help="run-registry path for --record")
    p.add_argument("--timeout", type=float, default=540.0, help="per-drill budget (s)")
    args = p.parse_args()

    if args.child == "serve":
        child_serve()
        return 0

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(q for q in (REPO_ROOT, env.get("PYTHONPATH")) if q)
    for round_idx in range(args.rounds):
        for drill in args.drills:
            t0 = time.perf_counter()
            if drill == "actor_learner":
                record = record_actor_learner(drill_actor_learner(timeout=args.timeout))
            elif drill == "mesh":
                record = record_mesh(drill_mesh(timeout=args.timeout))
            else:
                proc = subprocess.run(
                    [sys.executable, os.path.abspath(__file__), "--child", "serve"],
                    env=env,
                    cwd=REPO_ROOT,
                    stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT,
                    text=True,
                    timeout=args.timeout,
                )
                if proc.returncode != 0:
                    raise SystemExit(f"serve drill failed:\n{proc.stdout[-4000:]}")
                payload = next(
                    line.split("DRILL_JSON ", 1)[1]
                    for line in proc.stdout.splitlines()
                    if line.startswith("DRILL_JSON ")
                )
                record = record_serve(json.loads(payload))
            print(
                json.dumps(
                    {
                        "round": round_idx,
                        "drill": drill,
                        "outcome": record.get("outcome"),
                        "wall_s": round(time.perf_counter() - t0, 1),
                    }
                ),
                flush=True,
            )
            if record.get("outcome") != "completed":
                raise SystemExit(f"{drill} drill did not complete: {record}")
            if args.record:
                _append(record, args.runs)
    return 0


if __name__ == "__main__":
    sys.exit(main())
