"""Decoupled-topology LEARNING run (smoke/e2e tests alone do not show that
the decoupled path learns).

Spawns a real 2-process ``jax.distributed`` group on this host: process 0
plays Pendulum-v1 and owns the replay buffer, process 1 trains SAC on its
own mesh and streams the actor back (``algos/sac/sac_decoupled.py``). The
player's per-episode rewards are parsed from its output; the check passes
when the late-window mean improves on the early window by the margin a
same-budget coupled SAC reaches.

    python benchmarks/decoupled_learning_check.py --total-steps 12000
"""

from __future__ import annotations

import argparse
import json
import os
import re
import socket
import subprocess
import sys
import tempfile

RUNNER = """
import os, sys
import jax
jax.config.update('jax_platforms', 'cpu')
jax.distributed.initialize(
    coordinator_address=os.environ['COORD'],
    num_processes=int(os.environ['NPROC']),
    process_id=int(os.environ['PID_IDX']),
)
from sheeprl_tpu.cli import run
run(sys.argv[1:])
"""

REWARD_RE = re.compile(r"reward_env_\d+=(-?\d+(?:\.\d+)?)")


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--total-steps", type=int, default=12000)
    p.add_argument("--env-id", default="Pendulum-v1")
    p.add_argument("--log-base-dir", default=None)
    p.add_argument("--timeout", type=float, default=3600)
    args = p.parse_args()

    logdir = args.log_base_dir or tempfile.mkdtemp(prefix="sheeprl_tpu_declearn_")
    os.makedirs(logdir, exist_ok=True)
    cli = [
        "exp=sac_decoupled",
        "env=gym",
        f"env.id={args.env_id}",
        "env.sync_env=True",
        "env.num_envs=4",
        "env.capture_video=False",
        "buffer.memmap=False",
        f"algo.total_steps={args.total_steps}",
        "algo.learning_starts=400",
        "algo.replay_ratio=1",
        "algo.run_test=False",
        "checkpoint.save_last=False",
        "metric.log_level=1",
        "metric.log_every=50000",
        f"log_base_dir={logdir}",
    ]
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs, outs = [], []
    for pid in range(2):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
        env["COORD"] = f"127.0.0.1:{port}"
        env["NPROC"] = "2"
        env["PID_IDX"] = str(pid)
        env["PYTHONPATH"] = os.pathsep.join(q for q in (repo, env.get("PYTHONPATH")) if q)
        out = open(os.path.join(logdir, f"proc{pid}.out"), "w+")
        outs.append(out)
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", RUNNER, *cli],
                env=env,
                cwd=repo,
                stdout=out,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    import time as _time

    deadline = _time.monotonic() + args.timeout
    timed_out = False
    try:
        for p_ in procs:
            try:
                # one shared deadline across the group: sequential full-budget
                # waits would let a hung pair take 2x the stated --timeout
                p_.wait(timeout=max(1.0, deadline - _time.monotonic()))
            except subprocess.TimeoutExpired:
                timed_out = True
                break
    finally:
        for p_ in procs:
            if p_.poll() is None:
                p_.kill()
                p_.wait()
    failures = []
    rewards: list = []
    for pid, (p_, out) in enumerate(zip(procs, outs)):
        out.seek(0)
        text = out.read()
        if p_.returncode != 0:
            failures.append(f"--- process {pid} rc={p_.returncode} tail ---\n{text[-3000:]}")
        if pid == 0:
            rewards = [float(m) for m in REWARD_RE.findall(text)]
    if failures or timed_out:
        sys.stderr.write("\n".join(failures) + "\n")
        raise SystemExit(
            f"decoupled learning run {'timed out' if timed_out else 'failed'} "
            f"({len(failures)} process(es) non-zero) — tails above"
        )
    for out in outs:
        out.close()
    if len(rewards) < 10:
        raise SystemExit(f"only {len(rewards)} episodes logged — run longer")
    k = max(1, len(rewards) // 5)
    early, late = rewards[:k], rewards[-k:]
    best = max(rewards)
    print(
        json.dumps(
            {
                "workload": "sac_decoupled Pendulum-v1 (2-proc jax.distributed)",
                "episodes": len(rewards),
                "early_mean": round(sum(early) / len(early), 1),
                "late_mean": round(sum(late) / len(late), 1),
                "best": round(best, 1),
                "improved": sum(late) / len(late) > sum(early) / len(early),
                "logdir": logdir,
            }
        )
    )


if __name__ == "__main__":
    main()
