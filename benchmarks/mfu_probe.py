"""Single-chip MFU probe for the Dreamer-V3 fused train step.

What fraction of the chip's bf16 peak does one fused gradient step sustain,
at the bench shape and at real model sizes (XS..XL) — and is a slow step
device-busy time or dispatch/queue gaps? Not measured on the current code.

Method:

- The step is built EXACTLY as training builds it (``build_agent`` +
  ``make_train_fn`` from ``sheeprl_tpu.algos.dreamer_v3``) on a synthetic
  ``[T, B]`` batch — no env loop, no replay, pure step.
- FLOPs come from XLA's cost analysis of the compiled step
  (``utils.profiler.compiled_flops``).
- Device-busy time per step is estimated by CHAINING ``--chain`` steps
  (step i+1 consumes step i's params/opt outputs, so XLA executes them
  back-to-back) and timing dispatch→final materializing fetch:
  ``(wall - round trip) / chain`` isolates device time without a profiler
  UI. The closing ``np.asarray`` fetch is the sync; all intermediate
  outputs stay referenced until then.
- A wall-vs-device discrepancy check: the same chain timed twice plus the
  tiny-op round trip before/after. The probe prints both passes so the
  spread between them is attributable at read time.

Usage::

    python benchmarks/mfu_probe.py --sizes bench S --chain 8 --repeat 2
    python benchmarks/mfu_probe.py --sizes S --trace /tmp/dv3_trace  # adds a profiler trace
    # ISSUE-14 2-D sweep: (data, model) layouts x global batches to the
    # per-device ~B=300 knee, each probe recorded as a regress mfu cell
    python benchmarks/mfu_probe.py --sizes XL --mesh 1x4 2x4 --batch-size 64 128 256 304 --record

Writes one JSON line per (size, mesh, batch). ``--record`` appends each
probe to the run registry as a ``train:dreamer_v3:mfu_probe:<backend>x<n>p1:mfu``
cell — ``tools/regress.py`` floors TPU cells at 30% MFU (ISSUE 14 bar).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SIZES = {
    # the bench.py shape (tiny nets, 4 envs recipe): MFU here states how
    # much of the chip the bench workload can even use
    "bench": [
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.world_model.discrete_size=4",
        "algo.world_model.stochastic_size=4",
        "algo.world_model.encoder.cnn_channels_multiplier=2",
        "algo.world_model.recurrent_model.recurrent_state_size=8",
        "algo.world_model.transition_model.hidden_size=8",
        "algo.world_model.representation_model.hidden_size=8",
    ],
    "XS": ["algo=dreamer_v3_XS"],
    "S": ["algo=dreamer_v3_S"],
    "M": ["algo=dreamer_v3_M"],
    "L": ["algo=dreamer_v3_L"],
    "XL": ["algo=dreamer_v3_XL"],
}

from sheeprl_tpu.utils.profiler import PEAK_BF16_FLOPS as PEAK_BF16
from sheeprl_tpu.utils.profiler import tiny_op_rtt_seconds as tiny_rtt

# static base of every probe config (per-size deltas come from SIZES; batch
# and sequence length are appended per run)
BASE_OVERRIDES = [
    "exp=dreamer_v3",
    "env=dummy",
    "env.id=dummy_discrete",
    "env.screen_size=64",
    "env.num_envs=1",
    "algo.cnn_keys.encoder=[rgb]",
    "algo.mlp_keys.encoder=[]",
]


def build_step(size: str, batch_size: int, seq_len: int, mesh: tuple[int, int] = (1, 1)):
    """(train_fn, args tuple) at `size`, mirroring dreamer_v3.main's build.

    ``mesh=(d, m)`` places the step on a 2-D ``(data, model)`` mesh over
    ``d*m`` devices: params/opt model-sharded (GSPMD train path), the
    ``[T, B]`` batch split over the data axis — ``batch_size`` is GLOBAL.
    The default ``(1, 1)`` keeps the original single-chip probe."""
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import make_train_fn
    from sheeprl_tpu.ops.optim import build_tx
    from sheeprl_tpu.config.compose import compose
    from sheeprl_tpu.ops.math import init_moments
    from sheeprl_tpu.parallel.fabric import Fabric

    overrides = [
        *BASE_OVERRIDES,
        *SIZES[size],
        f"algo.per_rank_batch_size={batch_size}",
        f"algo.per_rank_sequence_length={seq_len}",
    ]
    cfg = compose("config", overrides)
    d, m = mesh
    if (d, m) == (1, 1):
        fabric = Fabric(devices=1, precision=str(cfg.fabric.get("precision", "fp32")))
    else:
        fabric = Fabric(
            devices=d * m,
            precision=str(cfg.fabric.get("precision", "fp32")),
            mesh_axes=("data", "model") if m > 1 else ("data",),
            mesh_shape=(d, m) if m > 1 else (d,),
        )

    from sheeprl_tpu.envs import make_env

    env = make_env(cfg, cfg.seed, 0, None, "train", vector_env_idx=0)()
    observation_space, action_space = env.observation_space, env.action_space
    env.close()
    actions_dim = (action_space.n,)

    wm, wm_params, actor, actor_params, critic, critic_params, target_critic_params, _player = build_agent(
        fabric, actions_dim, False, cfg, observation_space, None, None, None, None
    )

    world_tx = build_tx(cfg.algo.world_model.optimizer, cfg.algo.world_model.clip_gradients)
    actor_tx = build_tx(cfg.algo.actor.optimizer, cfg.algo.actor.clip_gradients)
    critic_tx = build_tx(cfg.algo.critic.optimizer, cfg.algo.critic.clip_gradients)
    # shard_params co-shards Adam moments with their params on a model-axis
    # mesh and replicates on a 1-D one (no topology check at the call site)
    world_opt = fabric.shard_params(world_tx.init(jax.device_get(wm_params)))
    actor_opt = fabric.shard_params(actor_tx.init(jax.device_get(actor_params)))
    critic_opt = fabric.shard_params(critic_tx.init(jax.device_get(critic_params)))
    moments_state = init_moments()
    if fabric.world_size > 1:
        moments_state = fabric.replicate(moments_state)

    train_fn = make_train_fn(
        fabric, wm, actor, critic, world_tx, actor_tx, critic_tx, cfg, False, actions_dim
    )

    T, B, A = seq_len, batch_size, int(np.sum(actions_dim))
    if fabric.world_size > 1 and B % max(1, fabric.data_parallel_size) != 0:
        raise SystemExit(
            f"global batch {B} not divisible by data={fabric.data_parallel_size}"
        )
    rng = np.random.default_rng(0)
    data = {
        # NHWC — this repo's native pixel layout (envs/dummy.py:4)
        "rgb": jnp.asarray(rng.integers(0, 255, (T, B, 64, 64, 3), np.uint8)),
        "actions": jnp.asarray(rng.standard_normal((T, B, A)), jnp.float32),
        "rewards": jnp.asarray(rng.standard_normal((T, B, 1)), jnp.float32),
        "terminated": jnp.zeros((T, B, 1), jnp.float32),
        "truncated": jnp.zeros((T, B, 1), jnp.float32),
        "is_first": jnp.zeros((T, B, 1), jnp.float32),
    }
    key = jax.random.PRNGKey(0)
    if fabric.world_size > 1:
        # commit batch over the data axis, key replicated — matches the train
        # loop's placements so the probe measures the trained layout
        data = jax.device_put(data, fabric.sharding(None, fabric.data_axis))
        key = fabric.replicate(key)
    args = (
        wm_params,
        actor_params,
        critic_params,
        target_critic_params,
        world_opt,
        actor_opt,
        critic_opt,
        moments_state,
        data,
        key,
    )
    return train_fn, args


def measure(
    size: str,
    batch_size: int,
    seq_len: int,
    chain: int,
    repeat: int,
    trace: str | None,
    mesh: tuple[int, int] = (1, 1),
):
    import jax

    from sheeprl_tpu.utils.profiler import compiled_flops

    d, m = mesh
    rec = {
        "size": size,
        "batch_size": batch_size,
        "sequence_length": seq_len,
        "chain": chain,
        "mesh": f"{d}x{m}",
        "device": jax.devices()[0].device_kind,
    }
    rtt0 = tiny_rtt()
    train_fn, args = build_step(size, batch_size, seq_len, mesh=mesh)

    def run_chain(args):
        # step i+1 consumes step i's outputs — XLA executes back-to-back.
        # keep every output referenced until the closing fetch
        keep = []
        wm_p, a_p, c_p, tc_p, w_o, a_o, c_o, mom, data, key = args
        t0 = time.perf_counter()
        for i in range(chain):
            key = jax.random.fold_in(key, i)
            wm_p, a_p, c_p, w_o, a_o, c_o, mom, metrics = train_fn(
                wm_p, a_p, c_p, tc_p, w_o, a_o, c_o, mom, data, key
            )
            keep.append(metrics)
        np.asarray(jax.device_get(keep[-1]))  # the only real sync
        dt = time.perf_counter() - t0
        return dt, (wm_p, a_p, c_p, tc_p, w_o, a_o, c_o, mom, data, key)

    # compile + warm outside any timing
    t0 = time.perf_counter()
    _, args = run_chain(args)
    rec["compile_plus_first_chain_s"] = round(time.perf_counter() - t0, 1)

    passes = []
    clamped = False
    for _ in range(max(1, repeat)):
        dt, args = run_chain(args)
        # on an RTT-dominated chain (tiny step x jittery link) the subtraction
        # can go non-positive: the chain is unmeasurable, not free
        net = dt - rtt0
        if net <= 0:
            clamped = True
            net = chain * 1e-6
        passes.append(round(net / chain * 1e3, 3))
    rec["step_ms_passes"] = passes
    step_s = min(passes) / 1e3
    rec["step_ms"] = min(passes)
    rtt1 = tiny_rtt()
    rec["rtt_ms_before_after"] = [round(rtt0 * 1e3, 1), round(rtt1 * 1e3, 1)]

    flops = compiled_flops(train_fn, *args)
    if flops:
        rec["flops_per_step"] = flops
    if clamped:
        # device time drowned in link jitter — no throughput claim possible;
        # raise --chain until the chain dominates the RTT
        rec["unmeasurable"] = "chain time <= RTT jitter; raise --chain"
    elif flops:
        rec["achieved_tflops"] = round(flops / step_s / 1e12, 2)
        peak = PEAK_BF16.get(rec["device"])
        if peak:
            # cost analysis reports the whole (pre-partition) module, so the
            # denominator is the aggregate peak of every chip in the mesh
            rec["mfu"] = round(flops / step_s / (peak * d * m), 4)

    if trace:
        with jax.profiler.trace(f"{trace}/{size}"):
            _, args = run_chain(args)
        rec["trace_dir"] = f"{trace}/{size}"
    return rec


def _record_cell(rec: dict, runs_path: str | None) -> None:
    """Append an obs-registry record so ``tools/regress.py`` tracks the probe
    as a ``train:dreamer_v3:<env>:<backend>x<n>p1:mfu`` cell (the ISSUE-14
    MFU gate). ``mfu`` falls back to 0.0 on devices missing from the bf16
    peak table (CPU virtual-mesh cells — tracked for continuity, never
    floored; the 30% bar applies to TPU backends only)."""
    import jax

    from sheeprl_tpu.obs.registry import SCHEMA_VERSION, append_run_record, runs_jsonl_path

    record = {
        "schema": SCHEMA_VERSION,
        "t": time.time(),
        "kind": "train",
        "algo": "dreamer_v3",
        "env": "mfu_probe",
        "backend": jax.default_backend(),
        "local_device_count": jax.local_device_count(),
        "process_count": jax.process_count(),
        "variant": "mfu",
        "outcome": "completed",
        "mfu": rec.get("mfu", 0.0),
        "mfu_measured": "mfu" in rec,
        "size": rec["size"],
        "mesh": rec["mesh"],
        "batch_size": rec["batch_size"],
        "step_ms": rec.get("step_ms"),
    }
    path = runs_jsonl_path(None, runs_path)
    if path is None:
        print("run registry disabled (SHEEPRL_TPU_RUNS_JSONL empty); record dropped", flush=True)
        return
    append_run_record(record, path)
    print(f"recorded mfu cell -> {path}", flush=True)


def _parse_meshes(specs: list[str]) -> list[tuple[int, int]]:
    out = []
    for item in specs:
        d, _, m = item.strip().partition("x")
        out.append((int(d), int(m) if m else 1))
    return out


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--sizes", nargs="+", default=["bench", "S"], choices=list(SIZES))
    p.add_argument("--batch-size", type=int, nargs="+", default=[16])
    p.add_argument("--seq-len", type=int, default=64)
    p.add_argument("--chain", type=int, default=8)
    p.add_argument("--repeat", type=int, default=2)
    p.add_argument("--trace", default=None, help="jax.profiler trace output dir")
    p.add_argument(
        "--mesh",
        nargs="+",
        default=["1x1"],
        help="DxM (data x model) mesh layouts to sweep, e.g. --mesh 1x1 2x4 1x4",
    )
    p.add_argument(
        "--record",
        nargs="?",
        const="",
        default=None,
        metavar="RUNS_JSONL",
        help="append an obs-registry record per probe (regress mfu cell); "
        "optional path overrides the default RUNS.jsonl",
    )
    args = p.parse_args()
    for size in args.sizes:
        for mesh in _parse_meshes(args.mesh):
            for batch in args.batch_size:
                rec = measure(
                    size, batch, args.seq_len, args.chain, args.repeat, args.trace, mesh=mesh
                )
                print(json.dumps(rec), flush=True)
                if args.record is not None:
                    _record_cell(rec, args.record or None)


if __name__ == "__main__":
    main()
