"""Proof that the main path starts on the chip: ``python chip_smoke.py``.

One process, no arguments, one TPU chip. It drives the flagship recipe
(``exp=dreamer_v3_dmc_walker_walk``: Dreamer-V3 S, bf16-mixed, 4 envs, replay
ratio 0.5, 64x64x3 pixels, batch 16 x sequence 64 — widths untouched) through
the two normal entry points, in this order:

1. ``ring``    the replay ring's write and sequence gather compiled (nothing
               allocated, nothing run) at the benchmark cells' ring sizes and
               at the recipe's own 500,000 frames: no program may copy or
               relayout the ring;
2. ``kernel``  the compiled (Mosaic, not interpreted) Pallas RSSM step at S
               width against ``ops.pallas_gru.reference_step``, forward and
               gradient;
3. ``train``   ``sheeprl_tpu.cli.run``: random-action prefill, then gradient
               steps past the recompile watchdog's warm point, two
               checkpoints;
4. ``eval``    ``sheeprl_tpu.cli.evaluation`` of the last checkpoint.

Every phase raises on the first thing that is not right, so the script exits
non-zero; nothing here catches a phase's failure. It has no CPU mode: without
a ``tpu`` device it fails before any phase. The LAST line of stdout is the
contract's one JSON object; everything worth reading is printed before it.

``python chip_smoke.py --chips 4`` runs the multi-chip path and what it is
compared with, and no other phase (see :func:`main_four_chips`).

The phases are functions of their sizes so that ``tests/test_chip_smoke.py``
can call the same code tiny on the CPU.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import re
import sys
import time
from typing import Any, Dict, List, Sequence

REPO = os.path.dirname(os.path.abspath(__file__))

#: the flagship recipe with its pixel source swapped for the dummy env
#: (dm_control cannot render where there is no EGL and no network)
RECIPE = (
    "exp=dreamer_v3_dmc_walker_walk",
    "env=dummy",
    "env.id=dummy_continuous",
    "env.capture_video=False",
    "metric.telemetry.enabled=True",
)

#: Dreamer-V3 S RSSM step: 32x32 latents + a 6-d action in, dense 512, GRU 512
S_KERNEL = {"in_dim": 32 * 32 + 6, "dense": 512, "hidden": 512}

#: replay rings of 64x64x3 frames as ``(envs, slots an env, action width)``:
#: the two benchmark cells' (``buffer.size`` 250,000) and the walker recipe's
#: own 500,000 frames over its 4 envs (6.4 GB, which a program that copied
#: the ring could not fit into the chip's 16 GB)
RINGS = {
    "dv3_S_walker.train": (4, 62_500, 6),
    "dv3_XL_crafter.train": (1, 250_000, 17),
    "walker recipe, buffer.size=500000": (4, 125_000, 6),
}


def say(msg: str) -> None:
    print(msg, flush=True)


def require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def cache_watch():
    """Persistent-compilation-cache hits and misses of this whole process:
    the program's own ``CompileWatchdog`` with no event sink, because eval
    has no telemetry of its own to count them."""
    from sheeprl_tpu.obs.recompile import CompileWatchdog

    watch = CompileWatchdog(lambda *_, **__: None)
    watch.start()
    return watch


def cache_since(watch, mark: Sequence[int] = (0, 0)) -> Dict[str, int]:
    return {"hits": watch.cache_hits - mark[0], "misses": watch.cache_misses - mark[1]}


# --------------------------------------------------------------------------- #
# phase: kernel
# --------------------------------------------------------------------------- #


def ring_relayouts(hlo: str, slots: int) -> List[str]:
    """Instructions of an optimised HLO module that produce an array with a
    ring's slot dimension other than in place: anything but the parameters,
    the (fused) ``dynamic-update-slice``s and the tuples that pass them on.
    A ``copy`` here is a relayout of the whole ring."""
    found = []
    for line in hlo.splitlines():
        m = re.match(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.+?) ([\w\-]+)\(", line)
        if m is None or not re.search(rf"[\[,]{slots}[,\]]", m.group(2)):
            continue
        name, shape, op = m.groups()
        in_place = op in ("parameter", "dynamic-update-slice", "tuple", "get-tuple-element", "bitcast")
        if not in_place and not (op == "fusion" and "dynamic-update-slice" in name):
            found.append(f"{op} {name} {shape}")
    return found


def describe_ring_programs(
    n_envs: int, slots: int, action_dim: int, *, frame=(64, 64, 3), batch: int = 16, sequence: int = 64, device=None
) -> Dict[str, Dict[str, Any]]:
    """``ring_write`` and ``ring_gather_sequences`` of a Dreamer-style ring,
    compiled from shapes alone: what ``memory_analysis()`` and the optimised
    HLO say about each (``device``: the programs' target, default the first
    attached one)."""
    import jax
    import numpy as np

    from sheeprl_tpu.data.device_buffer import lower_ring_programs

    step = {
        "rgb": jax.ShapeDtypeStruct((1, n_envs, *frame), np.uint8),
        "actions": jax.ShapeDtypeStruct((1, n_envs, action_dim), np.float32),
        **{k: jax.ShapeDtypeStruct((1, n_envs, 1), np.float32) for k in ("rewards", "terminated", "truncated", "is_first")},
    }
    out = {}
    for name, lowered in lower_ring_programs(step, slots, n_envs, batch, sequence, device=device).items():
        compiled = lowered.compile()
        memory = compiled.memory_analysis()
        out[name] = {
            "argument_bytes": int(memory.argument_size_in_bytes),
            "temp_bytes": int(memory.temp_size_in_bytes),
            "alias_bytes": int(memory.alias_size_in_bytes),
            "relayouts": ring_relayouts(compiled.as_text(), slots + 1),
        }
    return out


def phase_ring_programs(rings: Dict[str, Sequence[int]], *, max_temp_bytes: int, device=None) -> Dict[str, Any]:
    """No program of the replay ring copies it: at every size in ``rings``
    the write and the gather compile for the chip, hold no instruction of
    the ring's shape but the in-place updates, and ask for under
    ``max_temp_bytes`` of temporaries; the write aliases the whole ring."""
    summary = {}
    for label, (n_envs, slots, action_dim) in rings.items():
        summary[label] = programs = describe_ring_programs(n_envs, slots, action_dim, device=device)
        for name, got in programs.items():
            say(
                f"[ring] {label} ({n_envs} x {slots + 1} slots) {name}: arguments {got['argument_bytes']} B, "
                f"temp_size_in_bytes {got['temp_bytes']}, aliased {got['alias_bytes']} B, "
                f"ring-shaped copies {got['relayouts'] or 'none'}"
            )
            require(not got["relayouts"], f"{name} at {label} relayouts the ring: {got['relayouts']}")
            require(got["temp_bytes"] < max_temp_bytes, f"{name} at {label} asks for {got['temp_bytes']} B of temporaries")
        write = programs["ring_write"]
        require(write["alias_bytes"] >= write["argument_bytes"] - (1 << 20), f"ring_write at {label} does not alias the ring it is given")
    return summary


def phase_kernel(
    *,
    in_dim: int,
    dense: int,
    hidden: int,
    batches: Sequence[int],
    fwd_atol: float,
    grad_rtol: float,
    interpret: bool = False,
    seed: int = 0,
) -> Dict[str, Any]:
    """``fused_recurrent_step`` against ``reference_step`` on seeded inputs,
    forward and gradient with respect to all nine inputs. The reference runs
    at ``highest`` matmul precision (XLA's default rounds fp32 operands to
    bf16 on the MXU); the new state is bounded by 1 in magnitude, so
    ``fwd_atol`` is absolute, and ``grad_rtol`` is relative to the largest
    entry of each reference gradient. ``interpret`` is only for the CPU test;
    compiled, the lowered program must hold the Mosaic custom call."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu.ops.pallas_gru import fits_vmem, fused_recurrent_step, reference_step

    require(fits_vmem(in_dim, dense, hidden, jnp.float32), "the S step does not fit the kernel's VMEM gate")
    out: Dict[str, Any] = {"cases": []}
    for batch in batches:
        ks = jax.random.split(jax.random.PRNGKey(seed + batch), 9)
        args = (
            jax.random.normal(ks[0], (batch, in_dim), jnp.float32),
            jnp.tanh(jax.random.normal(ks[1], (batch, hidden), jnp.float32)),
            jax.random.normal(ks[2], (in_dim, dense), jnp.float32) / math.sqrt(in_dim),
            0.1 * jax.random.normal(ks[3], (dense,), jnp.float32),
            1.0 + 0.1 * jax.random.normal(ks[4], (dense,), jnp.float32),
            0.1 * jax.random.normal(ks[5], (dense,), jnp.float32),
            jax.random.normal(ks[6], (hidden + dense, 3 * hidden), jnp.float32) / math.sqrt(hidden + dense),
            1.0 + 0.1 * jax.random.normal(ks[7], (3 * hidden,), jnp.float32),
            0.1 * jax.random.normal(ks[8], (3 * hidden,), jnp.float32),
        )
        argnums = tuple(range(9))

        def loss_of(step):
            # a seeded projection, so every output column has its own weight
            proj = jax.random.normal(jax.random.PRNGKey(seed + 7), (hidden,), jnp.float32)

            def loss(*a):
                h = step(*a)
                return jnp.sum(h * proj), h

            return jax.jit(jax.value_and_grad(loss, argnums=argnums, has_aux=True))

        with jax.default_matmul_precision("highest"):
            fused = loss_of(lambda *a: fused_recurrent_step(*a, interpret=interpret))
            if not interpret:
                hlo = fused.lower(*args).compile().as_text()
                require("tpu_custom_call" in hlo, "the compiled kernel program holds no tpu_custom_call")
            (_, h_fused), g_fused = fused(*args)
            (_, h_ref), g_ref = loss_of(reference_step)(*args)
        fwd_err = float(jnp.max(jnp.abs(h_fused - h_ref)))
        grad_err = max(
            float(jnp.max(jnp.abs(gf - gr)) / (jnp.max(jnp.abs(gr)) + 1e-30)) for gf, gr in zip(g_fused, g_ref)
        )
        require(bool(np.isfinite(np.asarray(h_fused)).all()), f"kernel output not finite at batch {batch}")
        require(fwd_err <= fwd_atol, f"kernel forward off by {fwd_err:.3e} > {fwd_atol} at batch {batch}")
        require(grad_err <= grad_rtol, f"kernel gradient off by {grad_err:.3e} > {grad_rtol} at batch {batch}")
        case = {"batch": batch, "fwd_max_abs_err": fwd_err, "grad_max_rel_err": grad_err}
        out["cases"].append(case)
        say(f"[kernel] in={in_dim} dense={dense} hidden={hidden} {case} (tolerance fwd {fwd_atol}, grad {grad_rtol})")
    out["mode"] = "interpret" if interpret else "mosaic"
    return out


# --------------------------------------------------------------------------- #
# phase: train
# --------------------------------------------------------------------------- #


def _last_record(runs_jsonl: str, kind: str) -> Dict[str, Any]:
    from sheeprl_tpu.obs.registry import read_run_records

    records = [r for r in read_run_records(runs_jsonl) if r.get("kind") == kind]
    require(bool(records), f"no {kind!r} record in {runs_jsonl}")
    return records[-1]


def _events(path: str) -> List[Dict[str, Any]]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _all_finite(tree: Any) -> bool:
    import jax
    import numpy as np

    return all(bool(np.isfinite(np.asarray(leaf)).all()) for leaf in jax.tree.leaves(tree))


def phase_train(
    workdir: str,
    *,
    platform: str,
    total_steps: int,
    learning_starts: int,
    buffer_size: int,
    checkpoint_every: int,
    n_devices: int = 1,
    min_gradient_steps: int = 9,
    expect_warm: bool = True,
    overrides: Sequence[str] = (),
) -> Dict[str, Any]:
    """The recipe through ``sheeprl_tpu.cli.run`` with only the smoke's cuts,
    then everything the run left behind is read back: the run record, the
    telemetry stream and the checkpoints. Returns what it established."""
    import jax
    import numpy as np

    from sheeprl_tpu.cli import run
    from sheeprl_tpu.utils.checkpoint import load_checkpoint

    os.makedirs(workdir, exist_ok=True)
    runs_jsonl = os.path.join(workdir, "RUNS.jsonl")
    say(
        f"[train] cut for the smoke: algo.total_steps={total_steps} (recipe 500000), "
        f"algo.learning_starts={learning_starts} (recipe 1300), buffer.size={buffer_size} (recipe 500000), "
        f"checkpoint.every={checkpoint_every}, metric.log_every=4; widths, batch 16x64, precision, "
        "player_device/train_device/buffer.device/recurrent_model.fused stay as the recipe has them"
    )
    args = [
        *RECIPE,
        f"algo.total_steps={total_steps}",
        f"algo.learning_starts={learning_starts}",
        f"buffer.size={buffer_size}",
        f"checkpoint.every={checkpoint_every}",
        "metric.log_every=4",
        f"metric.telemetry.runs_jsonl={runs_jsonl}",
        f"log_base_dir={os.path.join(workdir, 'logs')}",
        *overrides,
    ]
    t0 = time.perf_counter()
    run(args)
    wall = time.perf_counter() - t0

    rec = _last_record(runs_jsonl, "train")
    require(rec["outcome"] == "completed", f"train outcome {rec['outcome']!r}: {rec.get('error')}")
    say(
        f"[train] run record: backend={rec['backend']} device_kind={rec['device_kind']} "
        f"local_device_count={rec['local_device_count']} native_gather={rec['native_gather']!r}"
    )
    require(rec["backend"] == platform, f"run record backend {rec['backend']!r}, expected {platform!r}")
    require(rec["local_device_count"] >= n_devices, f"run saw {rec['local_device_count']} devices")

    resolved = rec["resolved"]
    for name in ("player_device", "buffer_device"):
        say(f"[train] auto: {name} -> {resolved[name]}")
    placed = resolved["state_devices"]["value"]
    say(f"[train] .devices() of the state's leaves: {placed}")
    for name in ("params", "optimizer", "replay", "player_params"):
        devices = placed[name]
        require(
            bool(devices) and all(d.split(":")[0] == platform for d in devices),
            f"{name} live on {devices}, not on {platform!r} devices",
        )
    for name in ("params", "optimizer", "replay"):
        require(len(placed[name]) == n_devices, f"{name} span {placed[name]}, expected {n_devices} devices")

    steps = rec["train_gradient_steps"]
    require(steps >= min_gradient_steps, f"only {steps} gradient steps were taken")
    losses = {k: v for k, v in rec["final_metrics"].items() if k.startswith(("Loss/", "State/", "Grads/"))}
    require(bool(losses) and all(math.isfinite(v) for v in losses.values()), f"losses not finite: {losses}")
    say(f"[train] {steps} gradient steps in {rec['train_windows']} windows; final losses finite: {losses}")

    events = _events(rec["telemetry_jsonl"])
    warm = [e for e in events if e["event"] == "warm"]
    if expect_warm:
        require(len(warm) == 1, "the recompile watchdog's warm point was never reached")
        after = [e for e in events if e["event"] == "heartbeat" and e["t"] > warm[0]["t"]]
        require(len(after) >= 2, "the run ended right at the warm point: nothing ran after it")
        say(
            f"[train] warm point at policy step {warm[0]['step']} after {warm[0]['compiles']} compiles; "
            f"{len(after)} heartbeats after it"
        )
    require(rec["recompiles"] == 0, f"{rec['recompiles']} recompiles after the warm point")
    compile_s = sum(e["dur"] for e in events if e["event"] == "compile")
    big = sorted((e for e in events if e["event"] == "compile"), key=lambda e: -e["dur"])[:3]
    windows = [
        e["window_train_time"] / e["window_train_gradient_steps"]
        for e in events
        if e["event"] == "heartbeat" and e.get("window_train_gradient_steps")
    ]
    first_window_s, step_s = windows[0], float(np.median(windows[1:]))
    say(
        f"[train] compiles_total={rec['compiles_total']} (deliberate {rec['deliberate_compiles']}), recompiles=0; "
        f"compile+lower {compile_s:.1f}s of {wall:.1f}s wall; largest: "
        + ", ".join(f"{e['name']}:{e['phase']} {e['dur']:.1f}s" for e in big)
    )
    say(
        f"[train] first train window {first_window_s:.2f}s per gradient step (compile inside), "
        f"median of the {len(windows) - 1} later windows {step_s * 1e3:.1f} ms per gradient step"
    )
    say(
        f"[train] compile cache at {jax.config.jax_compilation_cache_dir}: hits={rec['compile_cache_hits']} "
        f"misses={rec['compile_cache_misses']}"
    )

    ckpts = sorted(glob.glob(os.path.join(workdir, "logs", "**", "*.ckpt"), recursive=True), key=os.path.getmtime)
    require(len(ckpts) >= 2, f"expected two checkpoints to compare, found {ckpts}")
    first, last = load_checkpoint(ckpts[0]), load_checkpoint(ckpts[-1])
    for name in ("world_model", "actor", "critic"):
        require(_all_finite(last[name]), f"{name} params of the last checkpoint are not finite")
        moved = max(
            float(np.max(np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))))
            for a, b in zip(jax.tree.leaves(first[name]), jax.tree.leaves(last[name]))
        )
        require(moved > 0.0, f"{name} params did not change between {ckpts[0]} and {ckpts[-1]}")
        say(f"[train] {name}: finite, max |change| between the two checkpoints {moved:.3e}")
    require(_all_finite(last["world_optimizer"]), "world optimizer state of the last checkpoint is not finite")
    say(f"[train] checkpoints: {[os.path.relpath(c, workdir) for c in ckpts]}")
    return {
        "record": rec,
        "checkpoint": ckpts[-1],
        "wall_s": wall,
        "compile_s": compile_s,
        "step_ms": step_s * 1e3,
        "first_window_s": first_window_s,
    }


# --------------------------------------------------------------------------- #
# phase: eval
# --------------------------------------------------------------------------- #


def phase_eval(checkpoint: str, workdir: str, *, platform: str) -> Dict[str, Any]:
    """``sheeprl_tpu.cli.evaluation`` of ``checkpoint`` (the second normal
    entry point), then its run record and where ``auto`` put the player."""
    import jax

    from sheeprl_tpu.cli import evaluation
    from sheeprl_tpu.parallel.fabric import dispatch_roundtrip_seconds, resolve_player_device

    t0 = time.perf_counter()
    evaluation([f"checkpoint_path={checkpoint}"])
    wall = time.perf_counter() - t0
    rec = _last_record(os.path.join(workdir, "RUNS.jsonl"), "eval")
    require(rec["outcome"] == "completed", f"eval outcome {rec['outcome']!r}: {rec.get('error')}")
    # the same resolver build_agent just ran in this process (the probe is
    # cached per process), asked again so that its answer can be printed
    device = resolve_player_device("auto")
    where = jax.devices()[0] if device is None else device
    say(
        f"[eval] completed in {wall:.1f}s; player_device auto -> {where.platform}:{where.id} "
        f"(dispatch round trip {dispatch_roundtrip_seconds() * 1e3:.3f} ms, threshold 5 ms)"
    )
    require(where.platform == platform, f"the eval player ran on {where.platform!r}, not {platform!r}")
    return {"record": rec, "wall_s": wall}


# --------------------------------------------------------------------------- #
# four chips: the mesh paths against the same program on one device
# --------------------------------------------------------------------------- #


def _compose_cfg(overrides: Sequence[str]):
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.utils.utils import dotdict

    return dotdict(compose("config", [*RECIPE, *overrides]))


def _seeded_batch(cfg, seq_len: int, batch: int, action_dim: int, seed: int) -> Dict[str, Any]:
    """One ``[T, B, ...]`` sequence batch in the replay's layout, from a seed."""
    import numpy as np

    rng = np.random.default_rng(seed)
    size = int(cfg.env.screen_size)
    is_first = np.zeros((seq_len, batch, 1), np.float32)
    is_first[0] = 1.0
    return {
        "rgb": rng.integers(0, 256, (seq_len, batch, size, size, 3), dtype=np.uint8),
        "actions": rng.normal(size=(seq_len, batch, action_dim)).astype(np.float32),
        "rewards": rng.normal(size=(seq_len, batch, 1)).astype(np.float32),
        "terminated": np.zeros((seq_len, batch, 1), np.float32),
        "truncated": np.zeros((seq_len, batch, 1), np.float32),
        "is_first": is_first,
    }


def _train_setup(cfg, fabric):
    """Agent, optimizers and their state on ``fabric``, as ``dreamer_v3.main``
    builds them (same seed, so every fabric starts from the same weights)."""
    import gymnasium as gym
    import jax
    import numpy as np

    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.ops.math import init_moments
    from sheeprl_tpu.ops.optim import build_tx

    size = int(cfg.env.screen_size)
    obs_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (size, size, 3), np.uint8)})
    actions_dim = (2,)
    wm, wm_p, actor, actor_p, critic, critic_p, target_p, _ = build_agent(fabric, actions_dim, True, cfg, obs_space)
    txs = (
        build_tx(cfg.algo.world_model.optimizer, cfg.algo.world_model.clip_gradients),
        build_tx(cfg.algo.actor.optimizer, cfg.algo.actor.clip_gradients),
        build_tx(cfg.algo.critic.optimizer, cfg.algo.critic.clip_gradients),
    )
    opts = tuple(
        fabric.shard_params(tx.init(jax.device_get(p))) for tx, p in zip(txs, (wm_p, actor_p, critic_p))
    )
    state = (wm_p, actor_p, critic_p, target_p, *opts, fabric.replicate(init_moments()))
    return (wm, actor, critic), txs, state, actions_dim


def _agreement(name: str, got, want, start, *, loss_rtol: float, min_cosine: float) -> Dict[str, float]:
    """Losses within ``loss_rtol`` of each other, and the two parameter
    updates (``new - start``) pointing the same way: Adam's first step moves
    every coordinate by about the learning rate, so a bound on ``|got -
    want|`` would hold for any two updates; their cosine does not."""
    import jax
    import numpy as np

    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import METRIC_ORDER

    m_got, m_want = np.asarray(got[-1], np.float64), np.asarray(want[-1], np.float64)
    require(bool(np.isfinite(m_got).all() and np.isfinite(m_want).all()), f"{name}: losses not finite")
    rel = np.abs(m_got - m_want) / np.maximum(np.abs(m_want), 1e-6)
    worst = {METRIC_ORDER[i]: float(f"{rel[i]:.3e}") for i in np.argsort(-rel)[:3]}
    loss_idx = [i for i, k in enumerate(METRIC_ORDER) if k.startswith("Loss/")]
    say(f"[{name}] metrics on the mesh {dict(zip(METRIC_ORDER, (round(float(v), 4) for v in m_got)))}")
    say(f"[{name}] largest relative differences to one device: {worst} (Loss/* tolerance {loss_rtol})")
    require(float(rel[loss_idx].max()) <= loss_rtol, f"{name}: a loss differs by {rel[loss_idx].max():.3e}")
    def update_of(new, old):
        return np.concatenate(
            [
                (np.asarray(a, np.float64) - np.asarray(b, np.float64)).ravel()
                for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old))
            ]
        )

    cosines = {}
    for i, label in enumerate(("world_model", "actor", "critic")):
        d_got, d_want = update_of(got[i], start[i]), update_of(want[i], start[i])
        require(float(np.abs(d_want).max()) > 0.0, f"{name}: the one-device {label} update is zero")
        cosines[label] = float(d_got @ d_want / (np.linalg.norm(d_got) * np.linalg.norm(d_want)))
        require(cosines[label] >= min_cosine, f"{name}: {label} update cosine {cosines[label]:.4f} < {min_cosine}")
    say(f"[{name}] cosine of the parameter updates, mesh against one device: {cosines} (at least {min_cosine})")
    return {"max_loss_rel": float(rel[loss_idx].max()), **cosines}


def _compile_and_describe(name: str, fabric, train_fn, state, batch, key):
    """Compile the step once; say where the batch shards sit, which
    collectives the program holds and what it needs on each device; return
    the executable for the caller to run."""
    compiled = train_fn.lower(*state, batch, key).compile()
    hlo = compiled.as_text()
    shard_devices = sorted({str(s.device) for s in batch["rgb"].addressable_shards})
    say(f"[{name}] batch sharding {batch['rgb'].sharding.spec} over {len(shard_devices)} devices: {shard_devices}")
    require(len(shard_devices) == fabric.world_size, f"{name}: batch shards sit on {shard_devices}")
    collectives = {c: hlo.count(f" {c}(") + hlo.count(f" {c}-start(") for c in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all")}
    say(f"[{name}] collectives in the compiled step: {collectives}")
    require(collectives["all-reduce"] > 0, f"{name}: the compiled step holds no all-reduce")
    mem = compiled.memory_analysis()
    say(
        f"[{name}] per-device memory_analysis: arguments {mem.argument_size_in_bytes / 2**20:.1f} MiB, "
        f"outputs {mem.output_size_in_bytes / 2**20:.1f} MiB, temporaries {mem.temp_size_in_bytes / 2**20:.1f} MiB"
    )
    return compiled


def phase_data_parallel(cfg, *, n_devices: int, loss_rtol: float, min_cosine: float, seed: int = 0) -> Dict[str, float]:
    """One seeded batch through the jitted train step on the ``[n]`` data
    mesh, and through the SAME per-shard program on one device:
    ``jax.vmap(local_train, axis_name=data)`` gives every collective and
    every per-shard key fold its mesh meaning, so the two differ by
    reduction order only."""
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import make_train_fn, make_train_step
    from sheeprl_tpu.parallel.fabric import Fabric, tree_devices

    fabric = Fabric(devices=n_devices, precision=str(cfg.fabric.precision))
    (wm, actor, critic), txs, state, actions_dim = _train_setup(cfg, fabric)
    seq_len, per_rank = int(cfg.algo.per_rank_sequence_length), int(cfg.algo.per_rank_batch_size)
    host_batch = _seeded_batch(cfg, seq_len, per_rank * n_devices, sum(actions_dim), seed)
    key = jax.random.PRNGKey(seed)
    host_state = jax.device_get(state)  # the step donates the optimizer state
    say(f"[dp{n_devices}] params on {tree_devices(state[0])}; recurrent model {_rssm_backend(wm, state[0])}")

    train_fn = make_train_fn(fabric, wm, actor, critic, *txs, cfg, True, actions_dim)
    batch = jax.device_put(host_batch, fabric.sharding(None, fabric.data_axis))
    step = _compile_and_describe(f"dp{n_devices}", fabric, train_fn, state, batch, key)
    got = jax.device_get(step(*state, batch, key))

    # the same SPMD program on ONE device
    one = jax.devices()[0]
    state1 = jax.device_put(host_state, one)
    local_train, uses_shard_map = make_train_step(fabric, wm, actor, critic, *txs, cfg, True, actions_dim)
    require(uses_shard_map, "the data-parallel step is not the shard_map program")
    split = {k: v.reshape(seq_len, n_devices, per_rank, *v.shape[2:]) for k, v in host_batch.items()}
    emulated = jax.jit(
        jax.vmap(local_train, in_axes=(None,) * 8 + (1, None), out_axes=0, axis_name=fabric.data_axis)
    )
    want = emulated(*state1, jax.device_put(split, one), jax.device_put(key, one))
    want = jax.device_get(jax.tree.map(lambda x: x[0], want))
    say(f"[dp{n_devices}] compared with vmap(local_train, axis_name={fabric.data_axis!r}) on {one}")
    return _agreement(f"dp{n_devices}", got, want, host_state, loss_rtol=loss_rtol, min_cosine=min_cosine)


def phase_model_parallel(cfg, *, mesh_shape: Sequence[int], loss_rtol: float, min_cosine: float, seed: int = 0) -> Dict[str, float]:
    """The same on a ``(data, model)`` mesh: there the step is ONE global
    program that GSPMD partitions, so what it is compared with is that
    program on a one-device fabric, same batch, same key."""
    import jax

    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import make_train_fn
    from sheeprl_tpu.parallel.fabric import Fabric

    n = int(mesh_shape[0] * mesh_shape[1])
    name = f"mesh{mesh_shape[0]}x{mesh_shape[1]}"
    seq_len, per_rank = int(cfg.algo.per_rank_sequence_length), int(cfg.algo.per_rank_batch_size)
    key = jax.random.PRNGKey(seed)
    results = {}
    for label, fabric in (
        (name, Fabric(devices=n, precision=str(cfg.fabric.precision), mesh_axes=("data", "model"), mesh_shape=mesh_shape)),
        ("one device", Fabric(devices=1, precision=str(cfg.fabric.precision))),
    ):
        (wm, actor, critic), txs, state, actions_dim = _train_setup(cfg, fabric)
        host_batch = _seeded_batch(cfg, seq_len, per_rank * int(mesh_shape[0]), sum(actions_dim), seed)
        train_fn = make_train_fn(fabric, wm, actor, critic, *txs, cfg, True, actions_dim)
        batch = jax.device_put(host_batch, fabric.sharding(None, fabric.data_axis))
        step = train_fn
        if fabric.world_size > 1:
            start = jax.device_get(state[:3])
            w2 = state[0]["params"]["recurrent_model"]["LayerNormGRUCell_0"]["Dense_0"]["kernel"]
            say(
                f"[{name}] GRU kernel {w2.shape} sharded {w2.sharding.spec} over "
                f"{len(w2.sharding.device_set)} devices; recurrent model {_rssm_backend(wm, state[0])}"
            )
            step = _compile_and_describe(name, fabric, train_fn, state, batch, key)
        results[label] = jax.device_get(step(*state, batch, key))
    return _agreement(name, results[name], results["one device"], start, loss_rtol=loss_rtol, min_cosine=min_cosine)


def _rssm_backend(wm, wm_params) -> str:
    """What ``recurrent_model.fused`` resolved to in this world model."""
    import flax.linen as nn

    name = nn.apply(lambda m: type(m.recurrent_model).__name__, wm)(wm_params)
    return f"{name} (fused={wm.fused_recurrent!r})"


# --------------------------------------------------------------------------- #
# entry points
# --------------------------------------------------------------------------- #


def tpu_devices(count: int):
    """The attached TPU chips, or an exit with no result printed."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found {devices[0].platform!r} devices, not a TPU; there is no CPU mode")
    if len(devices) != count:
        sys.exit(f"chip_smoke: this path needs {count} chip(s), JAX reports {len(devices)}")
    return devices


def finish(devices, summary: Dict[str, Any], out_name: str) -> None:
    stats = devices[0].memory_stats() or {}
    say(f"[device] memory_stats peak_bytes_in_use={stats.get('peak_bytes_in_use')} of bytes_limit={stats.get('bytes_limit')}")
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, out_name), "w") as f:
        json.dump(summary, f, indent=1, default=str)
    print(
        json.dumps(
            {
                "ok": True,
                "device": {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)},
            }
        ),
        flush=True,
    )


def main_one_chip() -> None:
    devices = tpu_devices(1)
    workdir = os.path.join(REPO, "logs", "chip_smoke", time.strftime("%Y%m%d-%H%M%S"))
    cache = cache_watch()
    say(f"[device] {devices[0].device_kind} x{len(devices)}; work dir {workdir}")
    from sheeprl_tpu import native

    t0 = time.perf_counter()
    ring = phase_ring_programs(RINGS, max_temp_bytes=64 << 20)
    kernel = phase_kernel(**S_KERNEL, batches=(16, 1024), fwd_atol=1e-2, grad_rtol=1e-3)
    mark = (cache.cache_hits, cache.cache_misses)
    # 70 updates of random actions fill 64-step sequences; the watchdog's warm
    # point is 64 updates after the first train window, and 16 more run past it
    train = phase_train(
        workdir, platform="tpu", total_steps=600, learning_starts=280, buffer_size=8192, checkpoint_every=400
    )
    say(f"[train] compile cache requests in this phase: {cache_since(cache, mark)}")
    mark = (cache.cache_hits, cache.cache_misses)
    evaluated = phase_eval(train["checkpoint"], workdir, platform="tpu")
    say(f"[eval] compile cache requests in this phase (programs train already built are hits): {cache_since(cache, mark)}")
    say(f"[native] host gather library: available={native.available()} status={native.status()!r}")
    cache.stop()
    say(f"[done] all phases in {time.perf_counter() - t0:.1f}s")
    finish(
        devices,
        {"ring": ring, "kernel": kernel, "train": train, "eval": evaluated, "cache": cache_since(cache)},
        "chip_smoke.json",
    )


def main_four_chips() -> None:
    """Only what exists across chips, and what it is compared with: the
    recipe through the CLI on the 4-chip data mesh (sharded replay ring),
    then one seeded batch through the train step on the ``[4]`` mesh and on
    the ``[2, 2]`` (data, model) mesh, each against one device."""
    devices = tpu_devices(4)
    workdir = os.path.join(REPO, "logs", "chip_smoke", time.strftime("%Y%m%d-%H%M%S") + "-x4")
    say(f"[device] {devices[0].device_kind} x{len(devices)}; work dir {workdir}")
    train = phase_train(
        workdir,
        platform="tpu",
        n_devices=4,
        total_steps=320,
        learning_starts=280,
        buffer_size=8192,
        checkpoint_every=300,
        expect_warm=False,
        overrides=("fabric.devices=4",),
    )
    cfg = _compose_cfg(())
    dp = phase_data_parallel(cfg, n_devices=4, loss_rtol=2e-2, min_cosine=0.9)
    mp = phase_model_parallel(cfg, mesh_shape=(2, 2), loss_rtol=2e-2, min_cosine=0.9)
    finish(devices, {"train": train, "data_parallel": dp, "model_parallel": mp}, "chip_smoke_x4.json")


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1, help="4: only the multi-chip phases")
    if parser.parse_args().chips == 4:
        main_four_chips()
    else:
        main_one_chip()
