"""CLI dispatcher (reference: sheeprl/cli.py:23-436).

``python -m sheeprl_tpu exp=<exp> key=value ...`` composes the config tree,
validates it, looks the algorithm up in the registry and calls its
entrypoint. Unlike the reference there is no ``fabric.launch`` process spawn:
JAX is SPMD — one process per host drives every local chip, and multi-host
runs start the same command on every host (``jax.distributed`` connects
them), replacing the launcher model of cli.py:190.
"""

from __future__ import annotations

import importlib
import os
import sys
import warnings
from typing import Any, Dict, List, Optional

from sheeprl_tpu.config import compose
from sheeprl_tpu.config.compose import compose_group, instantiate
from sheeprl_tpu.utils.registry import algorithm_registry, evaluation_registry
from sheeprl_tpu.utils.utils import dotdict, print_config


def resume_from_checkpoint(cfg: dotdict, cli_overrides: Optional[List[str]] = None) -> dotdict:
    """Merge the run config stored beside the checkpoint, keeping the current
    run's checkpoint/resume settings (reference cli.py:23-48).
    ``cli_overrides`` is the raw override list of the resuming invocation —
    explicitly-passed ``fabric.*`` keys win over the stored fabric section
    (elastic restore)."""
    import yaml

    ckpt_path = cfg.checkpoint.resume_from
    old_cfg_path = os.path.join(os.path.dirname(os.path.dirname(ckpt_path)), "config.yaml")
    if not os.path.isfile(old_cfg_path):
        raise ValueError(f"no config.yaml found next to the checkpoint: {old_cfg_path}")
    with open(old_cfg_path) as f:
        old_cfg = dotdict(yaml.safe_load(f))
    if old_cfg.env.id != cfg.env.id:
        raise ValueError(
            f"This experiment is run with a different environment from the checkpoint: "
            f"{cfg.env.id} vs {old_cfg.env.id}"
        )
    if old_cfg.algo.name != cfg.algo.name:
        raise ValueError(
            f"This experiment is run with a different algorithm from the checkpoint: "
            f"{cfg.algo.name} vs {old_cfg.algo.name}"
        )
    merged = dotdict(old_cfg.to_dict())
    merged.checkpoint = dotdict(cfg.checkpoint.to_dict())
    # The fabric section keeps the STORED values (precision, mesh axes —
    # so a resume can't silently change the run's numerics or topology) —
    # EXCEPT the keys the user explicitly overrode on the resume command
    # line, which enable elastic restore: the checkpoint stores global-batch
    # counters and host-layout arrays, so an 8-device checkpoint reshards
    # onto an explicitly requested smaller/larger mesh (the reference
    # refuses world-size changes instead). Composed defaults do NOT count as
    # overrides — every config carries all fabric keys, so copying them
    # wholesale would clobber a model-axis run's stored mesh on a plain
    # resume.
    for ov in cli_overrides or []:
        # normalize the way compose.parse_overrides does: `+key=` / `/key=`
        # prefixes add, `~key` deletes — all of them are explicit user intent
        # about that key, so all of them must defeat the stored fabric section
        key = ov.split("=", 1)[0].strip().lstrip("+~").lstrip("/")
        if key == "fabric":
            # bare `fabric=<group>` group override: the user re-selected the
            # whole fabric group — take the freshly composed section wholesale
            merged.fabric = dotdict(cfg.fabric.to_dict())
        elif key.startswith("fabric."):
            sub = key[len("fabric."):].split(".", 1)[0]
            if sub in cfg.fabric:
                merged.fabric[sub] = cfg.fabric[sub]
            else:
                # `~fabric.<sub>` deleted the key from the composed config —
                # mirror the deletion instead of KeyError-ing on the copy
                merged.fabric.pop(sub, None)
    merged.root_dir = cfg.root_dir
    merged.run_name = cfg.run_name
    return merged


def check_configs(cfg: dotdict) -> None:
    """Config sanity checks (reference cli.py:262-331)."""
    if cfg.algo.name is None:
        raise ValueError("algo.name must be set")
    entry = _find_entry(cfg.algo.name)
    if entry is None:
        registered = sorted({e["name"] for entries in algorithm_registry.values() for e in entries})
        raise ValueError(
            f"Given the algorithm named '{cfg.algo.name}', no registered algorithm has been found. "
            f"Registered algorithms: {registered}"
        )
    if cfg.metric.log_level > 0 and not cfg.metric.get("aggregator"):
        raise ValueError("metric.aggregator must be set when metric.log_level > 0")


def _find_entry(algo_name: str) -> Optional[Dict[str, Any]]:
    for module, entries in algorithm_registry.items():
        for entry in entries:
            if entry["name"] == algo_name:
                return {"module": module, **entry}
    return None


def _is_actor_learner_run(cfg) -> bool:
    """True when this process will take (or took) the in-host disaggregated
    actor–learner path: a ppo *_decoupled entrypoint without a
    jax.distributed process group (see ppo_decoupled.main's dispatch)."""
    algo_cfg = cfg.get("algo") if hasattr(cfg, "get") else None
    if algo_cfg is None:
        return False
    name = str(algo_cfg.get("name") or "")
    if not (name.startswith("ppo") and name.endswith("_decoupled")):
        return False
    try:
        import jax

        return jax.process_count() < 2
    except Exception:
        return False


def run_algorithm(cfg: dotdict) -> None:
    """Registry lookup → fabric build → entrypoint (reference cli.py:51-190)."""
    from sheeprl_tpu.utils.metric import MetricAggregator
    from sheeprl_tpu.utils.timer import timer

    # wire the observability kill-switches (reference cli.py:142-156)
    timer.disabled = bool(cfg.metric.get("disable_timer", False)) or cfg.metric.log_level <= 0
    MetricAggregator.disabled = cfg.metric.log_level <= 0

    entry = _find_entry(cfg.algo.name)
    module = importlib.import_module(entry["module"])
    entrypoint = getattr(module, entry["entrypoint"])

    # P2E finetuning: load the exploration run's config and force the env
    # settings to match it (reference cli.py:108-139)
    kwargs: Dict[str, Any] = {}
    if "finetuning" in cfg.algo.name and "p2e" in entry["module"]:
        import yaml

        ckpt_path = cfg.checkpoint.exploration_ckpt_path
        if not ckpt_path:
            raise ValueError("checkpoint.exploration_ckpt_path must be set for P2E finetuning")
        expl_cfg_path = os.path.join(os.path.dirname(os.path.dirname(ckpt_path)), "config.yaml")
        with open(expl_cfg_path) as f:
            exploration_cfg = dotdict(yaml.safe_load(f))
        if exploration_cfg.env.id != cfg.env.id:
            raise ValueError(
                "This experiment is run with a different environment from the one of the "
                f"exploration you want to finetune. Got '{cfg.env.id}', but the environment "
                f"used during exploration was {exploration_cfg.env.id}."
            )
        for k in (
            "frame_stack",
            "screen_size",
            "action_repeat",
            "grayscale",
            "clip_rewards",
            "frame_stack_dilation",
            "max_episode_steps",
            "reward_as_observation",
        ):
            if k in exploration_cfg.env:
                cfg.env[k] = exploration_cfg.env[k]
        kwargs["exploration_cfg"] = exploration_cfg

    fabric_cfg = dict(cfg.fabric.to_dict() if isinstance(cfg.fabric, dotdict) else cfg.fabric)
    callbacks = [instantiate(cb) for cb in fabric_cfg.pop("callbacks", None) or []]
    fabric = instantiate({**fabric_cfg, "callbacks": callbacks})

    # keep the aggregator's metric whitelist aligned with what the algorithm
    # produces (reference cli.py:142-156)
    utils_module_name = entry["module"].rsplit(".", 1)[0] + ".utils"
    try:
        algo_utils = importlib.import_module(utils_module_name)
        keys = set(getattr(algo_utils, "AGGREGATOR_KEYS", set()))
        agg_cfg = cfg.metric.get("aggregator", {})
        metrics = agg_cfg.get("metrics", {}) or {}
        dropped = [k for k in metrics if k not in keys]
        for k in dropped:
            metrics.pop(k)
    except ModuleNotFoundError:
        pass

    from sheeprl_tpu.obs import configure_telemetry, shutdown_telemetry
    from sheeprl_tpu.utils.logger import run_base_dir
    from sheeprl_tpu.utils.profiler import maybe_profile

    # the run's TB root (the versioned dir itself is only chosen inside the
    # entrypoint): traces land at <root>/profile, next to version_N, so
    # `tensorboard --logdir <root>` picks up the profile plugin data; the
    # telemetry JSONL lands beside them at <root>/telemetry.jsonl
    configure_telemetry(cfg, log_dir=run_base_dir(cfg))
    # auto-resume resolution ran before telemetry existed — flush its events
    from sheeprl_tpu.resilience import drain_async_checkpoints, emit_pending_resilience_events

    emit_pending_resilience_events()
    outcome, error = "completed", None
    try:
        with maybe_profile(cfg, log_dir=run_base_dir(cfg)):
            entrypoint(fabric, cfg, **kwargs)
    except SystemExit as err:
        # the preemption drain exits with the distinct code 77 — everything
        # else raising SystemExit mid-loop is a crash for the registry
        from sheeprl_tpu.resilience import PREEMPTED_EXIT_CODE

        outcome = "preempted" if err.code == PREEMPTED_EXIT_CODE else "crashed"
        error = None if outcome == "preempted" else repr(err)
        raise
    except BaseException as err:
        # unhandled train-loop crash: if the entrypoint armed its crash
        # guard, drain in-flight saves and commit an emergency checkpoint so
        # resume_from=auto restarts from this boundary; the exception still
        # propagates. register_run reclassifies to rolled_back when the run
        # died after NaN rollbacks.
        # disaggregated-topology outcomes get their own registry classes: an
        # actor that burnt its restart budget aborted the run without the
        # learner itself failing, and any other crash in the actor_learner
        # variant is the learner's
        try:
            from sheeprl_tpu.actor_learner.supervisor import ActorBudgetExhausted
        except Exception:  # never mask the original crash
            ActorBudgetExhausted = ()  # type: ignore[assignment]
        if isinstance(err, ActorBudgetExhausted):
            outcome = "actor_exhausted"
        elif _is_actor_learner_run(cfg):
            outcome = "learner_crashed"
        else:
            outcome = "crashed"
        error = repr(err)
        if isinstance(err, Exception):
            from sheeprl_tpu.resilience import crash_drain

            crash_drain(err)
        raise
    finally:
        # a background checkpoint write may still be in flight (including the
        # save_last one) — join it before closing the telemetry sink so its
        # ckpt_committed event makes the run_end totals
        drain_async_checkpoints()
        # run registry (obs/registry.py): the durable one-line record in
        # RUNS.jsonl, appended BEFORE shutdown so the telemetry rollup
        # (run_summary) is still alive to fold in
        from sheeprl_tpu.obs.registry import register_run

        # loop variants land in their own regress cell (tools/regress.py
        # appends :variant to the cell key): a 3x fused run must never become
        # the host loop's baseline, nor be gated against it
        variant = None
        algo_cfg = cfg.get("algo") if hasattr(cfg, "get") else None
        if algo_cfg is not None:
            if _is_actor_learner_run(cfg):
                variant = "actor_learner"
            elif algo_cfg.get("fused_rollout"):
                variant = "fused_rollout"
            elif algo_cfg.get("overlap_collection"):
                variant = "overlap_collection"
        extra = {"variant": variant} if variant else {}
        # what fabric.accelerator asked for and what the devices are: `auto`
        # takes what is there, and the record says what that was
        extra["accelerator"] = {"requested": fabric.accelerator, "platform": fabric.platform}
        register_run(cfg, kind="train", outcome=outcome, error=error, **extra)
        shutdown_telemetry()


def run(args: Optional[List[str]] = None) -> None:
    """Main entry (reference cli.py:344-352)."""
    overrides = list(sys.argv[1:] if args is None else args)
    if overrides and overrides[0] == "serve":
        # `python -m sheeprl_tpu serve checkpoint_path=...`: the policy-serving
        # tier (howto/serving.md) — config comes from beside the checkpoint,
        # not from a fresh composition, so dispatch before composing
        from sheeprl_tpu.cli_serve import serving

        return serving(overrides[1:])
    cfg = compose("config", overrides)
    cfg = dotdict(cfg)
    if cfg.checkpoint.resume_from == "auto":
        # resolve to a concrete committed checkpoint path (newest valid under
        # this run's base dir) — or None, which starts a fresh run
        from sheeprl_tpu.resilience import resolve_auto_resume

        cfg.checkpoint.resume_from = resolve_auto_resume(cfg)
    if cfg.checkpoint.resume_from:
        cfg = resume_from_checkpoint(cfg, cli_overrides=overrides)
    if cfg.metric.log_level > 0:
        print_config(cfg)
    check_configs(cfg)
    os.environ.setdefault("OMP_NUM_THREADS", str(cfg.num_threads))
    run_algorithm(cfg)


def eval_algorithm(cfg: dotdict) -> None:
    """Load a checkpoint and run the registered evaluation
    (reference cli.py:193-259)."""
    entry = None
    for module, entries in evaluation_registry.items():
        for e in entries:
            if e["name"] == cfg.algo.name:
                entry = {"module": module, **e}
    if entry is None:
        registered = sorted({e["name"] for entries in evaluation_registry.values() for e in entries})
        raise ValueError(
            f"no registered evaluation for algorithm '{cfg.algo.name}'; available: {registered}"
        )
    module = importlib.import_module(entry["module"])
    evaluate_fn = getattr(module, entry["entrypoint"])

    from sheeprl_tpu.parallel.fabric import Fabric
    from sheeprl_tpu.utils.checkpoint import load_checkpoint

    fabric = Fabric(devices=1, precision=str(cfg.fabric.get("precision", "fp32")))
    state = load_checkpoint(cfg.checkpoint_path)
    from sheeprl_tpu.obs.registry import register_run

    outcome, error = "completed", None
    try:
        evaluate_fn(fabric, cfg, state)
    except BaseException as err:
        outcome, error = "crashed", repr(err)
        raise
    finally:
        register_run(cfg, kind="eval", outcome=outcome, error=error, checkpoint=cfg.get("checkpoint_path"))


def evaluation(args: Optional[List[str]] = None) -> None:
    """``python -m sheeprl_tpu.cli_eval checkpoint_path=... [overrides]``
    (reference cli.py:355-391): rebuild the training config stored beside the
    checkpoint, force single-device / single-env, then evaluate."""
    import yaml

    overrides = list(sys.argv[1:] if args is None else args)
    kv = dict(o.split("=", 1) for o in overrides if "=" in o and not o.startswith(("+", "~")))
    ckpt_path = kv.get("checkpoint_path")
    if not ckpt_path:
        raise ValueError("checkpoint_path=<file> is required")
    cfg_path = os.path.join(os.path.dirname(os.path.dirname(ckpt_path)), "config.yaml")
    with open(cfg_path) as f:
        cfg = dotdict(yaml.safe_load(f))
    cfg.checkpoint_path = ckpt_path
    for k, v in kv.items():
        if k in ("checkpoint_path", "env.capture_video"):
            continue
        value = yaml.safe_load(v)
        if "." not in k and isinstance(cfg.get(k), dict) and isinstance(value, str):
            # `fabric=cpu` style group re-selection: re-compose the group
            # (hydra semantics), don't overwrite the subtree with a string
            cfg[k] = dotdict(compose_group(k, value))
            continue
        node = cfg
        parts = k.split(".")
        for p in parts[:-1]:
            node = node[p]
        node[parts[-1]] = value
    # a spliced group may carry ${...} interpolations (e.g. logger=mlflow's
    # ${exp_name}) — resolve them against the full tree before use
    from sheeprl_tpu.config.compose import resolve

    cfg = dotdict(resolve(cfg))
    # evaluation always runs single-device and single-env (reference
    # cli.py:363-387) — (re)applied after the overrides so a group
    # re-selection like `env=dmc` cannot undo it
    cfg.fabric["devices"] = 1
    cfg.env.num_envs = 1
    cfg.env.capture_video = kv.get("env.capture_video", "False").lower() in ("1", "true")
    eval_algorithm(cfg)


def registration(args: Optional[List[str]] = None) -> None:
    """``python -m sheeprl_tpu.cli_registration checkpoint_path=... [overrides]``
    (reference cli.py:394-436 + sheeprl_model_manager.py): rebuild the run
    config stored beside the checkpoint, pick the algorithm's
    ``log_models_from_checkpoint``, and register the configured sub-models
    with the model manager."""
    import yaml

    overrides = list(sys.argv[1:] if args is None else args)
    kv = dict(o.split("=", 1) for o in overrides if "=" in o and not o.startswith(("+", "~")))
    ckpt_path = kv.get("checkpoint_path")
    if not ckpt_path:
        raise ValueError("checkpoint_path=<file> is required")
    cfg_path = os.path.join(os.path.dirname(os.path.dirname(ckpt_path)), "config.yaml")
    with open(cfg_path) as f:
        cfg = dotdict(yaml.safe_load(f))
    cfg.checkpoint_path = ckpt_path
    # the stored run may have trained with model_manager disabled; compose the
    # algorithm's model-manager group so the registration targets exist
    from sheeprl_tpu.config.compose import group_options

    mm_name = cfg.algo.name
    if mm_name not in group_options("model_manager"):
        mm_name = "default"
    cfg.model_manager = compose_model_manager_group(mm_name, cfg)
    for k, v in kv.items():
        if k == "checkpoint_path":
            continue
        value = yaml.safe_load(v)
        if "." not in k and isinstance(cfg.get(k), dict) and isinstance(value, str):
            cfg[k] = dotdict(compose_group(k, value))
            continue
        node = cfg
        parts = k.split(".")
        for p in parts[:-1]:
            node = node.setdefault(p, dotdict({})) if isinstance(node, dict) else node[p]
        node[parts[-1]] = value

    from sheeprl_tpu.config.compose import resolve
    from sheeprl_tpu.parallel.fabric import Fabric
    from sheeprl_tpu.utils.checkpoint import load_checkpoint
    from sheeprl_tpu.utils.model_manager import register_model_from_checkpoint

    cfg = dotdict(resolve(cfg))
    fabric = Fabric(devices=1, precision=str(cfg.fabric.get("precision", "fp32")))
    state = load_checkpoint(ckpt_path)

    algo_name = cfg.algo.name
    if "decoupled" in algo_name:
        algo_name = algo_name.replace("_decoupled", "")
    if algo_name.startswith("p2e_dv"):
        algo_name = "_".join(algo_name.split("_")[:2])
    utils_module = importlib.import_module(f"sheeprl_tpu.algos.{algo_name}.utils")
    register_model_from_checkpoint(fabric, cfg, state, utils_module.log_models_from_checkpoint)


def compose_model_manager_group(name: str, cfg: dotdict) -> dotdict:
    """Resolve ``configs/model_manager/<name>.yaml`` with interpolations
    against the checkpoint's config (exp_name/env.id)."""
    import yaml

    from sheeprl_tpu.config.compose import _default_search_path, _find_config_file

    merged: Dict[str, Any] = {}

    def load(rel_name: str) -> None:
        p = _find_config_file(os.path.join("model_manager", rel_name), _default_search_path())
        with open(p) as f:
            content = yaml.safe_load(f) or {}
        for entry in content.pop("defaults", []) or []:
            if isinstance(entry, str) and entry != "_self_":
                load(entry)
        _deep_merge(merged, content)

    def _deep_merge(dst, src):
        for k, v in src.items():
            if isinstance(v, dict) and isinstance(dst.get(k), dict):
                _deep_merge(dst[k], v)
            else:
                dst[k] = v

    load(name)

    # resolve ${dotted.path} interpolations against the checkpoint's config
    # with the composer's own resolver (the yamls use ${exp_name}/${env.id})
    from sheeprl_tpu.config.compose import _resolve_value

    root = dict(cfg)
    root["model_manager"] = merged

    def resolve(node: Any) -> Any:
        if isinstance(node, dict):
            return {k: resolve(v) for k, v in node.items()}
        if isinstance(node, list):
            return [resolve(v) for v in node]
        return _resolve_value(root, node, ())

    resolved = resolve(merged)
    resolved["disabled"] = False
    return dotdict(resolved)


def available_agents() -> None:
    """Print the registry as a table (reference available_agents.py:7)."""
    try:
        from rich.console import Console
        from rich.table import Table

        table = Table(title="SheepRL-TPU agents")
        table.add_column("Module")
        table.add_column("Algorithm")
        table.add_column("Entrypoint")
        table.add_column("Decoupled")
        for module, entries in algorithm_registry.items():
            for e in entries:
                table.add_row(module, e["name"], e["entrypoint"], str(e["decoupled"]))
        Console().print(table)
    except ImportError:
        for module, entries in algorithm_registry.items():
            for e in entries:
                print(f"{module}: {e['name']} ({e['entrypoint']}), decoupled={e['decoupled']}")
