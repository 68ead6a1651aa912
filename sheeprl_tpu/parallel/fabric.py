"""TPU mesh runtime — the framework's replacement for ``lightning.fabric``
(reference L0, SURVEY.md §1/§2.7).

Where the reference wraps each module in DDP and all-reduces gradients over
NCCL (``fabric.setup_module`` / ``fabric.backward``), here distribution is
*declarative*: a ``jax.sharding.Mesh`` with a ``data`` axis (optionally a
``model`` axis for param sharding), batches placed with a data-axis
``NamedSharding`` and params replicated. A ``jax.jit`` train step closed over
those shardings gets its gradient all-reduce inserted by XLA as an ICI
collective — there is no imperative backward/all-reduce pair to call.

Multi-host: ``jax.distributed.initialize`` (DCN) is triggered by env vars or
explicit coordinator config; the same mesh then spans all processes and the
identical jitted step runs on every host (SPMD), replacing the reference's
launcher-spawned DDP ranks (cli.py:190).
"""

from __future__ import annotations

import dataclasses
import os
import re
import warnings
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def seed_everything(seed: int) -> jax.Array:
    """Seed numpy + return the root PRNG key (reference reproducibility
    wrapper, cli.py:174-189; torch/cudnn flags have no TPU counterpart —
    XLA is deterministic modulo collective reduction order)."""
    np.random.seed(seed)
    return jax.random.PRNGKey(seed)


_PRECISIONS = ("fp32", "bf16-mixed", "bf16-true")
# lightning-style spellings accepted from configs (reference fabric configs)
_PRECISION_ALIASES = {"32-true": "fp32", "32": "fp32", "bf16": "bf16-mixed"}


@dataclasses.dataclass(frozen=True)
class Precision:
    """Numeric policy (reference: Fabric precision ``bf16-mixed``,
    configs/fabric/default.yaml; SURVEY §2.8.3).

    - ``fp32``: everything float32.
    - ``bf16-mixed``: fp32 params/optimizer state, bf16 compute on the MXU —
      the policy matching the reference's GPU recipe.
    - ``bf16-true``: bf16 params and compute (halves HBM, used by the
      reference test-suite).
    """

    name: str = "fp32"

    def __post_init__(self) -> None:
        object.__setattr__(self, "name", _PRECISION_ALIASES.get(self.name, self.name))
        if self.name not in _PRECISIONS:
            raise ValueError(
                f"unknown precision {self.name!r}; choose from {_PRECISIONS} (aliases: {_PRECISION_ALIASES})"
            )

    @property
    def param_dtype(self) -> jnp.dtype:
        return jnp.bfloat16 if self.name == "bf16-true" else jnp.float32

    @property
    def compute_dtype(self) -> jnp.dtype:
        return jnp.bfloat16 if self.name in ("bf16-mixed", "bf16-true") else jnp.float32

    def cast_to_compute(self, tree: Any) -> Any:
        dtype = self.compute_dtype
        return jax.tree.map(
            lambda x: x.astype(dtype) if isinstance(x, (jax.Array, np.ndarray)) and jnp.issubdtype(x.dtype, jnp.floating) else x,
            tree,
        )


# --------------------------------------------------------------------------- #
# Regex partition-rule table (megatron-lm / EasyLM style)
# --------------------------------------------------------------------------- #
# Rules map a regex over the '/'-joined pytree path of a leaf to a sharding
# strategy. Because Adam's mu/nu (and any EMA twin of the params) mirror the
# param tree structure, a rule anchored on the leaf name ("kernel") covers the
# param AND its optimizer-state twins — the property the fused superstep needs
# so opt/EMA carries stay model-sharded instead of silently riding replicated.
#
# Strategies: "auto" (shape-based model-axis rule, Fabric.param_spec),
# "replicate" (force P()), or an explicit PartitionSpec. First match wins;
# unmatched leaves fall back to replicated with a warn-once per path.
DEFAULT_PARTITION_RULES: Tuple[Tuple[str, Any], ...] = (
    # stacked expert kernels ``[experts held, in, out]`` (models/seqpol.py):
    # by the shape rule, over their last two dims like any kernel. The leading
    # axis is the experts THIS chip holds and is never sharded: no expert mesh
    # axis is built, and a layer shared by several chips runs as one chip's
    # share (ROADMAP B9 d)
    (r"(^|/)experts/(gate|up|down)/kernel$", "auto"),
    # dense/conv kernels and embeddings (+ their mu/nu/EMA twins): shape rule
    (r"(^|/)(kernel|embedding)$", "auto"),
    # LayerNorm affine, biases, the learnable h0: small — keep replicated
    (r"(^|/)(bias|scale|initial_recurrent_state)$", "replicate"),
    # optimizer bookkeeping and return-normalizer moments: scalars
    (r"(^|/)(count|mu_hat|nu_hat|low|high)$", "replicate"),
)

_warned_unmatched_paths: set = set()


def reset_partition_rule_warnings() -> None:
    """Re-arm the unmatched-leaf warn-once filter (tests / repeated runs)."""
    _warned_unmatched_paths.clear()


def _path_token(entry: Any) -> str:
    """One tree-path entry as a plain string: dict keys, namedtuple/attr
    fields and sequence indices all render bare so rules can anchor on
    ``(^|/)name$`` regardless of the container type."""
    for attr in ("key", "name", "idx"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry)


def tree_path_str(path: Sequence[Any]) -> str:
    """'/'-joined rendering of a ``tree_flatten_with_path`` key path, e.g.
    ``1/0/mu/Dense_0/kernel`` for the Adam mu twin of a flax kernel."""
    return "/".join(_path_token(e) for e in path)


#: where the persistent XLA compilation cache lives when the environment does
#: not place it: one fixed, git-ignored directory inside the checkout (a
#: directory that moves between runs never hits)
REPO_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), ".jax_cache"
)


def configure_compilation_cache(group_size: int = 1) -> Optional[str]:
    """Turn JAX's persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX already read it at import — that
    directory is used and nothing here touches the setting. Unset: the cache
    goes to :data:`REPO_COMPILATION_CACHE_DIR`. ``compile_cache`` telemetry
    events count every request.

    On an accelerator the min-compile-time and min-entry-size gates are
    zeroed so even the small programs (eager ops, buffer writes, gathers)
    persist: there each costs a tenth of a second or more to compile and a
    run makes hundreds of them. On the CPU backend JAX's thresholds stay:
    tiny programs compile in milliseconds, and XLA:CPU's loader resolves the
    kernels of a reloaded executable by generic names process-wide, so
    reloading many of them can collide ("Function tanh_reduce_fusion not
    found").

    A multi-process CPU group (``group_size`` > 1) gets NO cache (returns
    ``None``): a gloo cross-process CPU executable does not survive a reload
    — even a warm-cache run of the SAME topology deserializes collectives
    that no longer reach the group and computes garbage without erroring."""
    # answered from the configured platform list where there is one: asking
    # the backend initializes it, after which jax.distributed cannot start
    platforms = jax.config.jax_platforms
    on_cpu = (platforms.split(",")[0] if platforms else jax.default_backend()) == "cpu"
    if group_size > 1 and on_cpu:
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()  # JAX latches "cache in use" at its first compile
        return None
    if not on_cpu:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    os.makedirs(REPO_COMPILATION_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", REPO_COMPILATION_CACHE_DIR)
    return REPO_COMPILATION_CACHE_DIR


class Fabric:
    """Device mesh + precision + process topology in one handle.

    Args:
        devices: number of devices to use (``-1`` / ``None`` = all).
        precision: one of ``fp32`` / ``bf16-mixed`` / ``bf16-true``.
        mesh_axes: axis names; first axis is the data axis. Default 1-D
            ``("data",)`` — pure DP, the reference's only strategy
            (SURVEY §2.7). A 2-D ``("data", "model")`` mesh enables param
            sharding for larger models.
        mesh_shape: sizes per axis; ``-1`` infers from the device count.
    """

    def __init__(
        self,
        devices: Optional[int | str] = None,
        precision: str = "fp32",
        accelerator: str = "auto",
        num_nodes: int = 1,
        mesh_axes: Sequence[str] = ("data",),
        mesh_shape: Optional[Sequence[int]] = None,
        callbacks: Optional[Sequence[Any]] = None,
        distributed_coordinator: Optional[str] = None,
        num_processes: Optional[int] = None,
        process_id: Optional[int] = None,
        aot_cache_dir: Optional[str] = None,
    ) -> None:
        group_size = self._maybe_init_distributed(distributed_coordinator, num_processes, process_id)
        if accelerator not in ("auto", "tpu", "cpu", "gpu"):
            raise ValueError(f"unknown accelerator {accelerator!r}")
        if accelerator == "cpu":
            # must happen before the first device query in this process
            try:
                jax.config.update("jax_platforms", "cpu")
            except RuntimeError:
                pass  # backend already initialized; devices below reflect it
        self.compilation_cache_dir = configure_compilation_cache(group_size)
        # AOT *executable* cache (ops/aotcache, howto/aot_cache.md): one tier
        # above the trace cache — the fused-superstep builders serialize
        # whole compiled windows through it so a preemption-resume skips the
        # compile entirely instead of just the retrace
        self.aot_cache = None
        self.aot_cache_dir = None
        if aot_cache_dir:
            from sheeprl_tpu.ops.aotcache import AotCache

            self.aot_cache_dir = os.path.abspath(os.path.expanduser(str(aot_cache_dir)))
            self.aot_cache = AotCache(self.aot_cache_dir)
        self.accelerator = accelerator
        self.num_nodes = num_nodes
        self.callbacks = list(callbacks or [])
        if devices in ("auto", "-1"):
            devices = None
        all_devices = jax.devices()
        #: what the devices ARE — ``accelerator`` is only what was asked for
        self.platform = all_devices[0].platform
        if accelerator in ("tpu", "gpu") and self.platform != accelerator:
            raise RuntimeError(
                f"fabric.accelerator={accelerator!r} was requested but JAX found "
                f"{self.platform!r} devices ({all_devices[0].device_kind}); refusing to "
                "run on another backend — use fabric.accelerator=auto (or cpu) to accept it"
            )
        n = len(all_devices) if devices in (None, -1) else int(devices)
        if n <= 0 or n > len(all_devices):
            raise ValueError(f"requested {devices} devices but {len(all_devices)} are available")
        self.devices = all_devices[:n]
        self.precision = Precision(precision)
        axes = tuple(mesh_axes)
        if mesh_shape is None:
            shape: Tuple[int, ...] = (n,) + (1,) * (len(axes) - 1)
        else:
            shape = tuple(mesh_shape)
            inferred = [i for i, s in enumerate(shape) if s == -1]
            if len(inferred) > 1:
                raise ValueError("at most one mesh axis may be -1")
            if inferred:
                known = int(np.prod([s for s in shape if s != -1])) or 1
                shape = tuple(n // known if s == -1 else s for s in shape)
        if int(np.prod(shape)) != n:
            raise ValueError(f"mesh shape {shape} does not cover {n} devices")
        self.mesh = Mesh(np.asarray(self.devices).reshape(shape), axes)
        self.data_axis = axes[0]

    @staticmethod
    def _maybe_init_distributed(
        coordinator: Optional[str], num_processes: Optional[int], process_id: Optional[int]
    ) -> int:
        """DCN process-group bring-up (replaces TorchCollective.setup,
        ppo_decoupled.py:645-649). No-op on a single host. Returns the
        process-group size (1 when not distributed)."""
        if coordinator is None and "SHEEPRL_TPU_COORDINATOR" in os.environ:
            coordinator = os.environ["SHEEPRL_TPU_COORDINATOR"]
            num_processes = int(os.environ["SHEEPRL_TPU_NUM_PROCESSES"]) if "SHEEPRL_TPU_NUM_PROCESSES" in os.environ else None
            process_id = int(os.environ["SHEEPRL_TPU_PROCESS_ID"]) if "SHEEPRL_TPU_PROCESS_ID" in os.environ else None
        if coordinator is None:
            return 1
        # a configured coordinator with a missing/1 process count is a broken
        # launch, not a single-host run: every host would train independently
        # as process 0 with no cross-host reduction
        if not num_processes or num_processes <= 1 or process_id is None:
            raise ValueError(
                "distributed coordinator is set but num_processes/process_id are not — set "
                "SHEEPRL_TPU_NUM_PROCESSES (> 1) and SHEEPRL_TPU_PROCESS_ID on every host"
            )
        # CPU multi-process meshes need the gloo collectives client (the
        # default CPU backend refuses cross-process computations outright);
        # harmless on TPU hosts, where it only governs their cpu devices
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        # NOTE: do not probe jax.process_count() here — it initializes the
        # backend, after which distributed init is impossible; initialize
        # eagerly and tolerate an already-connected process group
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=num_processes,
                process_id=process_id,
            )
        except RuntimeError as e:
            # jax raises "distributed.initialize should only be called once"
            # on re-init and "must be called before any JAX computations" when
            # the caller initialized the backend first (e.g. an external
            # launcher already connected the process group)
            msg = str(e).lower()
            if not any(s in msg for s in ("already", "only be called once", "must be called before")):
                raise
        # tolerating the error is only safe when a process group actually
        # exists: otherwise every host would silently train alone as rank 0
        if jax.process_count() != num_processes:
            raise RuntimeError(
                f"distributed init requested {num_processes} processes but the JAX backend sees "
                f"{jax.process_count()} — initialize jax.distributed before any JAX computation "
                "(or let Fabric do it by constructing it first)"
            )
        return num_processes

    # ------------------------------------------------------------------ #
    # topology
    # ------------------------------------------------------------------ #
    @property
    def world_size(self) -> int:
        return len(self.devices)

    @property
    def process_index(self) -> int:
        return jax.process_index()

    @property
    def num_processes(self) -> int:
        """Host-process count — the analogue of the reference's rank count for
        step accounting (each process drives ``num_envs`` envs). NOT the chip
        count: one SPMD process feeds many chips."""
        return jax.process_count()

    @property
    def is_global_zero(self) -> bool:
        return jax.process_index() == 0

    @property
    def local_device_count(self) -> int:
        return len([d for d in self.devices if d.process_index == jax.process_index()])

    @property
    def model_axis(self) -> Optional[str]:
        """Name of the param-sharding mesh axis, or None on a pure-DP mesh
        (``mesh_axes=[data, model]`` + ``mesh_shape=[d, m]`` with m > 1
        enables it)."""
        if "model" in self.mesh.axis_names and self.mesh.shape["model"] > 1:
            return "model"
        return None

    @property
    def model_parallel_size(self) -> int:
        return self.mesh.shape["model"] if "model" in self.mesh.axis_names else 1

    @property
    def data_parallel_size(self) -> int:
        """Width of the batch split — the data axis alone, NOT world_size
        (on a 2-D mesh each batch shard is co-owned by ``model`` peers)."""
        return self.mesh.shape[self.data_axis]

    @property
    def local_data_parallel_size(self) -> int:
        """This process's share of the data axis (its sampling quota)."""
        return max(1, self.local_device_count // self.model_parallel_size)

    @property
    def pure_data_parallel(self) -> bool:
        """True when the whole mesh is one process × one data axis — the only
        topology where explicit-collective SPMD (``shard_map`` supersteps,
        the sharded replay ring) is sound: no param axis to cut across, and
        every shard of the scan lives in this process's dispatch."""
        return self.num_processes == 1 and self.model_axis is None

    # ------------------------------------------------------------------ #
    # placement
    # ------------------------------------------------------------------ #
    def sharding(self, *spec: Any) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    @property
    def batch_sharding(self) -> NamedSharding:
        """Leading-axis data-parallel placement."""
        return NamedSharding(self.mesh, P(self.data_axis))

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def shard_batch(self, tree: Any) -> Any:
        """Place host arrays with the leading axis split across the data axis
        (replaces per-rank ``to(device)`` copies; one transfer per shard)."""
        return jax.device_put(tree, self.batch_sharding)

    def replicate(self, tree: Any) -> Any:
        """Fully replicate params/state across the mesh (the JAX counterpart
        of DDP module broadcast, dreamer_v3/agent.py:1205-1214)."""
        return jax.device_put(tree, self.replicated)

    def param_spec(self, leaf: Any) -> P:
        """PartitionSpec for one param/optimizer-state leaf on this mesh.

        Rule (scaling-book tensor-parallel recipe, GSPMD does the rest): on a
        mesh with a ``model`` axis, shard the LAST dimension of any >=2-D
        array over it when divisible (column-parallel dense/conv kernels —
        activations pick up the sharding and XLA inserts the all-gathers /
        reduce-scatters); fall back to the second-to-last dimension
        (row-parallel) when only that divides; replicate everything else
        (biases, scales, scalars). Applying the same rule to optimizer state
        automatically co-shards Adam moments with their params."""
        axis = self.model_axis
        shape = getattr(leaf, "shape", ())
        if axis is None or len(shape) < 2:
            return P()
        m = self.mesh.shape[axis]
        if shape[-1] % m == 0 and shape[-1] >= m:
            return P(*([None] * (len(shape) - 1) + [axis]))
        if shape[-2] % m == 0 and shape[-2] >= m:
            return P(*([None] * (len(shape) - 2) + [axis, None]))
        return P()

    def shard_params(self, tree: Any) -> Any:
        """Place a param/optimizer pytree with the :meth:`param_spec` rule —
        param sharding over the ``model`` axis when the mesh has one,
        plain replication otherwise (so call sites need no topology check)."""
        if self.model_axis is None:
            return self.replicate(tree)
        # ONE batched device_put for the whole tree: per-leaf puts would pay
        # a dispatch round trip per leaf
        shardings = jax.tree.map(
            lambda leaf: NamedSharding(self.mesh, self.param_spec(leaf)), tree
        )
        return jax.device_put(tree, shardings)

    def match_partition_rules(self, tree: Any, rules: Optional[Sequence[Tuple[str, Any]]] = None) -> Any:
        """PartitionSpec pytree for ``tree`` from a regex rule table.

        Every leaf's '/'-joined path (:func:`tree_path_str`) is matched
        against ``rules`` (default :data:`DEFAULT_PARTITION_RULES`) in order;
        the first hit decides the spec: ``"auto"`` delegates to the
        shape-based :meth:`param_spec`, ``"replicate"`` forces ``P()``, and
        an explicit ``PartitionSpec`` is used verbatim. Unmatched leaves fall
        back to replicated with a warn-once per path — a silent fallback on
        a large matrix is exactly the all-gather-per-scan-step bug this
        table exists to prevent.

        Because optimizer state (Adam mu/nu) and EMA twins mirror the param
        tree, applying the same table to the whole superstep carry
        ``(params, opt, ema, moments)`` co-shards every twin of a kernel
        with the kernel itself. Returns a pytree with the exact structure of
        ``tree`` whose leaves are ``PartitionSpec``s (feed through
        ``NamedSharding(mesh, spec)`` for placement or jit shardings).
        """
        table = DEFAULT_PARTITION_RULES if rules is None else tuple(rules)
        compiled = [(re.compile(pattern), strategy) for pattern, strategy in table]
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        specs = []
        for path, leaf in flat:
            name = tree_path_str(path)
            for pattern, strategy in compiled:
                if pattern.search(name):
                    if strategy == "auto":
                        specs.append(self.param_spec(leaf))
                    elif strategy == "replicate":
                        specs.append(P())
                    elif isinstance(strategy, P):
                        specs.append(strategy)
                    else:
                        raise ValueError(
                            f"unknown partition-rule strategy {strategy!r} for pattern "
                            f"{pattern.pattern!r} (use 'auto', 'replicate' or a PartitionSpec)"
                        )
                    break
            else:
                if name not in _warned_unmatched_paths:
                    _warned_unmatched_paths.add(name)
                    warnings.warn(
                        f"no partition rule matched leaf {name!r} "
                        f"(shape={getattr(leaf, 'shape', ())}); replicating it — add a rule "
                        "if this leaf should be model-sharded",
                        UserWarning,
                        stacklevel=2,
                    )
                specs.append(P())
        return jax.tree_util.tree_unflatten(treedef, specs)

    def carry_shardings(self, tree: Any, rules: Optional[Sequence[Tuple[str, Any]]] = None) -> Any:
        """:meth:`match_partition_rules` materialised as ``NamedSharding``s
        (same structure as ``tree``) — the form ``jax.jit`` in/out shardings
        and ``with_sharding_constraint`` consume."""
        specs = self.match_partition_rules(tree, rules)
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), specs, is_leaf=lambda s: isinstance(s, P)
        )

    def make_global(self, tree: Any, spec: Any) -> Any:
        """Assemble per-process host arrays into one global sharded array
        (multi-host only; single process returns the tree untouched). ``spec``
        is the PartitionSpec of the GLOBAL array — each process contributes
        its local block along the sharded axes, replacing the reference's
        per-rank DistributedSampler feeding (SURVEY §2.7)."""
        if jax.process_count() == 1:
            return tree
        sharding = NamedSharding(self.mesh, spec if isinstance(spec, P) else P(*spec))
        return jax.tree.map(lambda x: jax.make_array_from_process_local_data(sharding, np.asarray(x)), tree)

    def local_batch_size(self, global_batch_size: int) -> int:
        data_size = self.mesh.shape[self.data_axis]
        if global_batch_size % data_size != 0:
            raise ValueError(
                f"global batch size {global_batch_size} is not divisible by the data-axis size {data_size}"
            )
        return global_batch_size // data_size

    # ------------------------------------------------------------------ #
    # checkpoint I/O (process-0 writes; reference fabric.save/load)
    # ------------------------------------------------------------------ #
    def save(self, path: str, state: Dict[str, Any]) -> None:
        from sheeprl_tpu.utils.checkpoint import save_checkpoint

        if self.is_global_zero:
            save_checkpoint(path, state)

    def load(self, path: str) -> Dict[str, Any]:
        from sheeprl_tpu.utils.checkpoint import load_checkpoint

        return load_checkpoint(path)

    def call(self, hook: str, **kwargs: Any) -> None:
        """Invoke ``hook`` on every registered callback (replaces
        ``fabric.call("on_checkpoint_coupled")``, dreamer_v3.py:752-758)."""
        for cb in self.callbacks:
            fn = getattr(cb, hook, None)
            if callable(fn):
                fn(fabric=self, **kwargs)

    def __repr__(self) -> str:
        return (
            f"Fabric(devices={self.world_size}, mesh={dict(self.mesh.shape)}, "
            f"precision={self.precision.name!r}, processes={jax.process_count()})"
        )


# --------------------------------------------------------------------------- #
# Player placement — learner-on-accelerator / actor-on-host split
# --------------------------------------------------------------------------- #
#
# The reference runs the player's forward on the same device as training (its
# player shares CUDA storage with the trainer, dreamer_v3/agent.py:1229-1235),
# and so does this framework by default. Every per-env-step action fetch pays
# one dispatch round trip, which caps env-steps/sec at 1/round-trip regardless
# of model speed. When a tiny-op round trip on the default backend measures
# above ``_RTT_PROBE_THRESHOLD_S``, the policy-inference nets (small in every
# reference recipe) run on the host CPU backend instead, with parameters
# streamed accelerator→host once per train block.

_RTT_PROBE_THRESHOLD_S = 0.005
_rtt_cache: Dict[str, float] = {}


def dispatch_roundtrip_seconds() -> float:
    """Measured dispatch+fetch latency of a tiny op on the default backend
    (compile excluded, cached per process)."""
    if "rtt" not in _rtt_cache:
        import time

        f = jax.jit(lambda a: a + 1.0)
        x = jnp.zeros((1,), jnp.float32)
        np.asarray(f(x))  # compile
        t0 = time.perf_counter()
        for _ in range(3):
            np.asarray(f(x))
        _rtt_cache["rtt"] = (time.perf_counter() - t0) / 3
    return _rtt_cache["rtt"]


#: params budget under which 'auto' may host-train: a 2x64 control MLP's whole
#: fused update is cheap on one CPU core; a pixel CNN (>~1M params) stays on
#: the accelerator whatever the round trip
_HOST_TRAIN_PARAM_BUDGET = 300_000


def resolve_train_device(spec: str, params: Any, world_size: int) -> Optional[jax.Device]:
    """Resolve a train-placement spec to a device (None = default backend).

    The PPO-family interaction loop is dominated by the env loop on the
    host; each update ships a tiny minibatch program to the accelerator
    (upload + dispatch + metric and param fetches). ``auto`` host-trains when
    that cannot pay off: single device, a dispatch round trip above
    ``_RTT_PROBE_THRESHOLD_S`` (same probe as the player), and a model under
    ``_HOST_TRAIN_PARAM_BUDGET`` params. Multi-device runs always train on
    the mesh. The outcome lands in the run record (``resolved.train_device``).
    """
    from sheeprl_tpu.obs.telemetry import telemetry_resolved

    if spec not in (None, "accelerator", "device", "cpu", "auto"):
        raise ValueError(f"unknown train_device spec {spec!r} (accelerator | cpu | auto)")
    device, fields = None, {}
    if world_size > 1:
        if spec == "cpu":
            raise ValueError("algo.train_device=cpu requires a single-device run")
    elif spec == "cpu":
        device = jax.local_devices(backend="cpu")[0]
    elif spec == "auto" and jax.local_devices()[0].platform != "cpu":
        n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
        fields = {"n_params": n_params}
        if n_params <= _HOST_TRAIN_PARAM_BUDGET:
            fields["roundtrip_s"] = dispatch_roundtrip_seconds()
            if fields["roundtrip_s"] > _RTT_PROBE_THRESHOLD_S:
                device = jax.local_devices(backend="cpu")[0]
    telemetry_resolved("train_device", _placement_name(device), spec=str(spec), **fields)
    return device


def resolve_player_device(spec: str = "auto") -> Optional[jax.Device]:
    """Resolve a player-placement spec to a device (None = default backend).

    - ``accelerator``: play on the training backend (reference behavior).
    - ``cpu``: play on the host CPU backend.
    - ``auto``: play on the training backend unless a tiny-op probe measures
      a dispatch round trip above 5 ms — then the host runs the policy and
      the env loop never waits on the accelerator. This includes conv
      policies: a pixel-encoder forward at the S model size is a few ms on
      one host core.

    The outcome lands in the run record (``resolved.player_device``).
    """
    from sheeprl_tpu.obs.telemetry import telemetry_resolved

    if spec not in (None, "accelerator", "cpu", "auto"):
        raise ValueError(f"unknown player device spec {spec!r}; use accelerator/cpu/auto")
    device, fields = None, {}
    if jax.default_backend() != "cpu":
        if spec == "cpu":
            device = jax.local_devices(backend="cpu")[0]
        elif spec == "auto":
            fields = {"roundtrip_s": dispatch_roundtrip_seconds()}
            if fields["roundtrip_s"] > _RTT_PROBE_THRESHOLD_S:
                device = jax.local_devices(backend="cpu")[0]
    telemetry_resolved("player_device", _placement_name(device), spec=str(spec), **fields)
    return device


def _placement_name(device: Optional[jax.Device]) -> str:
    """Run-record spelling of a resolved placement: the platform it runs on."""
    return jax.default_backend() if device is None else device.platform


def tree_devices(tree: Any) -> list:
    """Sorted ``platform:id`` of every device that holds a ``jax.Array`` leaf
    of ``tree`` — where the state actually is (``.devices()`` of the leaves),
    whatever ``jax.default_backend()`` says."""
    return sorted(
        {
            f"{d.platform}:{d.id}"
            for leaf in jax.tree.leaves(tree)
            if isinstance(leaf, jax.Array)
            for d in leaf.devices()
        }
    )


def put_tree(tree: Any, device: Optional[jax.Device]) -> Any:
    """``jax.device_put`` a pytree onto ``device`` (async); identity when
    ``device`` is None. The cross-backend accelerator→CPU copy is how player
    params refresh after each train block in host-player mode."""
    if device is None:
        return tree
    return jax.device_put(tree, device)


class _ParamStreamer:
    """One-round-trip cross-backend pytree transfer.

    ``jax.device_put`` of a pytree moves it leaf by leaf — one dispatch
    round trip PER LEAF. This packs every leaf into a single byte vector
    with a jitted concat on the source backend, crosses once, and rebuilds
    the tree with a jitted split on the target backend — the TPU analogue of
    the reference's flat param-vector broadcast (ppo_decoupled.py:126-130),
    and the wire format of ``actor_learner/param_lane.py``."""

    def __init__(self, tree: Any, device: jax.Device) -> None:
        leaves, self.treedef = jax.tree.flatten(tree)
        self.shapes = tuple(tuple(l.shape) for l in leaves)
        self.dtypes = tuple(jnp.dtype(l.dtype) for l in leaves)
        self.device = device
        sizes = [int(np.prod(s)) * d.itemsize for s, d in zip(self.shapes, self.dtypes)]
        self.offsets = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
        self.nbytes = int(self.offsets[-1])  # pack size; sizes the landing estimate

        def _to_bytes(leaf, dtype):
            if dtype == jnp.uint8:
                return leaf.reshape(-1)
            if dtype == jnp.dtype(jnp.bool_):
                return leaf.astype(jnp.uint8).reshape(-1)
            # same-width bitcast for int8, per-byte split for wider dtypes
            return jax.lax.bitcast_convert_type(leaf, jnp.uint8).reshape(-1)

        def pack(leaves):
            return jnp.concatenate([_to_bytes(l, d) for l, d in zip(leaves, self.dtypes)])

        def unpack(flat):
            out = []
            for s, d, o0, o1 in zip(self.shapes, self.dtypes, self.offsets[:-1], self.offsets[1:]):
                seg = flat[int(o0) : int(o1)]
                if d == jnp.uint8:
                    out.append(seg.reshape(s))
                elif d == jnp.dtype(jnp.bool_):
                    out.append(seg.reshape(s).astype(d))
                elif d.itemsize == 1:
                    out.append(jax.lax.bitcast_convert_type(seg.reshape(s), d))
                else:
                    out.append(jax.lax.bitcast_convert_type(seg.reshape(s + (d.itemsize,)), d))
            return out

        self._pack = jax.jit(pack)
        self._unpack = jax.jit(unpack)

    def matches(self, tree: Any) -> bool:
        leaves, treedef = jax.tree.flatten(tree)
        return (
            treedef == self.treedef
            and tuple(tuple(l.shape) for l in leaves) == self.shapes
            and tuple(jnp.dtype(l.dtype) for l in leaves) == self.dtypes
        )

    def __call__(self, tree: Any) -> Any:
        leaves = jax.tree.leaves(tree)
        flat = self._pack(leaves)
        flat = jax.device_put(flat, self.device)
        return jax.tree.unflatten(self.treedef, self._unpack(flat))

    # Deferred two-phase transfer: ``begin`` packs on the source backend and
    # starts the device→host copy without waiting for it; ``finish`` (called
    # a train block or two later) materializes the bytes — by then the copy
    # has landed and costs ~0 instead of one blocking round trip. This is
    # what lets a host-pinned player refresh params without ever stalling
    # the env loop on the transfer.
    def begin(self, tree: Any) -> Any:
        return self.send(self.pack(tree))

    # ``begin`` in its two halves, for a tree that has to wait its turn: packed
    # at once, so that nothing holds the tree's own leaves past the call (a
    # train step may donate them), and sent when the link is free.
    def pack(self, tree: Any) -> Any:
        return self._pack(jax.tree.leaves(tree))

    def send(self, flat: Any) -> Any:
        try:
            flat.copy_to_host_async()
        except AttributeError:  # non-jax.Array inputs (already host)
            pass
        return flat

    def finish(self, flat: Any) -> Any:
        host = np.asarray(flat)
        placed = jax.device_put(host, self.device)
        return jax.tree.unflatten(self.treedef, self._unpack(placed))


class DispatchFence:
    """Bounded-backlog throttle for fully-asynchronous training loops.

    A loop that never fetches from the device can race arbitrarily far ahead
    of it, queueing executions (and the buffers they pin) without bound.
    ``push`` takes any device array from
    the newest dispatch group, keeps a 1-element slice of it as a marker with
    an async device→host copy, and blocks on the OLDEST marker once more than
    ``depth`` groups are in flight — so the host stays at most ``depth``
    groups ahead while paying ~0 per fence in the steady state (the old
    marker's copy has long landed)."""

    def __init__(self, depth: int = 4) -> None:
        import collections

        self.depth = max(1, int(depth))
        self._pending: "collections.deque" = collections.deque()

    def push(self, marker: Any) -> None:
        m = jnp.ravel(marker)[:1]
        try:
            m.copy_to_host_async()
        except AttributeError:
            pass
        self._pending.append(m)
        while len(self._pending) > self.depth:
            np.asarray(self._pending.popleft())

    def drain(self) -> None:
        while self._pending:
            np.asarray(self._pending.popleft())


class _StreamPipe:
    """At-most-one-in-flight async param stream with a pending candidate.

    ``offer`` never blocks: if a transfer is in flight the newest tree is
    packed, the pack stashed and streamed when the current one lands. Either
    way the tree is read by a program enqueued before ``offer`` returns and
    none of its leaves is kept, so the caller may donate them to its next
    dispatch. ``poll`` returns a materialized tree once the in-flight copy is
    old enough to have landed (age gate — there is no completion event to
    poll for a host copy), else None."""

    def __init__(self, streamer: "_ParamStreamer") -> None:
        self.streamer = streamer
        self._inflight: Optional[Tuple[Any, float]] = None
        self._candidate: Any = None

    @staticmethod
    def _link_bytes_per_s() -> float:
        """Assumed device→host bulk bandwidth for the landing estimate when
        the dispatch round trip is above the probe threshold (10 MB/s unless
        SHEEPRL_TPU_LINK_BYTES_PER_S says otherwise)."""
        try:
            value = float(os.environ.get("SHEEPRL_TPU_LINK_BYTES_PER_S", 10e6))
        except ValueError:
            return 10e6
        # `v > 1e3` is False for nan too — max() would keep nan and silently
        # disable the bytes term of the gate
        return value if value > 1e3 else 1e3

    def _age_threshold(self) -> float:
        # the copy cannot have landed before bytes/bandwidth + one round
        # trip have passed; polling earlier turns the "free" finish into a
        # BLOCKING partial-transfer wait. Waiting the full landing estimate
        # costs only param staleness, which the async design already
        # accepts. The bytes term only applies when the dispatch round trip
        # is above the probe threshold (same probe as player
        # auto-placement) — below it the device moves GB/s and the cheap
        # gate is right.
        rtt = dispatch_roundtrip_seconds()
        if rtt <= _RTT_PROBE_THRESHOLD_S:
            return max(1.5 * rtt, 0.02)
        xfer = self.streamer.nbytes / self._link_bytes_per_s()
        return max(1.5 * rtt, 0.02, xfer + rtt)

    def offer(self, tree: Any) -> None:
        import time

        if self._inflight is None:
            self._inflight = (self.streamer.begin(tree), time.perf_counter())
        else:
            self._candidate = self.streamer.pack(tree)

    def poll(self) -> Any:
        import time

        if self._inflight is None:
            return None
        flat, t0 = self._inflight
        if time.perf_counter() - t0 < self._age_threshold():
            return None
        tree = self.streamer.finish(flat)
        self._inflight = None
        if self._candidate is not None:
            self._inflight = (self.streamer.send(self._candidate), time.perf_counter())
            self._candidate = None
        return tree

    def flush(self) -> Any:
        """Force-finish everything in flight (end of training): returns the
        NEWEST tree, blocking as needed — the age gate does not apply."""
        out = None
        if self._inflight is not None:
            out = self.streamer.finish(self._inflight[0])
            self._inflight = None
        if self._candidate is not None:
            out = self.streamer.finish(self._candidate)
            self._candidate = None
        return out


class HostPlayerParams:
    """Mixin for player classes: any assignment to an attribute named in
    ``_placed_attrs`` is placed onto ``self.device`` (async) when the player
    is pinned to another backend. This keeps every
    ``player.params = new_params`` sync site in the algorithm loops — and the
    exploration/task actor swaps of the P2E entrypoints — correct in
    host-player mode without touching the call sites; with ``device=None``
    assignments pass through untouched.

    Cross-backend trees with several device-resident leaves stream as ONE
    flat transfer (see ``_ParamStreamer``); host/numpy trees and trees
    already on the target device fall through to a plain ``device_put``."""

    _placed_attrs: Tuple[str, ...] = ()

    def __setattr__(self, name: str, value: Any) -> None:
        if name in self._placed_attrs and value is not None:
            dev = getattr(self, "device", None)
            if dev is not None:
                value = self._place(name, value, dev)
        object.__setattr__(self, name, value)

    def _place(self, name: str, value: Any, dev: jax.Device) -> Any:
        remote = [
            l
            for l in jax.tree.leaves(value)
            if isinstance(l, jax.Array) and dev not in l.devices()
        ]
        if len(remote) <= 2:
            return jax.device_put(value, dev)
        streamer = self._streamer_for(name, value, dev)
        return streamer(value)

    def _streamer_for(self, name: str, value: Any, dev: jax.Device) -> "_ParamStreamer":
        streamers = getattr(self, "_streamers", None)
        if streamers is None:
            streamers = {}
            object.__setattr__(self, "_streamers", streamers)
        streamer = streamers.get(name)
        if streamer is None or not streamer.matches(value):
            streamer = _ParamStreamer(value, dev)
            streamers[name] = streamer
        return streamer

    def stream_attr(self, name: str, value: Any) -> None:
        """Non-blocking variant of ``self.<name> = value`` for hot loops.

        Synchronous placement pays one blocking device→host round trip per
        train block. This streams the
        tree through a :class:`_StreamPipe` instead: the assignment returns
        immediately and the attribute flips to the new params one or two
        blocks later, once the async copy has landed. Use only where a few
        blocks of param staleness is acceptable (the actor-learner lag of any
        async RL system); latency-sensitive swaps (e.g. exchanging the
        exploration actor for the task actor) must keep plain assignment."""
        dev = getattr(self, "device", None)
        if dev is None or value is None:
            object.__setattr__(self, name, value)
            return
        remote = [
            l
            for l in jax.tree.leaves(value)
            if isinstance(l, jax.Array) and dev not in l.devices()
        ]
        if len(remote) <= 2:
            object.__setattr__(self, name, jax.device_put(value, dev))
            return
        pipes = getattr(self, "_stream_pipes", None)
        if pipes is None:
            pipes = {}
            object.__setattr__(self, "_stream_pipes", pipes)
        streamer = self._streamer_for(name, value, dev)
        pipe = pipes.get(name)
        if pipe is None or pipe.streamer is not streamer:
            pipe = _StreamPipe(streamer)
            pipes[name] = pipe
        landed = pipe.poll()
        if landed is not None:
            object.__setattr__(self, name, landed)
        pipe.offer(value)

    def poll_stream_attrs(self) -> None:
        """Land any in-flight async param stream that has finished copying
        (non-blocking). Players call this from the action path so params
        still flip under sparse Ratio schedules, where the next
        :meth:`stream_attr` call — the only other landing site — may be many
        env steps away."""
        pipes = getattr(self, "_stream_pipes", None)
        if not pipes:
            return
        for name, pipe in pipes.items():
            landed = pipe.poll()
            if landed is not None:
                object.__setattr__(self, name, landed)

    def flush_stream_attrs(self) -> None:
        """Land every in-flight async param stream NOW (blocking). Training
        loops call this after their last update so the closing evaluation /
        model registration sees the final weights, not ones a train block
        stale."""
        pipes = getattr(self, "_stream_pipes", None)
        if not pipes:
            return
        for name, pipe in pipes.items():
            tree = pipe.flush()
            if tree is not None:
                object.__setattr__(self, name, tree)
