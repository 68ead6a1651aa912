"""AOT executable cache: serialize compiled XLA executables, skip the compile.

The persistent XLA trace cache (``fabric.configure_compilation_cache``) still
re-traces, re-lowers, and round-trips XLA on every boot. This module caches
the *final product* — the loaded executable — via
``jax.experimental.serialize_executable``, so a replica restart, fleet
scale-up, or preemption-resume deserializes in O(seconds) instead of
recompiling in O(minutes).

**Key schema.** An entry is keyed by the canonical-JSON digest of::

    cache_version × tag × input avals (treedef + shape/dtype/weak_type)
    × params structural digest × caller fingerprint (e.g. config subtree)
    × topology (backend, jax version, device kinds/count, process count,
      mesh axes/shape, pinned device)

Executables close over *shapes*, not weights (params are call arguments), so
the params component is the structural :func:`tree_digest`, not a value hash
— a hot-swapped checkpoint with identical structure reuses the same entry.
Any drift in the other components (new jax wheel, different mesh, different
chip) lands on a different file name and misses cleanly.

**Commit discipline.** Stores follow the ``resilience/manifest`` pattern:
payload staged under a ``.tmp-`` name in the cache dir, fsync'd, then
promoted by a single ``os.replace`` — a reader never observes a torn entry.
Stale staging files from a crashed writer are swept by :meth:`AotCache.gc_torn`.
Writes run on a background daemon thread (joined in :meth:`AotCache.close`)
so the cold path never waits on serialization IO.

**Never a hard dependency.** Every failure mode — missing entry, corrupt or
torn file, deserialization error, serialization error — degrades to the
existing compile path with an ``aot_cache`` telemetry event. A corrupt entry
is GC'd on sight so it cannot poison the next boot.
"""

from __future__ import annotations

import atexit
import hashlib
import json
import os
import pickle
import queue
import tempfile
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import numpy as np

from sheeprl_tpu.obs.telemetry import telemetry_aot_cache, telemetry_aot_load
from sheeprl_tpu.resilience.manifest import tree_digest

CACHE_VERSION = 2  # 2: entries carry the device ids they were compiled for
ENTRY_SUFFIX = ".aotx"
# staging prefix for atomic entry promotes (matches the manifest discipline)
TMP_PREFIX = ".tmp-"


def _canonical(obj: Any) -> str:
    return json.dumps(obj, sort_keys=True, separators=(",", ":"), default=str)


# serializes toggles of the global trace-cache switch in _compile_serializable
_COMPILE_CONFIG_LOCK = threading.Lock()


def _compile_serializable(compile_fn: Callable[[], Any]) -> Any:
    """Run ``compile_fn`` with the persistent XLA trace cache switched off.

    An executable whose compile *hits* that cache deserializes fine for
    dispatch but does not survive ``serialize_executable`` — the payload
    loads with "Symbols not found" (CPU backend), so :meth:`AotCache.store`'s
    round-trip verification refuses it and the AOT tier silently never
    populates. The trace cache buys nothing here anyway: this tier caches
    the final executable, one level above it. The switch is
    ``jax_enable_compilation_cache`` (the cache's directory is never
    touched), and JAX latches "cache in use" at its first compile, so each
    flip is followed by ``reset_cache()``. Restored on exit so every other
    compile in the process keeps the trace cache."""
    from jax.experimental.compilation_cache import compilation_cache

    with _COMPILE_CONFIG_LOCK:
        if not jax.config.jax_enable_compilation_cache:
            return compile_fn()
        try:
            jax.config.update("jax_enable_compilation_cache", False)
            compilation_cache.reset_cache()
            return compile_fn()
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
            compilation_cache.reset_cache()


def _leaf_aval(leaf: Any) -> Tuple[Any, ...]:
    """(shape, dtype, weak_type) of a leaf — arrays, ShapeDtypeStructs and
    Python scalars alike — without materializing anything on device."""
    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
        return (tuple(int(d) for d in leaf.shape), str(leaf.dtype), bool(getattr(leaf, "weak_type", False)))
    # a bare Python scalar traces weak-typed
    return ((), str(np.asarray(leaf).dtype), True)


def avals_digest(tree: Any) -> str:
    """Short digest of a pytree's treedef + leaf avals. Two argument lists
    with the same digest lower to the same executable signature."""
    flat, treedef = jax.tree.flatten(tree)
    parts = [str(treedef)] + [_canonical(_leaf_aval(leaf)) for leaf in flat]
    return hashlib.md5("\n".join(parts).encode()).hexdigest()[:16]


def _runtime_versions() -> Dict[str, Any]:
    """jax + backend identity (patchable in tests to simulate version bumps)."""
    versions: Dict[str, Any] = {"jax": jax.__version__}
    try:
        versions["platform_version"] = str(jax.devices()[0].client.platform_version)
    except Exception:
        pass
    return versions


def topology_key(mesh: Any = None, device: Any = None) -> Dict[str, Any]:
    """The topology component of a cache key. Serialized executables bake in
    their device assignment, so the pinned ``device`` (fleet per-replica
    ladders) and the mesh shape both participate."""
    devs = jax.devices()
    key: Dict[str, Any] = {
        "backend": jax.default_backend(),
        "device_kinds": sorted({str(d.device_kind) for d in devs}),
        "device_count": len(devs),
        "process_count": jax.process_count(),
    }
    key.update(_runtime_versions())
    if mesh is not None:
        key["mesh_axes"] = [str(a) for a in mesh.axis_names]
        key["mesh_shape"] = [int(s) for s in np.shape(mesh.devices)]
    if device is not None:
        key["device"] = str(device)
    return key


def config_fingerprint(node: Any) -> str:
    """Digest of a config subtree — the cache-key component that guards
    against same-shape-but-different-constants staleness (e.g. a learning
    rate baked into the train graph as a literal)."""
    to_dict = getattr(node, "to_dict", None)
    if callable(to_dict):
        node = to_dict()
    return hashlib.md5(_canonical(node).encode()).hexdigest()[:12]


class CacheKey(NamedTuple):
    """A fully-resolved cache key: the human-auditable ``parts`` dict and the
    digest that names the entry file."""

    tag: str
    parts: Dict[str, Any]
    digest: str


def _compiled_device_ids(compiled: Any) -> List[int]:
    """Ids of the devices ``compiled`` was built for, in assignment order —
    the same ``_unloaded_executable`` that ``serialize_executable.serialize``
    pickles. A serialized executable must be loaded onto exactly these: left
    to its default, ``deserialize_and_load`` loads over EVERY device of the
    backend and a one-device executable then refuses its arguments."""
    return [int(d.id) for d in compiled._executable._unloaded_executable.device_list]


def _devices_by_id(device_ids: List[int]) -> List[Any]:
    by_id = {d.id: d for d in jax.devices()}
    return [by_id[i] for i in device_ids]


def _sanitize(tag: str) -> str:
    return "".join(c if c.isalnum() or c in "-_." else "_" for c in tag)[:64]


# live caches flush their writer queues at interpreter exit so a short-lived
# training process never loses the store it just paid a compile for
_LIVE_CACHES: "weakref.WeakSet[AotCache]" = weakref.WeakSet()


def _drain_live_caches() -> None:
    for cache in list(_LIVE_CACHES):
        try:
            cache.close()
        except Exception:
            pass


atexit.register(_drain_live_caches)


class AotCache:
    """Directory of serialized compiled executables with atomic commits.

    ``load``/``store`` are thread-safe; stores are staged on a background
    daemon writer thread (stop event + join in :meth:`close` — JX08) unless
    ``sync=True``. All failures degrade to ``None``/no-op with an
    ``aot_cache`` telemetry event; nothing here ever raises into a cold path.
    """

    def __init__(self, cache_dir: str, *, sweep_torn_s: float = 3600.0) -> None:
        self.cache_dir = os.path.abspath(str(cache_dir))
        os.makedirs(self.cache_dir, exist_ok=True)
        self.hits = 0
        self.misses = 0
        self.stores = 0
        self.errors = 0
        self._lock = threading.Lock()
        self._queue: "queue.Queue[Optional[Tuple[CacheKey, Any]]]" = queue.Queue()
        self._stop = threading.Event()
        self._writer: Optional[threading.Thread] = None
        self._closed = False
        # staging files older than the sweep age are orphans from a crashed
        # writer; young ones may belong to a live sibling process, leave them
        self.gc_torn(max_age_s=float(sweep_torn_s))
        _LIVE_CACHES.add(self)

    # ------------------------------------------------------------------- keys
    def key(
        self,
        *,
        tag: str,
        avals: Any,
        params: Any = None,
        fingerprint: Optional[str] = None,
        mesh: Any = None,
        device: Any = None,
        extra: Optional[Dict[str, Any]] = None,
    ) -> CacheKey:
        """Build the entry key for an executable lowered against ``avals``
        (any pytree of arrays/specs — typically the call arguments)."""
        parts: Dict[str, Any] = {
            "cache_version": CACHE_VERSION,
            "tag": str(tag),
            "avals": avals_digest(avals),
            "topology": topology_key(mesh=mesh, device=device),
        }
        if params is not None:
            leaf_count, digest = tree_digest(params)
            parts["params_digest"] = [leaf_count, digest]
        if fingerprint is not None:
            parts["fingerprint"] = str(fingerprint)
        if extra:
            parts["extra"] = dict(extra)
        digest = hashlib.md5(_canonical(parts).encode()).hexdigest()
        return CacheKey(str(tag), parts, digest)

    def entry_path(self, key: CacheKey) -> str:
        return os.path.join(self.cache_dir, f"{_sanitize(key.tag)}-{key.digest}{ENTRY_SUFFIX}")

    def has(self, key: CacheKey) -> bool:
        return os.path.isfile(self.entry_path(key))

    # ------------------------------------------------------------------- load
    def load(self, key: CacheKey) -> Optional[Any]:
        """Deserialize the executable for ``key``, or ``None`` on any miss:
        absent entry (clean miss), corrupt/torn/foreign entry (GC'd), or
        deserialization failure. The caller falls back to compile."""
        path = self.entry_path(key)
        if not os.path.isfile(path):
            self.misses += 1
            telemetry_aot_cache("miss", key.tag, digest=key.digest)
            return None
        t0 = time.perf_counter()
        try:
            with open(path, "rb") as f:
                doc = pickle.load(f)
            if not isinstance(doc, dict) or doc.get("cache_version") != CACHE_VERSION:
                raise ValueError(f"unsupported cache entry version {doc.get('cache_version') if isinstance(doc, dict) else type(doc)}")
            if doc.get("key") != key.parts:
                raise ValueError("embedded key does not match requested key (corrupt or foreign entry)")
            from jax.experimental import serialize_executable as _se

            # compile events XLA fires while loading a serialized executable
            # are neither recompiles nor `deliberate:` compiles — classify
            # them under the aot-load window so the watchdog stays quiet
            with telemetry_aot_load(key.tag):
                fn = _se.deserialize_and_load(
                    doc["payload"],
                    doc["in_tree"],
                    doc["out_tree"],
                    execution_devices=_devices_by_id(doc["device_ids"]),
                )
        except Exception as err:
            self.errors += 1
            telemetry_aot_cache("corrupt_gc", key.tag, digest=key.digest, error=repr(err))
            try:
                os.remove(path)
            except OSError:
                pass
            return None
        self.hits += 1
        telemetry_aot_cache(
            "hit",
            key.tag,
            digest=key.digest,
            load_s=time.perf_counter() - t0,
            bytes=os.path.getsize(path) if os.path.isfile(path) else None,
        )
        return fn

    # ------------------------------------------------------------------ store
    def store(self, key: CacheKey, compiled: Any, *, sync: bool = False) -> None:
        """Persist ``compiled`` (a ``jax.stages.Compiled``) under ``key``.
        Asynchronous by default — the writer thread serializes and commits so
        the cold path never waits; ``sync=True`` commits before returning
        (prewarm and tests). Failures are events, never exceptions."""
        if self._closed:
            sync = True
        if sync:
            self._write_entry(key, compiled)
            return
        with self._lock:
            if self._writer is None or not self._writer.is_alive():
                self._writer = threading.Thread(
                    target=self._writer_loop, name="aot-cache-writer", daemon=True
                )
                self._writer.start()
        self._queue.put((key, compiled))

    def _writer_loop(self) -> None:
        while not self._stop.is_set():
            try:
                item = self._queue.get(timeout=0.2)
            except queue.Empty:
                continue
            try:
                if item is not None:
                    self._write_entry(*item)
            finally:
                self._queue.task_done()

    def _write_entry(self, key: CacheKey, compiled: Any) -> None:
        t0 = time.perf_counter()
        tmp = None
        try:
            from jax.experimental import serialize_executable as _se

            payload, in_tree, out_tree = _se.serialize(compiled)
            device_ids = _compiled_device_ids(compiled)
            # verify the payload round-trips BEFORE committing: an executable
            # that itself came out of the XLA persistent trace cache can
            # serialize into an unloadable payload (CPU backend: "Symbols not
            # found") — committed, it would cost every future boot a
            # corrupt_gc + recompile instead of a hit
            _se.deserialize_and_load(
                payload, in_tree, out_tree, execution_devices=_devices_by_id(device_ids)
            )
            doc = {
                "cache_version": CACHE_VERSION,
                "key": key.parts,
                "device_ids": device_ids,
                "payload": payload,
                "in_tree": in_tree,
                "out_tree": out_tree,
            }
            fd, tmp = tempfile.mkstemp(dir=self.cache_dir, prefix=TMP_PREFIX, suffix=ENTRY_SUFFIX)
            with os.fdopen(fd, "wb") as f:
                pickle.dump(doc, f, protocol=pickle.HIGHEST_PROTOCOL)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.entry_path(key))
            tmp = None
        except Exception as err:
            self.errors += 1
            if os.environ.get("SHEEPRL_TPU_AOT_DEBUG"):
                import traceback

                traceback.print_exc()
            telemetry_aot_cache("store_failed", key.tag, digest=key.digest, error=repr(err))
            if tmp is not None and os.path.exists(tmp):
                try:
                    os.remove(tmp)
                except OSError:
                    pass
            return
        self.stores += 1
        telemetry_aot_cache(
            "store",
            key.tag,
            digest=key.digest,
            store_s=time.perf_counter() - t0,
            bytes=os.path.getsize(self.entry_path(key)),
        )

    # --------------------------------------------------------------- combined
    def load_or_compile(self, key: CacheKey, compile_fn: Callable[[], Any], *, sync_store: bool = False) -> Tuple[Any, bool]:
        """``(executable, from_cache)`` — deserialize on hit, else run
        ``compile_fn`` and persist its result for the next boot."""
        fn = self.load(key)
        if fn is not None:
            return fn, True
        compiled = _compile_serializable(compile_fn)
        self.store(key, compiled, sync=sync_store)
        return compiled, False

    # --------------------------------------------------------------- lifecycle
    def flush(self, timeout: Optional[float] = None) -> None:
        """Block until queued stores have committed (best-effort when a
        timeout is given)."""
        if timeout is None:
            self._queue.join()
            return
        deadline = time.monotonic() + timeout
        while self._queue.unfinished_tasks and time.monotonic() < deadline:
            time.sleep(0.01)

    def close(self, timeout: float = 30.0) -> None:
        """Drain pending stores and stop the writer thread."""
        self._closed = True
        self.flush(timeout=timeout)
        self._stop.set()
        with self._lock:
            writer = self._writer
        if writer is not None and writer.is_alive():
            writer.join(timeout=timeout)
        _LIVE_CACHES.discard(self)

    # --------------------------------------------------------------------- gc
    def torn_entries(self, max_age_s: float = 0.0) -> List[str]:
        """Staging files older than ``max_age_s`` — orphans from a crashed
        writer (a committed entry is never in this state; promotion is one
        rename)."""
        now = time.time()
        torn: List[str] = []
        try:
            entries = os.listdir(self.cache_dir)
        except OSError:
            return torn
        for entry in entries:
            if not entry.startswith(TMP_PREFIX):
                continue
            path = os.path.join(self.cache_dir, entry)
            try:
                if now - os.path.getmtime(path) >= max_age_s:
                    torn.append(path)
            except OSError:
                continue
        return sorted(torn)

    def gc_torn(self, max_age_s: float = 0.0) -> List[str]:
        """Delete orphaned staging files. Returns the paths removed."""
        removed: List[str] = []
        for path in self.torn_entries(max_age_s=max_age_s):
            try:
                os.remove(path)
                removed.append(path)
            except OSError:
                pass
        if removed:
            telemetry_aot_cache("torn_gc", "", removed=len(removed))
        return removed

    def stats(self) -> Dict[str, int]:
        return {"hits": self.hits, "misses": self.misses, "stores": self.stores, "errors": self.errors}


class AotCachedFunction:
    """Wrap a ``jax.jit``-ed function with the executable cache.

    The first call per input-aval signature resolves an executable: cache hit
    deserializes, miss lowers from the concrete arguments, compiles, and
    stores for the next process. Later calls dispatch straight to the
    resolved ``Compiled`` — same donation semantics as the jitted original
    (``lower`` inspects avals only; nothing is donated until the call).
    A distinct signature (e.g. a differently-shaped ctx window) gets its own
    entry, mirroring jit's per-signature executable cache.
    """

    def __init__(
        self,
        jitted: Any,
        cache: AotCache,
        *,
        tag: str,
        params: Any = None,
        fingerprint: Optional[str] = None,
        mesh: Any = None,
        extra: Optional[Dict[str, Any]] = None,
    ) -> None:
        self._jitted = jitted
        self._cache = cache
        self._tag = str(tag)
        self._params = params
        self._fingerprint = fingerprint
        self._mesh = mesh
        self._extra = dict(extra) if extra else None
        self._lock = threading.Lock()
        self._loaded: Dict[str, Any] = {}
        self.from_cache: Dict[str, bool] = {}

    def _resolve(self, args: Tuple[Any, ...]) -> Any:
        sig = avals_digest(args)
        with self._lock:
            fn = self._loaded.get(sig)
            if fn is not None:
                return fn
            key = self._cache.key(
                tag=self._tag,
                avals=args,
                params=self._params,
                fingerprint=self._fingerprint,
                mesh=self._mesh,
                extra=self._extra,
            )
            fn, hit = self._cache.load_or_compile(key, lambda: self._jitted.lower(*args).compile())
            self._loaded[sig] = fn
            self.from_cache[sig] = hit
            return fn

    def __call__(self, *args: Any) -> Any:
        return self._resolve(args)(*args)
