"""Replica-fleet serving: N data-parallel policy replicas behind one router.

The single :class:`~sheeprl_tpu.serve.server.PolicyServer` multiplexes
replica *threads* over one queue and one params reference. The fleet is the
next structural step: each :class:`FleetSlot` is a full serving unit — its
own continuous-batching :class:`~sheeprl_tpu.serve.slots.SlotPool`, its own
AOT ladder compiled for its *device*, its own device-resident copy of the
params (data-parallel placement, re-placed per hot-swap version) — and the
:class:`~sheeprl_tpu.serve.router.Router` in front owns every fleet-wide
decision. Composition:

- **supervision** — the single-server doctrine (detect dead/hung, restart
  under a :class:`~sheeprl_tpu.rollout.supervisor.RestartBudget` with
  exponential backoff, mask when the budget is spent, keep serving degraded
  on N-1) is re-instantiated per slot, with one fleet-shaping change: a dead
  replica's queued + in-flight work is *re-routed at the front of a sibling*
  (``router.reroute``) before the restart is even scheduled. The
  crash-requeue-at-front contract survives the jump from one queue to N.
- **elastic scaling** — the monitor doubles as the autoscaler: sustained
  queue depth per active replica above ``scale_up_depth`` activates a
  standby slot (its ladder is compiled *before* it takes traffic — warmup
  precedes routing, same as server start); sustained depth below
  ``scale_down_depth`` retires the newest active slot (router stops routing,
  its work re-homes, the thread drains out). ``min_replicas`` /
  ``max_replicas`` bound both directions.
- **CPU spill** — optional ``cpu_spill_replicas`` slots compiled for the
  host backend absorb ``batch``-priority traffic (eval / loadgen) when the
  device replicas are queueing past ``spill_depth``, keeping interactive
  latency flat while bulk traffic degrades gracefully instead of shedding.
- **chaos surface** — ``kill_replica(i)`` is the drill entry point: the
  replica dies *without completing its in-flight futures* (the worst legal
  crash), and the acceptance drill asserts zero admitted requests are
  dropped while the survivors hold the SLO.

:class:`FleetServer` keeps the exact :class:`PolicyServer` facade (``infer``
/ ``submit`` / ``wait`` / ``snapshot`` / ``request_swap``), so the client,
the load generator and the telemetry pipeline serve either tier unchanged.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

from sheeprl_tpu.obs.telemetry import telemetry_request_path
from sheeprl_tpu.obs.trace import trace_event
from sheeprl_tpu.resilience.manifest import CommittedCheckpoint, read_manifest
from sheeprl_tpu.rollout.supervisor import RestartBudget
from sheeprl_tpu.serve.config import ServeConfig
from sheeprl_tpu.serve.errors import DeadlineExceeded, ServerClosed, SwapRejected
from sheeprl_tpu.serve.fault_injection import ServeFaultSchedule
from sheeprl_tpu.serve.model import CompiledLadder, ModelStore, ModelVersion, ServedPolicy
from sheeprl_tpu.serve.replica import InjectedCrash, ReplicaStats
from sheeprl_tpu.serve.router import INTERACTIVE, RoutedRequest, Router, RouteTarget
from sheeprl_tpu.serve.server import ServeStats
from sheeprl_tpu.serve.slots import SlotPool, safe_complete

DEVICE = "device"
CPU_SPILL = "cpu_spill"
REMOTE = "remote"  # per-host agent adopted over TCP (sheeprl_tpu.net.remote)


class FleetReplica(threading.Thread):
    """One serving incarnation bound to one slot's pool/ladder/device.

    Differences from the single-server replica are exactly the fleet
    contracts: work it cannot finish stays *in its pool* (in-flight window
    included) for the router to re-home, and ``kill()`` makes it die without
    completing futures — the crash shape the chaos drill injects.
    """

    def __init__(
        self,
        index: int,
        *,
        pool: SlotPool,
        ladder: CompiledLadder,
        store: ModelStore,
        device: Any,
        stats: ReplicaStats,
        batch_counter: Any,
        breaker_threshold: int,
        fault_schedule: Optional[ServeFaultSchedule] = None,
        poll_timeout_s: float = 0.05,
        on_batch: Optional[Callable[[int, float], None]] = None,
        on_shed: Optional[Callable[[str], None]] = None,
    ) -> None:
        super().__init__(name=f"fleet-replica-{index}", daemon=True)
        self.index = index
        self.pool = pool
        self.ladder = ladder
        self.store = store
        self.device = device
        self.stats = stats
        self._batch_counter = batch_counter
        self.breaker_threshold = int(breaker_threshold)
        self._faults = fault_schedule
        self._poll_timeout_s = float(poll_timeout_s)
        self._on_batch = on_batch
        self._on_shed = on_shed
        self._stop_evt = threading.Event()
        self._killed = threading.Event()
        self._params_step: Optional[int] = None
        self._params: Any = None
        self.exit_reason: Optional[str] = None

    def request_stop(self) -> None:
        self._stop_evt.set()

    def kill(self) -> None:
        """Chaos entry point: die at the next check WITHOUT completing
        in-flight futures. The work stays in the pool for re-routing."""
        self._killed.set()
        self._stop_evt.set()

    # ------------------------------------------------------------------- loop
    def run(self) -> None:  # pragma: no cover - exercised via the fleet tests
        try:
            self._loop()
        except InjectedCrash as err:
            self.exit_reason = f"injected crash: {err}"
        except Exception as err:
            self.exit_reason = f"crashed: {err!r}"
        else:
            self.exit_reason = (
                "killed" if self._killed.is_set() else self.exit_reason or "stopped"
            )

    def _loop(self) -> None:
        while not self._stop_evt.is_set() and not self.pool.closed:
            self.stats.beat()
            batch = self.pool.take_batch(self._poll_timeout_s)
            if self._killed.is_set():
                return  # batch (if any) stays in the in-flight window
            if not batch:
                continue
            self._serve_batch(batch)

    def _serve_batch(self, batch: List[Any]) -> None:
        batch_index = next(self._batch_counter)
        if self._faults is not None:
            for fault in self._faults.batch_faults(self.index, batch_index):
                if fault.kind == "slow_inference":
                    self._sleep_injected(fault.duration_s)
                elif fault.kind == "replica_crash":
                    # the batch stays in the pool's in-flight window; the
                    # fleet monitor re-routes it at the front of a sibling
                    raise InjectedCrash(f"scheduled replica_crash at batch {batch_index}")
        t0 = time.monotonic()
        try:
            params = self._params_for()
            rung = self.ladder.rung_for(len(batch))
            staged = self.pool.staged_batch(batch, rung)
            t_staged = time.monotonic()
            outputs = self.ladder.run_staged(params, staged, rung, len(batch))
            t_done = time.monotonic()
        except Exception as err:
            self.stats.failures += 1
            self.stats.consecutive_failures += 1
            self.pool.requeue_failed(batch)
            if self.stats.consecutive_failures >= self.breaker_threshold:
                raise RuntimeError(
                    f"circuit breaker open after {self.stats.consecutive_failures} "
                    f"consecutive inference failures"
                ) from err
            return
        if self._killed.is_set():
            return  # die before delivery: futures stay pending → re-routed
        latency_s = time.monotonic() - t0
        self.stats.consecutive_failures = 0
        self.stats.batches += 1
        self.stats.requests += len(batch)
        self.stats.beat()
        now = time.monotonic()
        for req, out in zip(batch, outputs):
            if req.future.done():
                continue  # hedge twin won
            if req.expired(now):
                req.fail_expired(now)
                if self._on_shed is not None:
                    try:
                        self._on_shed("expired")
                    except Exception:
                        pass
            else:
                # stamp the serving checkpoint step BEFORE completion: the
                # online bridge reads it off the request right after wait()
                req.served_step = self._params_step
                delivered = safe_complete(req, out)
                if delivered and req.trace_id:
                    # critical-path decomposition, measured at the replica
                    # that actually delivered the result: queue-wait is
                    # admission→this batch's start, assembly is the staging
                    # row-gather + params placement, compute is the dispatch
                    queue_wait_ms = (t0 - req.enqueue_t) * 1e3
                    assembly_ms = (t_staged - t0) * 1e3
                    compute_ms = (t_done - t_staged) * 1e3
                    hedged = len(getattr(req, "placements", ())) > 1
                    rerouted = getattr(req, "rerouted", 0) > 0
                    trace_event(
                        "request_done",
                        req.trace_id,
                        rid=req.rid,
                        replica=self.index,
                        batch=len(batch),
                        queue_wait_ms=queue_wait_ms,
                        assembly_ms=assembly_ms,
                        compute_ms=compute_ms,
                        hedged=hedged,
                        rerouted=rerouted,
                    )
                    telemetry_request_path(
                        queue_wait_ms=queue_wait_ms,
                        assembly_ms=assembly_ms,
                        compute_ms=compute_ms,
                        hedged=hedged,
                        rerouted=rerouted,
                    )
        self.pool.complete_batch(batch)
        if self._on_batch is not None:
            try:
                self._on_batch(len(batch), latency_s)
            except Exception:
                pass

    def _params_for(self) -> Any:
        """The serving version's params, placed on this replica's device
        (re-placed once per promoted version, not per batch)."""
        version = self.store.current
        if self._params_step != version.step:
            params = version.params
            if self.device is not None:
                import jax

                try:
                    params = jax.device_put(version.params, self.device)
                except Exception:
                    params = version.params
            self._params = params
            self._params_step = version.step
        return self._params

    def _sleep_injected(self, duration_s: float) -> None:
        end = time.monotonic() + duration_s
        while not self._stop_evt.is_set():
            remaining = end - time.monotonic()
            if remaining <= 0:
                return
            self.stats.beat()  # slow, not hung
            time.sleep(min(0.02, remaining))


class FleetSlot:
    """One supervised fleet position. The slot — not any thread incarnation —
    owns the pool, the batch counter, the restart budget, the device binding
    and the activation state, so all of them survive restarts."""

    def __init__(self, index: int, kind: str, config: ServeConfig, *, obs_spec: Any = None) -> None:
        import itertools

        self.index = index
        self.kind = kind
        self.device: Any = None
        self.remote_addr: Optional[str] = None  # REMOTE slots: agent host:port
        self.pool = SlotPool(
            capacity=config.max_batch,
            backlog_bound=config.fleet.backlog_per_replica,
            obs_spec=obs_spec,
        )
        self.batch_counter = itertools.count()
        self.budget = RestartBudget(config.max_restarts, config.restart_refund_s)
        self.thread: Optional[Any] = None  # FleetReplica | net.remote.RemoteReplica
        self.stats: Optional[ReplicaStats] = None
        self.ladder: Optional[CompiledLadder] = None
        self.active = False  # routable position (autoscaler toggles)
        self.retiring = False
        self.masked = False
        self.mask_reason: Optional[str] = None
        self.restart_at: Optional[float] = None
        self.restart_deferrals = 0  # restarts held back by a hung-alive thread
        self.restarts = 0
        self.total_requests = 0
        self.total_failures = 0

    @property
    def alive(self) -> bool:
        return self.thread is not None and self.thread.is_alive()

    def health(self, now: float, timeout_s: float) -> float:
        """Routing weight in [0, 1]: 0 = unroutable, decaying with heartbeat
        age so a struggling replica sheds traffic before it is declared
        hung."""
        if not self.active or self.masked or self.retiring or not self.alive:
            return 0.0
        if self.restart_at is not None:
            # declared hung, awaiting restart: the thread may be alive (stuck
            # in a dispatch) but nothing will serve new work until respawn
            return 0.0
        if self.stats is None:
            return 0.0
        age = max(0.0, now - self.stats.heartbeat)
        return max(0.05, 1.0 - age / max(timeout_s, 1e-6))

    def fold_stats(self) -> None:
        if self.stats is not None:
            self.total_requests += self.stats.requests
            self.total_failures += self.stats.failures


class FleetServer:
    """N supervised replicas + router behind the ``PolicyServer`` facade."""

    def __init__(
        self,
        policy: ServedPolicy,
        config: ServeConfig,
        *,
        step: int,
        path: str,
        ckpt_dir: Optional[str] = None,
        on_event: Optional[Callable[[str, Dict[str, Any]], None]] = None,
    ) -> None:
        if not config.fleet.enabled:
            raise ValueError("FleetServer requires serve.fleet.enabled=true")
        self.config = config
        self.policy = policy
        self.step = int(step)
        self.path = str(path)
        self.ckpt_dir = ckpt_dir
        self._on_event = on_event
        self.stats = ServeStats()
        self.fault_schedule = ServeFaultSchedule(config.faults) if config.faults else None
        self.slots: List[FleetSlot] = []
        self.router: Optional[Router] = None
        self.store: Optional[ModelStore] = None
        self.aot_cache: Optional[Any] = None
        self._ladders: Dict[Any, CompiledLadder] = {}  # device -> compiled ladder
        self._monitor_thread: Optional[threading.Thread] = None
        self._swap_thread: Optional[threading.Thread] = None
        self._closing = threading.Event()
        self._started = False
        self._lock = threading.Lock()
        self.warmup_s: Dict[int, float] = {}
        self.scale_ups = 0
        self.scale_downs = 0
        self._pressure_streak = 0
        self._idle_streak = 0
        self._last_autoscale_t = 0.0

    # -------------------------------------------------------------- lifecycle
    def start(self) -> "FleetServer":
        """Warm the initial replicas' ladders, place params, open the front
        door. When this returns every initially-active replica is compiled
        and pulling; standby slots compile at activation, before routing."""
        if self._started:
            return self
        import jax

        if self.config.aot_cache_dir:
            from sheeprl_tpu.ops.aotcache import AotCache

            # one cache shared by every per-device ladder (entries are keyed
            # by device, so replicas never load a sibling's executable)
            self.aot_cache = AotCache(self.config.aot_cache_dir)
        fleet = self.config.fleet
        devices = self._device_ring()
        spill_devices = self._spill_devices()
        for i in range(fleet.max_replicas):
            slot = FleetSlot(i, DEVICE, self.config, obs_spec=self.policy.obs_spec)
            slot.device = devices[i % len(devices)] if devices else None
            self.slots.append(slot)
        for j in range(fleet.cpu_spill_replicas):
            slot = FleetSlot(
                fleet.max_replicas + j, CPU_SPILL, self.config, obs_spec=self.policy.obs_spec
            )
            slot.device = spill_devices[j % len(spill_devices)] if spill_devices else None
            self.slots.append(slot)
        for k, addr in enumerate(fleet.remote_agents):
            # a per-host agent adopted as one slot: the pool/budget/counter
            # live HERE, so re-route-at-front and budgeted restarts (which
            # for this kind are reconnects) run on unchanged machinery
            slot = FleetSlot(
                fleet.max_replicas + fleet.cpu_spill_replicas + k,
                REMOTE,
                self.config,
                obs_spec=self.policy.obs_spec,
            )
            slot.remote_addr = str(addr)
            self.slots.append(slot)

        base_ladder = self._ladder_for(None)
        self.warmup_s = dict(base_ladder.compile_s)
        self.store = ModelStore(
            self.policy,
            base_ladder,
            step=self.step,
            path=self.path,
            fault_schedule=self.fault_schedule,
            on_event=self._event,
        )
        for slot in self.slots:
            if slot.kind == DEVICE and slot.index >= fleet.num_replicas:
                continue  # standby: warms at activation
            slot.active = True
            if slot.kind != REMOTE:  # remote compute lives agent-side
                slot.ladder = self._ladder_for(slot.device)
            self._spawn(slot)

        self.router = Router(
            targets=self._route_targets,
            max_pending=fleet.resolved_max_pending(self.config),
            slo_s=self.config.slo_ms / 1e3,
            hedge_quantile=fleet.hedge_quantile,
            hedge_floor_s=fleet.hedge_floor_ms / 1e3,
            hedge_max=fleet.hedge_max,
            hedge_scan_s=fleet.hedge_scan_ms / 1e3,
            spill_depth=fleet.spill_depth,
            fault_schedule=self.fault_schedule,
            on_event=self._event,
        ).start()

        self._monitor_thread = threading.Thread(
            target=self._monitor, name="fleet-monitor", daemon=True
        )
        self._monitor_thread.start()
        if self.config.swap_poll_s > 0 and self.ckpt_dir:
            self._swap_thread = threading.Thread(
                target=self._swap_watch, name="fleet-swap-watch", daemon=True
            )
            self._swap_thread.start()
        self.stats.mark_started()
        self._started = True
        return self

    def close(self) -> None:
        self._closing.set()
        if self.router is not None:
            self.router.close()
        if self._monitor_thread is not None:
            self._monitor_thread.join(2.0)
        for slot in self.slots:
            if slot.thread is not None:
                slot.thread.request_stop()
        deadline = time.monotonic() + 2.0
        for slot in self.slots:
            if slot.thread is not None:
                slot.thread.join(max(0.0, deadline - time.monotonic()))
            slot.fold_stats()
            slot.pool.close()
        if self._swap_thread is not None:
            self._swap_thread.join(1.0)
        if self.aot_cache is not None:
            # drain queued executable stores (writer thread joins) so the
            # next spawn against this cache dir boots from cache
            self.aot_cache.close()

    def __enter__(self) -> "FleetServer":
        return self.start()

    def __exit__(self, *exc: Any) -> None:
        self.close()

    # ------------------------------------------------------------ request path
    def submit(
        self,
        obs: Any,
        deadline_s: Optional[float] = None,
        *,
        priority: str = INTERACTIVE,
        idempotent: bool = True,
    ) -> RoutedRequest:
        if not self._started or self.router is None:
            raise ServerClosed("fleet not started: warmup has not run")
        self.stats.record_submit()
        try:
            return self.router.submit(
                obs,
                deadline_s or self.config.default_deadline_s,
                idempotent=idempotent,
                priority=priority,
            )
        except Exception as err:
            from sheeprl_tpu.serve.errors import Overloaded

            if isinstance(err, Overloaded):
                self.stats.record_shed("overloaded")
            self.stats.record_failed()
            raise

    def infer(
        self,
        obs: Any,
        deadline_s: Optional[float] = None,
        *,
        priority: str = INTERACTIVE,
        idempotent: bool = True,
    ) -> Any:
        req = self.submit(obs, deadline_s, priority=priority, idempotent=idempotent)
        return self.wait(req)

    def wait(self, req: RoutedRequest) -> Any:
        from concurrent.futures import TimeoutError as FutureTimeout

        budget = max(0.0, req.deadline_t - time.monotonic()) + 0.25
        try:
            out = req.future.result(timeout=budget)
        except DeadlineExceeded:
            self.stats.record_failed()
            raise
        except (TimeoutError, FutureTimeout):
            self.stats.record_failed()
            now = time.monotonic()
            raise DeadlineExceeded(now - req.enqueue_t, req.deadline_t - req.enqueue_t) from None
        except Exception:
            self.stats.record_failed()
            raise
        latency = time.monotonic() - req.enqueue_t
        self.stats.record_complete(latency)
        if self.router is not None:
            self.router.record_latency(latency)
        return out

    # ------------------------------------------------------------------ chaos
    def kill_replica(self, index: int) -> bool:
        """Drill API: make replica ``index`` die without completing its
        in-flight futures. Returns False when it has no live thread."""
        slot = self.slots[index]
        if slot.thread is None or not slot.thread.is_alive():
            return False
        slot.thread.kill()
        self._event("replica_killed", {"replica": index})
        trace_event("replica_killed", replica=index)  # process-scoped (tid 0)
        return True

    # ------------------------------------------------------------------- swap
    def request_swap(self, ckpt_path: str) -> ModelVersion:
        if self.store is None:
            raise ServerClosed("fleet not started")
        man = read_manifest(ckpt_path)
        if man is None:
            raise SwapRejected(f"checkpoint {ckpt_path} has no commit manifest (torn or foreign write)")
        return self.store.request_swap(CommittedCheckpoint(int(man["step"]), ckpt_path, man))

    def maybe_swap(self) -> Optional[ModelVersion]:
        if self.store is None or not self.ckpt_dir:
            return None
        return self.store.maybe_swap_newest(self.ckpt_dir)

    def _swap_watch(self) -> None:
        while not self._closing.wait(self.config.swap_poll_s):
            try:
                self.maybe_swap()
            except Exception:
                pass

    # ------------------------------------------------------------------ stats
    def snapshot(self) -> Dict[str, Any]:
        snap = self.stats.snapshot()
        snap["slo_ms"] = self.config.slo_ms
        snap["batch_ladder"] = list(self.config.batch_ladder)
        snap["warmup_s"] = dict(self.warmup_s)
        if self.aot_cache is not None:
            snap["aot_cache"] = self.aot_cache.stats()
            with self._lock:
                ladders = dict(self._ladders)
            snap["ladder_from_cache"] = {
                str(dev): dict(ladder.from_cache) for dev, ladder in ladders.items()
            }
        snap["queue_depth"] = self.router.pending_depth() if self.router else 0
        routable = [s for s in self.slots if s.active and not s.masked]
        snap["replicas_alive"] = sum(1 for s in routable if s.alive)
        snap["replicas_masked"] = sum(1 for s in self.slots if s.masked)
        snap["restarts"] = sum(s.restarts for s in self.slots)
        snap["degraded"] = snap["replicas_masked"] > 0
        if self.store is not None:
            snap["serving_step"] = self.store.current.step
            snap["swaps"] = self.store.swaps
            snap["swap_rejects"] = self.store.swap_rejects
            snap["rollbacks"] = self.store.rollbacks
        now = time.monotonic()
        snap["fleet"] = {
            "active_device_replicas": sum(
                1 for s in self.slots if s.kind == DEVICE and s.active and not s.masked
            ),
            "cpu_spill_replicas": sum(1 for s in self.slots if s.kind == CPU_SPILL and s.active),
            "remote_replicas": sum(
                1 for s in self.slots if s.kind == REMOTE and s.active and not s.masked
            ),
            "scale_ups": self.scale_ups,
            "scale_downs": self.scale_downs,
            "router": self.router.snapshot() if self.router else {},
            "replicas": [
                {
                    "index": s.index,
                    "kind": s.kind,
                    "device": str(s.device) if s.device is not None else None,
                    "remote": s.remote_addr,
                    "active": s.active,
                    "alive": s.alive,
                    "masked": s.masked,
                    "retiring": s.retiring,
                    "restarts": s.restarts,
                    "health": round(s.health(now, self.config.replica_timeout_s), 3),
                    "depth": s.pool.depth(),
                    "outstanding": s.pool.outstanding(),
                    "requests": s.total_requests
                    + (s.stats.requests if s.stats is not None else 0),
                    "failures": s.total_failures
                    + (s.stats.failures if s.stats is not None else 0),
                }
                for s in self.slots
            ],
        }
        return snap

    # ---------------------------------------------------------------- monitor
    def _route_targets(self) -> List[RouteTarget]:
        now = time.monotonic()
        timeout = self.config.replica_timeout_s
        return [
            RouteTarget(s.index, s.pool, s.health(now, timeout), s.kind)
            for s in self.slots
            if s.active
        ]

    def _monitor(self) -> None:
        interval = self.config.monitor_interval_s
        fleet = self.config.fleet
        self._last_autoscale_t = time.monotonic()
        while not self._closing.is_set():
            now = time.monotonic()
            for slot in self.slots:
                try:
                    self._supervise_slot(slot, now)
                except Exception as err:
                    # one bad pass on one slot must not kill the fleet's only
                    # supervision thread (mirrors the hedge scan's loop)
                    self._event(
                        "monitor_error", {"replica": slot.index, "error": repr(err)}
                    )
            if now - self._last_autoscale_t >= fleet.autoscale_interval_s:
                self._last_autoscale_t = now
                try:
                    self._autoscale()
                except Exception as err:
                    self._event("monitor_error", {"replica": None, "error": repr(err)})
            self._closing.wait(interval)

    def _supervise_slot(self, slot: FleetSlot, now: float) -> None:
        if not slot.active or slot.masked:
            return
        if slot.restart_at is not None:
            if now < slot.restart_at:
                return
            prev = slot.thread
            if prev is not None and prev.is_alive():
                prev.join(0.05)
            if prev is not None and prev.is_alive():
                # the hung incarnation is still inside a dispatch on this
                # pool: a second thread on the same pool would race it, so
                # the restart waits until the old thread is confirmed dead
                # (its late complete/requeue is ownership-checked anyway)
                slot.restart_at = now + max(self.config.monitor_interval_s, 0.05)
                if slot.restart_deferrals == 0:
                    self._event("replica_restart_deferred", {"replica": slot.index})
                slot.restart_deferrals += 1
                return
            slot.restart_at = None
            slot.restart_deferrals = 0
            self._spawn(slot)
            return
        if not slot.alive:
            reason = (
                slot.thread.exit_reason if slot.thread is not None else None
            ) or "thread exited"
            self._handle_fault(slot, reason)
        elif (
            slot.stats is not None
            and now - slot.stats.heartbeat > self.config.replica_timeout_s
        ):
            age = now - slot.stats.heartbeat
            slot.thread.request_stop()
            self._event("replica_hung", {"replica": slot.index, "heartbeat_age_s": age})
            self._handle_fault(slot, f"hung (heartbeat {age:.1f}s stale)")

    def _handle_fault(self, slot: FleetSlot, reason: str) -> None:
        """Crash-requeue-at-front, fleet edition: the dead replica's work is
        re-routed to a sibling FIRST, then the restart/mask decision runs —
        recovery of the *work* never waits on recovery of the *worker*."""
        if self.router is not None:
            # a dead thread's in-flight window re-homes in full; a hung but
            # still-alive thread may yet finish its dispatch, so only its
            # idempotent requests are duplicated (hedge semantics) — the
            # rest complete when it wakes or expire by their own deadline
            alive = slot.thread is not None and slot.thread.is_alive()
            self.router.reroute(
                slot.index,
                slot.pool,
                reason,
                inflight="idempotent" if alive else "all",
            )
        slot.fold_stats()
        if slot.budget.exhausted:
            slot.masked = True
            slot.mask_reason = reason
            slot.thread = None
            slot.stats = None
            self._event(
                "replica_masked",
                {
                    "replica": slot.index,
                    "reason": reason,
                    "restarts": slot.restarts,
                    "alive": sum(1 for s in self.slots if s.alive),
                    "degraded": True,
                },
            )
            return
        charge = slot.budget.charge()
        slot.restarts += 1
        backoff = self.config.backoff_s(charge)
        slot.restart_at = time.monotonic() + backoff
        self._event(
            "replica_restart",
            {
                "replica": slot.index,
                "reason": reason,
                "restarts": slot.restarts,
                "backoff_s": backoff,
            },
        )

    def _autoscale(self) -> None:
        fleet = self.config.fleet

        def active_device() -> List[FleetSlot]:
            return [s for s in self.slots if s.kind == DEVICE and s.active and not s.masked]

        device_slots = active_device()
        # emergency floor, no patience: masking can drop the fleet below
        # min_replicas — even to zero, where no queue-depth signal could ever
        # fire again — so standby slots are re-activated immediately. The
        # hedge scan then re-places every stranded request on the recovered
        # capacity.
        if len(device_slots) < fleet.min_replicas:
            standby = [
                s for s in self.slots if s.kind == DEVICE and not s.active and not s.masked
            ]
            for slot in standby[: fleet.min_replicas - len(device_slots)]:
                slot.retiring = False
                slot.active = True
                # counted before the spawn: the new replica may answer a
                # request before this thread runs its next line
                self.scale_ups += 1
                self._spawn(slot)
                self._event(
                    "fleet_scale_up",
                    {"replica": slot.index, "reason": "below_min_replicas"},
                )
            device_slots = active_device()
        if not device_slots:
            return
        depth_per = sum(s.pool.depth() for s in device_slots) / len(device_slots)
        if depth_per >= fleet.scale_up_depth:
            self._pressure_streak += 1
            self._idle_streak = 0
        elif depth_per <= fleet.scale_down_depth:
            self._idle_streak += 1
            self._pressure_streak = 0
        else:
            self._pressure_streak = 0
            self._idle_streak = 0
        if self._pressure_streak >= fleet.scale_patience:
            self._pressure_streak = 0
            standby = [
                s
                for s in self.slots
                if s.kind == DEVICE and not s.active and not s.masked
            ]
            if standby:
                slot = standby[0]
                slot.retiring = False
                slot.active = True
                self.scale_ups += 1
                self._spawn(slot)  # compiles its ladder before it is routable
                self._event(
                    "fleet_scale_up",
                    {"replica": slot.index, "depth_per_replica": depth_per},
                )
        elif self._idle_streak >= fleet.scale_patience:
            self._idle_streak = 0
            if len(device_slots) > fleet.min_replicas:
                slot = device_slots[-1]
                slot.retiring = True  # router stops targeting it immediately
                if self.router is not None:
                    # a healthy retiring thread finishes its own in-flight
                    # dispatch (re-homing it would double-run non-idempotent
                    # requests); only its queued work moves to a sibling
                    alive = slot.thread is not None and slot.thread.is_alive()
                    self.router.reroute(
                        slot.index,
                        slot.pool,
                        "scale_down",
                        inflight="none" if alive else "all",
                    )
                if slot.thread is not None:
                    slot.thread.request_stop()
                slot.active = False
                slot.retiring = False
                self.scale_downs += 1
                self._event("fleet_scale_down", {"replica": slot.index})

    # --------------------------------------------------------------- internal
    def _spawn(self, slot: FleetSlot) -> None:
        prev = slot.thread
        if prev is not None and prev.is_alive():
            # never run two incarnations on one pool: stop the old thread and
            # give it a beat to exit; if it is still alive (hung mid-dispatch,
            # or a retired thread draining its window) arm a deferred restart
            # and let the monitor spawn once it is confirmed dead
            prev.request_stop()
            prev.join(0.05)
            if prev.is_alive():
                slot.restart_at = time.monotonic() + max(
                    self.config.monitor_interval_s, 0.05
                )
                return
        if slot.kind == REMOTE:
            from sheeprl_tpu.net.remote import RemoteReplica

            slot.stats = ReplicaStats()
            # generation rides the restart count: the agent's handshake trace
            # distinguishes a reconnect from a first attach, mirroring the
            # actor transport's generation bump
            slot.thread = RemoteReplica(
                slot.index,
                pool=slot.pool,
                addr=slot.remote_addr,
                stats=slot.stats,
                batch_counter=slot.batch_counter,
                breaker_threshold=self.config.breaker_threshold,
                timeout_s=self.config.fleet.remote_timeout_s,
                generation=slot.restarts,
                on_batch=self.stats.record_batch,
                on_shed=self.stats.record_shed,
            )
            slot.thread.start()
            return
        if slot.ladder is None:
            slot.ladder = self._ladder_for(slot.device)
        slot.stats = ReplicaStats()
        slot.thread = FleetReplica(
            slot.index,
            pool=slot.pool,
            ladder=slot.ladder,
            store=self.store,
            device=slot.device,
            stats=slot.stats,
            batch_counter=slot.batch_counter,
            breaker_threshold=self.config.breaker_threshold,
            fault_schedule=self.fault_schedule,
            on_batch=self.stats.record_batch,
            on_shed=self.stats.record_shed,
        )
        slot.thread.start()

    def _ladder_for(self, device: Any) -> CompiledLadder:
        """One AOT ladder per distinct device, compiled on first use (fleet
        start for initial replicas, activation for standbys)."""
        with self._lock:
            if device in self._ladders:
                return self._ladders[device]
        from sheeprl_tpu.obs import telemetry_deliberate_compiles

        import jax

        with telemetry_deliberate_compiles("serve_batch_ladder"):
            if device is None:
                ladder = CompiledLadder(
                    self.policy, self.config.batch_ladder, aot_cache=self.aot_cache
                )
            else:
                try:
                    with jax.default_device(device):
                        ladder = CompiledLadder(
                            self.policy,
                            self.config.batch_ladder,
                            aot_cache=self.aot_cache,
                            device=device,
                        )
                except Exception:
                    ladder = self._ladder_for(None)
        with self._lock:
            self._ladders.setdefault(device, ladder)
            return self._ladders[device]

    def _device_ring(self) -> List[Any]:
        import jax

        try:
            return list(jax.local_devices())
        except Exception:
            return []

    def _spill_devices(self) -> List[Any]:
        import jax

        try:
            cpus = list(jax.devices("cpu"))
            if cpus:
                return cpus
        except Exception:
            pass
        return self._device_ring()

    def _event(self, kind: str, info: Dict[str, Any]) -> None:
        self.stats.record_event(kind)
        if self._on_event is not None:
            try:
                self._on_event(kind, info)
            except Exception:
                pass
