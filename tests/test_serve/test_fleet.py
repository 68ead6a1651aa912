"""Replica-fleet drills (howto/serving.md, fleet section): warmup before
traffic on every replica, health-weighted routing, hedged retries rescuing a
stuck primary, router blackhole rescue, kill-mid-burst with zero dropped
admitted requests, budget exhaustion -> masked degraded N-1, CPU spill for
batch-priority traffic, elastic scale up/down — and the slow chaos ramp:
kill a replica mid-ramp on a 4-replica fleet and hold the SLO on survivors.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from sheeprl_tpu.serve.batching import Request
from sheeprl_tpu.serve.errors import Overloaded

from .conftest import expected_action, linear_obs

pytestmark = [pytest.mark.serve, pytest.mark.fleet]

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _wait_until(predicate, timeout_s=5.0, interval_s=0.005):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval_s)
    return predicate()


# ------------------------------------------------------------------ fleet ----


def test_fleet_warmup_then_correct_actions_and_snapshot(make_fleet):
    server, _, state = make_fleet()
    server.start()
    assert sorted(server.warmup_s) == [1, 2, 4]
    obs = linear_obs(state, value=0.5)
    out = server.infer(obs)
    np.testing.assert_allclose(out, expected_action(state, obs), rtol=1e-5)
    snap = server.snapshot()
    assert snap["completed"] == 1 and snap["serving_step"] == 100
    assert snap["replicas_alive"] == 2 and not snap["degraded"]
    fleet = snap["fleet"]
    assert fleet["active_device_replicas"] == 2
    assert fleet["router"]["routed"] == 1 and fleet["router"]["shed"] == 0
    assert len(fleet["replicas"]) == 2
    assert all(r["health"] > 0 for r in fleet["replicas"] if r["active"])


def test_fleet_admission_bound_sheds_typed(make_fleet):
    server, _, state = make_fleet(
        fleet={"max_pending": 1, "num_replicas": 1, "max_replicas": 1},
        fault_injection={
            "enabled": True,
            "faults": [
                {"kind": "slow_inference", "replica": 0, "at_batch": 0, "duration_s": 0.2, "for_batches": 50}
            ],
        },
    )
    server.start()
    reqs = []
    shed = 0
    for _ in range(6):
        try:
            reqs.append(server.submit(linear_obs(state), deadline_s=5.0))
        except Overloaded:
            shed += 1
    assert shed >= 1  # past the fleet-wide pending bound: typed, immediate
    for req in reqs:
        server.wait(req)  # admitted requests still complete
    assert server.router.shed == shed


def test_kill_replica_mid_burst_zero_dropped(make_fleet, tmp_path):
    """The fast chaos drill: kill a replica while a burst is in flight —
    every admitted request completes (re-route-at-front), the fleet restarts
    the dead replica, and the survivors keep serving. Runs under the trace
    plane: the merged timeline must show one complete causal chain per
    request, the kill's stranded batch attributed re-routed, and the
    queue-wait/assembly/compute decomposition via ``tools.report --trace``."""
    from sheeprl_tpu.obs.trace import configure_trace, shutdown_trace

    trace_path = str(tmp_path / "trace.serve.jsonl")
    configure_trace("serve", trace_path)
    try:
        server, _, state = make_fleet(
            fleet={"num_replicas": 2, "max_replicas": 2, "max_pending": 10_000},
            # pin a batch in flight on replica 0 so the kill strands it —
            # the re-route-at-front path fires deterministically
            fault_injection={
                "enabled": True,
                "faults": [
                    {"kind": "slow_inference", "replica": 0, "at_batch": 0, "duration_s": 0.25, "for_batches": 50}
                ],
            },
        )
        server.start()
        results, errors = [], []

        def client(n):
            for i in range(n):
                try:
                    obs = linear_obs(state, value=float(i % 7))
                    out = server.infer(obs, deadline_s=10.0)
                    np.testing.assert_allclose(out, expected_action(state, obs), rtol=1e-5)
                    results.append(out)
                except Exception as err:  # noqa: BLE001 — drill collects everything
                    errors.append(err)

        threads = [threading.Thread(target=client, args=(30,)) for _ in range(4)]
        for t in threads:
            t.start()
        # kill only once replica 0 actually holds a batch — the slow_inference
        # fault pins EVERY burst batch for 0.25s, so whichever batch we observe
        # in flight, the kill lands inside its pin window and strands it; a
        # narrower window races the observed batch completing before the kill
        assert _wait_until(lambda: len(server.slots[0].pool._inflight) > 0)
        assert server.kill_replica(0)
        for t in threads:
            t.join(20.0)
        assert not errors and len(results) == 120
        assert _wait_until(lambda: server.slots[0].alive)  # budgeted restart
        snap = server.snapshot()
        assert snap["failed"] == 0 and snap["restarts"] >= 1
        # the stranded batch was re-homed: by the monitor's re-route-at-front,
        # or by a hedge twin when the adaptive hedge scan (threshold learned
        # down to ~ms on a warm ladder) beats the monitor pass to the rescue
        router_snap = snap["fleet"]["router"]
        assert router_snap["rerouted_requests"] + router_snap["hedged"] >= 1

        # request_done is emitted by the delivering replica thread right
        # after the future resolves — give the last few a beat to land
        def done_count():
            with open(trace_path) as f:
                return sum(1 for line in f if '"request_done"' in line)

        assert _wait_until(lambda: done_count() >= 120)
    finally:
        shutdown_trace()

    # -- merged end-to-end trace: the drill's acceptance evidence -----------
    from tools import trace as trace_tool

    merged = trace_tool.merge([trace_path])
    summary = trace_tool.summarize(merged)
    req = summary["requests"]
    assert req["traces"] == 120  # every admitted request minted one chain
    assert req["terminals"] == {"request_done": 120}  # zero dangling/expired
    # the kill's victims carry request_reroute, or request_hedge when the
    # adaptive hedge scan won the rescue race (same either/or as the snapshot)
    assert req["rerouted"] + req["hedged"] >= 1
    assert "hedge_winner_dupes" not in req  # first-completion-wins held
    for tid, evs in merged["traces"].items():
        kinds = trace_tool.trace_kinds(evs)
        assert kinds[0] == "request_admit", (tid, kinds)
        assert kinds.count("request_done") == 1, (tid, kinds)
    # the fault victim's chain: re-homed, then done exactly once
    victims = [
        evs for evs in merged["traces"].values()
        if any(e["kind"] in ("request_reroute", "request_hedge") for e in evs)
    ]
    assert victims
    for evs in victims:
        done = [e for e in evs if e["kind"] == "request_done"][0]
        rescued = [e["kind"] for e in evs]
        if "request_reroute" in rescued:
            assert done["rerouted"] is True
        else:
            assert done["hedged"] is True
    # the kill itself lands on the untraced (process-scoped) timeline
    assert any(e["kind"] == "replica_killed" for e in merged["untraced"])

    # tools.report --trace prints the request latency decomposition
    proc = subprocess.run(
        [sys.executable, "-m", "tools.report", "--trace", trace_path],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    printed = json.loads(proc.stdout)
    for key in ("total_ms", "queue_wait_ms", "assembly_ms", "compute_ms"):
        assert "p50" in printed["requests"][key] and "p95" in printed["requests"][key]


def test_budget_exhaustion_masks_and_fleet_serves_degraded(make_fleet):
    server, _, state = make_fleet(
        max_restarts=1,
        restart_refund_s=None,
        fleet={"num_replicas": 2, "max_replicas": 2},
    )
    server.start()
    for _ in range(2):  # budget of 1: second death masks the slot
        assert _wait_until(lambda: server.slots[0].alive)
        server.kill_replica(0)
        assert _wait_until(lambda: not server.slots[0].alive, timeout_s=2.0)
        _wait_until(lambda: server.slots[0].masked or server.slots[0].restart_at is not None or server.slots[0].alive)
    assert _wait_until(lambda: server.slots[0].masked)
    obs = linear_obs(state)
    np.testing.assert_allclose(server.infer(obs), expected_action(state, obs), rtol=1e-5)
    snap = server.snapshot()
    assert snap["degraded"] and snap["replicas_masked"] == 1
    assert snap["fleet"]["active_device_replicas"] == 1  # N-1, still serving


def test_emergency_floor_reactivates_standby_after_last_replica_masked(make_fleet):
    """Losing the LAST active replica (masked, budget spent) must not strand
    the fleet at zero capacity: the autoscaler's emergency floor activates a
    standby slot immediately — no queue-depth signal required, because an
    empty fleet can never generate one — and the hedge scan re-places every
    stranded request on the recovered capacity."""
    server, _, state = make_fleet(
        max_restarts=0,
        restart_refund_s=None,
        fleet={"num_replicas": 1, "min_replicas": 1, "max_replicas": 2, "max_pending": 10_000},
    )
    server.start()
    obs = linear_obs(state)
    server.infer(obs)
    server.kill_replica(0)
    assert _wait_until(lambda: server.slots[0].masked, timeout_s=5.0)
    # a request submitted into the dead window is parked unplaced and
    # rescued once the standby comes up
    req = server.submit(obs, deadline_s=10.0)
    np.testing.assert_allclose(server.wait(req), expected_action(state, obs), rtol=1e-5)
    assert server.slots[1].alive and server.slots[1].active
    snap = server.snapshot()
    assert snap["degraded"] and snap["fleet"]["active_device_replicas"] == 1
    assert snap["fleet"]["scale_ups"] >= 1


def test_cpu_spill_absorbs_batch_priority(make_fleet):
    server, _, state = make_fleet(
        fleet={
            "num_replicas": 1,
            "max_replicas": 1,
            "cpu_spill_replicas": 1,
            "spill_depth": 0,  # device "saturated" immediately: spill opens
        }
    )
    server.start()
    spill_index = server.config.fleet.max_replicas  # spill slots follow device slots
    obs = linear_obs(state)
    req = server.submit(obs, deadline_s=5.0, priority="batch")
    assert req.placements == [spill_index]
    np.testing.assert_allclose(server.wait(req), expected_action(state, obs), rtol=1e-5)
    assert server.router.spilled == 1
    # interactive traffic never lands on the spill tier while a device
    # replica is routable
    req = server.submit(obs, deadline_s=5.0)
    assert req.placements and req.placements[0] != spill_index
    server.wait(req)


def test_autoscale_up_under_pressure_then_down_when_idle(make_fleet):
    server, _, state = make_fleet(
        fleet={
            "num_replicas": 1,
            "min_replicas": 1,
            "max_replicas": 2,
            "max_pending": 10_000,
            "scale_up_depth": 2.0,
            "scale_down_depth": 0.5,
            "scale_patience": 1,
            "autoscale_interval_s": 0.02,
        },
        fault_injection={
            "enabled": True,
            "faults": [
                {"kind": "slow_inference", "replica": 0, "at_batch": 0, "duration_s": 0.1, "for_batches": 30}
            ],
        },
    )
    server.start()
    assert server.snapshot()["fleet"]["active_device_replicas"] == 1
    reqs = [server.submit(linear_obs(state, value=float(i)), deadline_s=30.0) for i in range(24)]
    assert _wait_until(lambda: server.scale_ups >= 1, timeout_s=5.0)
    for req in reqs:
        # the scaled-up replica (no fault) plus hedges past the latency
        # quantile drain the backlog
        server.wait(req)
    assert _wait_until(lambda: server.scale_downs >= 1, timeout_s=5.0)
    snap = server.snapshot()
    assert snap["fleet"]["scale_ups"] >= 1 and snap["fleet"]["scale_downs"] >= 1
    assert snap["fleet"]["active_device_replicas"] == 1  # back at the floor
    assert snap["failed"] == 0


# ----------------------------------------------------------------- router ----


def _pools(n, capacity=4):
    from sheeprl_tpu.serve.slots import SlotPool

    return [SlotPool(capacity=capacity, backlog_bound=64) for _ in range(n)]


def _targets(pools, healths=None, kinds=None):
    from sheeprl_tpu.serve.router import RouteTarget

    healths = healths or [1.0] * len(pools)
    kinds = kinds or ["device"] * len(pools)
    return lambda: [
        RouteTarget(i, p, h, k) for i, (p, h, k) in enumerate(zip(pools, healths, kinds))
    ]


def test_router_health_weighted_least_loaded():
    from sheeprl_tpu.serve.router import Router

    pools = _pools(3)
    now = time.monotonic()
    # pool 0 holds 2 requests, sickly pool 1 holds 1, pool 2 is empty
    for _ in range(2):
        pools[0].offer(Request(None, now, now + 60.0))
    pools[1].offer(Request(None, now, now + 60.0))
    healths = [1.0, 0.1, 1.0]
    router = Router(targets=_targets(pools, healths), max_pending=100, slo_s=0.1)
    req = router.submit(None, 60.0)
    assert req.placements == [2]  # least loaded wins outright
    # saturate pool 2: now the sick-but-emptier pool 1 (1/0.1 = 10) loses to
    # the healthy-but-busier pool 0 (2/1.0 = 2) — traffic tapers off a
    # struggling replica before the supervisor ever declares it dead
    for _ in range(3):
        pools[2].offer(Request(None, now, now + 60.0))
    req2 = router.submit(None, 60.0)
    assert req2.placements == [0]
    router.close()


def test_hedged_retry_first_completion_wins():
    """A request stuck on a silent primary is duplicated to a sibling after
    the hedge threshold; the twin's completion wins the Future and the
    loser's copy is dropped at its pool's next dispatch assembly."""
    from sheeprl_tpu.serve.router import Router
    from sheeprl_tpu.serve.slots import safe_complete

    pools = _pools(2)
    router = Router(
        targets=_targets(pools),
        max_pending=100,
        slo_s=0.02,  # few samples -> hedge threshold = max(floor, slo)
        hedge_scan_s=0.002,
    ).start()
    req = router.submit(np.float32(7.0), 60.0)
    assert req.placements == [0]
    assert _wait_until(lambda: req.hedges == 1, timeout_s=5.0)
    assert req.placements == [0, 1]
    # the sibling serves the hedge twin
    batch = pools[1].take_batch(1.0)
    assert [r.rid for r in batch] == [req.rid]
    assert safe_complete(batch[0], "served-by-1")
    pools[1].complete_batch(batch)
    assert req.future.result(timeout=1.0) == "served-by-1"
    # the loser's copy is skipped (future already done), not served dead
    assert pools[0].take_batch(0.05) == []
    assert _wait_until(lambda: router.hedged_won == 1, timeout_s=2.0)
    assert router.hedged == 1
    router.close()


def test_router_blackhole_rescued_by_scan():
    from sheeprl_tpu.serve.fault_injection import parse_serve_faults, ServeFaultSchedule
    from sheeprl_tpu.serve.router import Router

    pools = _pools(2)
    schedule = ServeFaultSchedule(
        parse_serve_faults([
            {"kind": "router_blackhole", "at_request": 0, "duration_s": 0.05}
        ])
    )
    router = Router(
        targets=_targets(pools),
        max_pending=100,
        slo_s=60.0,  # hedging out of the picture: only the rescue path moves it
        hedge_scan_s=0.002,
        fault_schedule=schedule,
    ).start()
    req = router.submit(None, 60.0)
    assert req.placements == []  # swallowed at the front door
    assert router.blackholed == 1
    assert _wait_until(lambda: req.placements != [], timeout_s=5.0)  # rescued
    assert pools[req.placements[0]].outstanding() == 1
    router.close()


def test_reroute_at_front_lands_on_healthiest_sibling():
    from sheeprl_tpu.serve.router import Router

    pools = _pools(3, capacity=2)
    now = time.monotonic()
    pools[2].offer(Request(None, now, now + 60.0))  # sibling 2 is busier
    router = Router(targets=_targets(pools), max_pending=100, slo_s=60.0)
    victims = [router.submit(None, 60.0) for _ in range(2)]
    assert all(v.placements == [1] or v.placements == [0] for v in victims)
    dead = victims[0].placements[0]
    moved = router.reroute(dead, pools[dead], "drill")
    survivors = [v for v in victims if v.placements[0] == dead]
    assert moved == len(survivors)
    for v in survivors:
        assert v.rerouted == 1 and v.placements[-1] not in (dead, 2)
    assert router.rerouted_requests == moved
    router.close()


def test_stale_incarnation_cannot_clobber_live_inflight_window():
    """A hung incarnation that wakes AFTER its window was drained and a new
    incarnation started must release nothing: in-flight tracking is
    ownership-checked per dispatch, so the live window survives a stale
    complete/requeue and stays recoverable by a later drain."""
    from sheeprl_tpu.serve.slots import SlotPool

    pool = SlotPool(capacity=2, backlog_bound=8)
    now = time.monotonic()
    a, b = Request(None, now, now + 60.0), Request(None, now, now + 60.0)
    pool.offer(a), pool.offer(b)
    stale = pool.take_batch(0.0)  # the incarnation that will hang here
    assert [r.rid for r in stale] == [a.rid, b.rid]
    drained = pool.drain()  # declared hung/dead: the fleet re-homes its window
    assert [r.rid for r in drained] == [a.rid, b.rid]
    c = Request(None, now, now + 60.0)
    pool.offer(c)
    live = pool.take_batch(0.0)  # the restarted incarnation dispatches
    assert [r.rid for r in live] == [c.rid]
    pool.complete_batch(stale)  # stale thread wakes late: releases nothing
    assert pool.outstanding() == 1
    pool.requeue_failed(stale)  # ...and requeues nothing it no longer owns
    assert pool.depth() == 0 and pool.outstanding() == 1
    assert [r.rid for r in pool.drain()] == [c.rid]  # live window recoverable


def test_drain_scopes_inflight_by_executor_liveness():
    """Re-homing a live thread's in-flight window would run non-idempotent
    requests twice, so drain scopes it: a healthy retiring replica keeps the
    whole window, a hung-but-alive one gives up only idempotent requests
    (duplication there is hedging), a confirmed-dead one gives up all."""
    from sheeprl_tpu.serve.router import RoutedRequest
    from sheeprl_tpu.serve.slots import SlotPool

    pool = SlotPool(capacity=4, backlog_bound=8)
    now = time.monotonic()
    idem = RoutedRequest(None, now, now + 60.0, idempotent=True)
    nonidem = RoutedRequest(None, now, now + 60.0, idempotent=False)
    pool.offer(idem), pool.offer(nonidem)
    assert len(pool.take_batch(0.0)) == 2
    queued = RoutedRequest(None, now, now + 60.0, idempotent=False)
    pool.offer(queued)
    assert [r.rid for r in pool.drain(inflight="none")] == [queued.rid]
    assert pool.outstanding() == 2  # the whole window stays with its executor
    assert [r.rid for r in pool.drain(inflight="idempotent")] == [idem.rid]
    assert pool.outstanding() == 1  # non-idempotent stays with its executor
    assert [r.rid for r in pool.drain()] == [nonidem.rid]
    assert pool.outstanding() == 0


def test_router_expires_unplaced_requests_at_deadline():
    """A request admitted but never placed (blackhole, full fleet) is in NO
    pool, so no pool can expire it — the scan's backstop must fail it at its
    own deadline and drop the in-flight tracking, or it leaks forever and a
    raw-future consumer hangs."""
    from sheeprl_tpu.serve.errors import DeadlineExceeded
    from sheeprl_tpu.serve.fault_injection import ServeFaultSchedule, parse_serve_faults
    from sheeprl_tpu.serve.router import Router

    pools = _pools(2)
    schedule = ServeFaultSchedule(
        parse_serve_faults([
            {"kind": "router_blackhole", "at_request": 0, "duration_s": 30.0}
        ])
    )
    router = Router(
        targets=_targets(pools),
        max_pending=100,
        slo_s=60.0,  # hedging out of the picture: only the backstop can act
        hedge_scan_s=0.002,
        fault_schedule=schedule,
    ).start()
    req = router.submit(None, 0.05)
    assert req.placements == []
    with pytest.raises(DeadlineExceeded):
        req.future.result(timeout=5.0)
    assert _wait_until(lambda: router.inflight_count() == 0, timeout_s=5.0)
    assert router.expired == 1
    router.close()


def test_admission_bound_counts_unplaced_inflight():
    """Blackholed requests occupy no pool, so pool depth alone would let the
    router admit past ``max_pending`` for the blackhole's whole duration —
    the admission signal must include admitted-but-unplaced requests."""
    from sheeprl_tpu.serve.fault_injection import ServeFaultSchedule, parse_serve_faults
    from sheeprl_tpu.serve.router import Router

    pools = _pools(2)
    schedule = ServeFaultSchedule(
        parse_serve_faults([
            {"kind": "router_blackhole", "at_request": 0, "duration_s": 30.0}
        ])
    )
    router = Router(
        targets=_targets(pools),
        max_pending=2,
        slo_s=60.0,
        fault_schedule=schedule,
    ).start()
    for _ in range(2):
        assert router.submit(None, 60.0).placements == []
    assert router.unplaced_inflight() == 2
    with pytest.raises(Overloaded):
        router.submit(None, 60.0)
    router.close()


# ------------------------------------------------------------- chaos ramp ----


@pytest.mark.slow
def test_chaos_ramp_kill_mid_ramp_holds_slo_on_survivors(make_fleet):
    """The headline drill: a 4-replica fleet under a stepped saturation
    ramp; one replica is killed as the second step begins. Zero admitted
    requests are dropped or expired, the ramp still finds a knee, and the
    surviving N-1 fleet holds the SLO at the knee."""
    from sheeprl_tpu.serve.config import LoadConfig
    from sheeprl_tpu.serve.loadgen import run_ramp

    server, _, state = make_fleet(
        slo_ms=500.0,
        max_restarts=0,  # the dead replica stays dead: survivors own the SLO
        restart_refund_s=None,
        fleet={
            # min == num == max: the elasticity is pinned out of the drill —
            # this one measures crash resilience on a fixed fleet
            "num_replicas": 4,
            "min_replicas": 4,
            "max_replicas": 4,
            "max_pending": 10_000,
        },
    )
    server.start()
    assert server.snapshot()["replicas_alive"] == 4
    killed = []

    def on_step(step, rate):
        if step == 1:
            killed.append(server.kill_replica(0))

    report = run_ramp(
        server,
        LoadConfig(enabled=True, duration_s=1.0, concurrency=8, max_retries=5, seed=0),
        rates_hz=[60.0, 100.0, 160.0],
        step_duration_s=0.6,
        on_step=on_step,
    )
    assert killed == [True]
    total_expired = sum(s["expired"] for s in report["steps"])
    total_errors = sum(s["errors"] for s in report["steps"])
    assert total_expired == 0 and total_errors == 0  # zero dropped admitted
    assert report["knee_rate_hz"] is not None and report["max_good_qps"] > 0
    snap = server.snapshot()
    assert snap["replicas_alive"] == 3  # survivors, no restart budget
    assert snap["shed_expired"] == 0 and snap["failed"] == 0
    assert snap["p95_ms"] is not None and snap["p95_ms"] <= server.config.slo_ms
