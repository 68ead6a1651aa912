"""Serving-tier AOT executable cache drills (ISSUE 17): a second server boot
deserializes the whole batch ladder instead of compiling it, hot swap
re-populates missing cache entries before the gauntlet flips versions, a
fleet reboot loads every per-device ladder from cache, and the slow
autoscale-under-spike drill proves a scale-up replica becomes routable from
a cached executable while the fleet holds the SLO with zero dropped admitted
requests."""

import glob
import os
import time

import numpy as np
import pytest

from .conftest import commit_linear, expected_action, linear_obs

# These drills only run hermetically: tests/test_serve/test_aotcache_hermetic.py
# spawns a fresh interpreter (persistent trace cache OFF from the first compile)
# and re-runs this file with the marker env var set. In a shared suite process
# they are structurally unsound: any executable DESERIALIZED from the warm
# cross-run trace cache — even a module-level ``PRNGKey(0)`` constant compiled
# during collection — registers its kernel symbols process-wide, and later
# fresh compiles that reuse a same-named kernel (the fusion names are generic,
# e.g. ``dot_add_fusion``) serialize WITHOUT embedding it and can never be
# loaded back ("Symbols not found"). AotCache's store-time verification then
# rightly refuses every store. Nothing can undo a deserialize that already
# happened, and the cache's enabled/dir state latches process-wide at the
# first compile — a fresh child process is the only clean room.
pytestmark = [
    pytest.mark.serve,
    pytest.mark.skipif(
        not os.environ.get("SHEEPRL_TPU_AOT_HERMETIC"),
        reason="AOT round-trip drills run in a hermetic child via test_aotcache_hermetic.py",
    ),
]


@pytest.fixture(autouse=True)
def _real_compiles():
    """Belt-and-suspenders for direct runs of this file: disable the XLA
    persistent trace cache (tests/conftest.py) so a trace-cache HIT cannot
    hand these drills an executable whose serialized payload is unloadable
    (CPU backend, "Symbols not found"). The hermetic child already strips
    the cache via JAX_ENABLE_COMPILATION_CACHE=false; see the module docstring
    for why a shared warm-cache process can still poison same-named kernels
    in ways this fixture cannot undo."""
    import jax

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", old)


def _wait_until(predicate, timeout_s=5.0, interval_s=0.005):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval_s)
    return predicate()


def _entries(cache_dir):
    return sorted(glob.glob(os.path.join(str(cache_dir), "*.aotx")))


def test_second_server_boot_deserializes_ladder(make_server, tmp_path):
    cache_dir = tmp_path / "aotcache"
    cold, _, state = make_server(aot_cache_dir=str(cache_dir))
    cold.start()
    obs = linear_obs(state)
    np.testing.assert_allclose(cold.infer(obs), expected_action(state, obs), rtol=1e-5)
    snap = cold.snapshot()
    assert snap["ladder_from_cache"] == {1: False, 2: False, 4: False}
    assert snap["aot_cache"]["misses"] == 3 and snap["aot_cache"]["hits"] == 0
    cold.close()  # drains the async writer: all three rungs committed
    assert len(_entries(cache_dir)) == 3

    warm, _, state = make_server(aot_cache_dir=str(cache_dir))
    warm.start()
    snap = warm.snapshot()
    assert snap["ladder_from_cache"] == {1: True, 2: True, 4: True}
    assert snap["aot_cache"] == {"hits": 3, "misses": 0, "stores": 0, "errors": 0}
    np.testing.assert_allclose(warm.infer(obs), expected_action(state, obs), rtol=1e-5)


def test_hot_swap_prewarms_missing_entries(make_server, tmp_path):
    """Entries GC'd between boot and swap (cleaned cache volume): the swap
    gauntlet re-populates them synchronously before the flip, so the NEXT
    boot still cold-starts from cache."""
    cache_dir = tmp_path / "aotcache"
    server, ckpt_dir, state = make_server(aot_cache_dir=str(cache_dir))
    server.start()
    server.aot_cache.flush()
    assert len(_entries(cache_dir)) == 3
    for path in _entries(cache_dir):
        os.remove(path)

    path2, state2 = commit_linear(ckpt_dir, 200, seed=1)
    version = server.request_swap(path2)
    assert version.step == 200
    # prewarm ran inside the swap: the structurally-identical entries are back
    assert len(_entries(cache_dir)) == 3
    obs = linear_obs(state2)
    np.testing.assert_allclose(server.infer(obs), expected_action(state2, obs), rtol=1e-5)


def test_fleet_reboot_loads_every_ladder_from_cache(make_fleet, tmp_path):
    cache_dir = tmp_path / "aotcache"
    cold, _, state = make_fleet(aot_cache_dir=str(cache_dir))
    cold.start()
    obs = linear_obs(state)
    np.testing.assert_allclose(cold.wait(cold.submit(obs, deadline_s=10.0)), expected_action(state, obs), rtol=1e-5)
    cold.close()
    assert _entries(cache_dir)  # base + per-device ladders committed

    warm, _, state = make_fleet(aot_cache_dir=str(cache_dir))
    warm.start()
    snap = warm.snapshot()
    assert snap["aot_cache"]["misses"] == 0 and snap["aot_cache"]["hits"] > 0
    assert snap["ladder_from_cache"] and all(
        rungs and all(rungs.values()) for rungs in snap["ladder_from_cache"].values()
    )
    np.testing.assert_allclose(warm.wait(warm.submit(obs, deadline_s=10.0)), expected_action(state, obs), rtol=1e-5)


@pytest.mark.slow
@pytest.mark.fleet
def test_autoscale_spike_scales_up_from_cache_holding_slo(make_fleet, tmp_path):
    """The ISSUE 17 drill: a load spike forces a scale-up and the new
    replica's ladder comes from the cache (populated by an earlier
    full-fleet boot), p95 stays within the SLO and zero admitted requests
    are dropped."""
    cache_dir = tmp_path / "aotcache"
    # boot the full fleet once to populate every device's entries (the
    # steady-state a long-running service reaches before any preemption)
    seed_fleet, _, state = make_fleet(
        aot_cache_dir=str(cache_dir),
        fleet={"num_replicas": 2, "min_replicas": 2, "max_replicas": 2},
    )
    seed_fleet.start()
    seed_fleet.close()
    assert _entries(cache_dir)

    server, _, state = make_fleet(
        slo_ms=1000.0,
        aot_cache_dir=str(cache_dir),
        fleet={
            "num_replicas": 1,
            "min_replicas": 1,
            "max_replicas": 2,
            "max_pending": 10_000,
            "scale_up_depth": 2.0,
            "scale_down_depth": 0.0,  # never scale back down mid-drill
            "scale_patience": 1,
            "autoscale_interval_s": 0.02,
        },
        fault_injection={
            "enabled": True,
            "faults": [
                # the spike: the only active replica turns slow, queue depth
                # crosses scale_up_depth, the autoscaler activates a standby
                {"kind": "slow_inference", "replica": 0, "at_batch": 0, "duration_s": 0.08, "for_batches": 30}
            ],
        },
    )
    server.start()
    assert server.snapshot()["fleet"]["active_device_replicas"] == 1
    # stepped ramp: three widening waves of admitted traffic
    reqs = []
    for wave in (8, 16, 24):
        reqs += [server.submit(linear_obs(state, value=float(i)), deadline_s=30.0) for i in range(wave)]
        time.sleep(0.05)
    assert _wait_until(lambda: server.scale_ups >= 1, timeout_s=10.0)
    for req in reqs:
        server.wait(req)

    snap = server.snapshot()
    assert snap["fleet"]["scale_ups"] >= 1
    # the scaled-up replica (and everything else) deserialized its ladder:
    # the spike never paid a compile
    assert snap["aot_cache"]["misses"] == 0 and snap["aot_cache"]["hits"] > 0
    assert snap["ladder_from_cache"] and all(
        rungs and all(rungs.values()) for rungs in snap["ladder_from_cache"].values()
    )
    # SLO held, zero dropped admitted requests
    assert snap["failed"] == 0 and snap["shed_expired"] == 0
    assert snap["p95_ms"] is not None and snap["p95_ms"] <= server.config.slo_ms
