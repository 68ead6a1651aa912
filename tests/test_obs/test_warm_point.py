"""The warm point every off-policy/Dreamer loop shares
(obs/recompile.py ``CompileWatchdog.mark_warm_after_warmup``): from that
update on, a lowering counts as a recompile.
"""

import jax
import jax.numpy as jnp
import pytest

from sheeprl_tpu.obs import configure_telemetry, shutdown_telemetry, telemetry_mark_warm_after_warmup
from sheeprl_tpu.obs.recompile import CompileWatchdog, RecompileWarning

W = CompileWatchdog.WARMUP_UPDATES


@pytest.fixture()
def dog():
    return CompileWatchdog(lambda kind, **fields: None)


def test_fresh_run_opens_at_shared_warm_point(dog):
    for update in range(0, 10 + W + 1):
        dog.mark_warm_after_warmup(update, 10)
        assert dog.warm == (update >= 10 + W), update


def test_resumed_run_waits_its_own_warmup(dog):
    """A run resuming at update 5000 (long past learning_starts + warmup)
    still compiles its gradient path on its FIRST update — the warm point
    must wait WARMUP_UPDATES from the first observed update, not arrive
    immediately (which would count every one of those compiles as a
    recompile)."""
    dog.mark_warm_after_warmup(5000, 0)
    assert not dog.warm
    dog.mark_warm_after_warmup(5000 + W - 1, 0)
    assert not dog.warm
    dog.mark_warm_after_warmup(5000 + W, 0)
    assert dog.warm


def test_warm_mark_reaches_the_watchdog_of_a_running_loop(tmp_path):
    """With telemetry on, a loop that lowers a fresh program every update
    counts no recompile before the update the rule names, and one for every
    update from there on."""
    learning_starts, last = 3, 3 + W + 2
    tel = configure_telemetry({"metric": {"telemetry": {"enabled": True, "poll_interval": 0.0}}}, log_dir=str(tmp_path))
    try:
        x = jnp.ones((3,))
        for update in range(1, last + 1):
            telemetry_mark_warm_after_warmup(update, learning_starts)
            warm = update >= learning_starts + W
            before = tel.watchdog.recompiles
            if warm:
                with pytest.warns(RecompileWarning):
                    # a FRESH lambda defeats the jit cache: one lowering an update
                    jax.block_until_ready(jax.jit(lambda v: v * 3 + 1)(x))
            else:
                jax.block_until_ready(jax.jit(lambda v: v * 3 + 1)(x))
            assert tel.watchdog.recompiles - before == int(warm), update
        assert tel.watchdog.recompiles == last - (learning_starts + W) + 1
    finally:
        shutdown_telemetry()
