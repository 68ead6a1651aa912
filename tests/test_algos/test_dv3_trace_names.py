"""The names a device trace files the Dreamer-V3 path's work under
(howto/telemetry.md, "Program and scope names"): every jitted program the loop
dispatches is lowered at tiny widths and its own name, and each
``jax.named_scope`` inside it, is found in the lowered text with debug info.
Nothing runs and nothing compiles here."""

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import pytest

#: program -> the scopes its ops carry
NAMES = {
    "dv3_train_step": (
        "dv3/wm/encode",
        "dv3/wm/rssm_scan",
        "dv3/wm/decode",
        "dv3/wm/optimizer",
        "dv3/behaviour/imagine",
        "dv3/behaviour/actor_loss",
        "dv3/behaviour/optimizer",
        "dv3/critic/loss",
        "dv3/critic/optimizer",
    ),
    "dv3_player_step": ("dv3/player/encode", "dv3/player/rssm", "dv3/player/actor"),
    "dv3_player_reset": (),
    "dv3_target_ema": (),
    "ring_write": (),
    "ring_amend": (),
    "ring_gather_sequences": (),
}
CASES = [(program, None) for program in NAMES] + [(p, scope) for p, scopes in NAMES.items() for scope in scopes]

TINY = [
    "exp=dreamer_v3",
    "env=dummy",
    "env.id=dummy_continuous",
    "env.num_envs=2",
    "env.screen_size=16",
    "algo.per_rank_batch_size=2",
    "algo.per_rank_sequence_length=4",
    "algo.horizon=3",
    "algo.dense_units=8",
    "algo.mlp_layers=1",
    "algo.world_model.encoder.cnn_channels_multiplier=2",
    "algo.world_model.recurrent_model.recurrent_state_size=8",
    "algo.world_model.representation_model.hidden_size=8",
    "algo.world_model.transition_model.hidden_size=8",
    "algo.world_model.discrete_size=4",
    "algo.world_model.stochastic_size=4",
    "algo.cnn_keys.encoder=[rgb]",
    "algo.mlp_keys.encoder=[]",
    "algo.cnn_keys.decoder=[rgb]",
    "algo.mlp_keys.decoder=[]",
    "fabric.accelerator=cpu",
    "fabric.devices=1",
]


@pytest.fixture(scope="module")
def lowered():
    """``program name -> lowered text with debug info`` for the seven programs."""
    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as program
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.data.device_buffer import DeviceReplayBuffer
    from sheeprl_tpu.ops.math import init_moments
    from sheeprl_tpu.ops.optim import build_tx
    from sheeprl_tpu.parallel.fabric import Fabric
    from sheeprl_tpu.utils.utils import dotdict

    cfg = dotdict(compose("config", TINY))
    fabric = Fabric(devices=1, precision="fp32", accelerator="cpu")
    envs, act_dim, size = 2, 3, 16
    obs_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (size, size, 3), np.uint8)})
    wm, wm_p, actor, actor_p, critic, critic_p, target_p, player = program.build_agent(fabric, (act_dim,), True, cfg, obs_space)
    txs = [build_tx(cfg.algo[n].optimizer, cfg.algo[n].clip_gradients) for n in ("world_model", "actor", "critic")]
    opts = [tx.init(p) for tx, p in zip(txs, (wm_p, actor_p, critic_p))]
    train = program.make_train_fn(fabric, wm, actor, critic, *txs, cfg, True, (act_dim,))
    T, B = 4, 2
    batch = {
        "rgb": jnp.zeros((T, B, size, size, 3), jnp.uint8),
        "actions": jnp.zeros((T, B, act_dim)),
        **{k: jnp.zeros((T, B, 1)) for k in ("rewards", "terminated", "truncated", "is_first")},
    }
    key = jax.random.PRNGKey(0)

    def text(jitted, *args, **kwargs):
        return jitted.lower(*args, **kwargs).as_text(debug_info=True)

    out = {"dv3_train_step": text(train, wm_p, actor_p, critic_p, target_p, *opts, init_moments(), batch, key)}
    player.init_states()
    obs = {"rgb": jnp.zeros((envs, size, size, 3), jnp.uint8)}
    out["dv3_player_step"] = text(player._step, player.wm_params, player.actor_params, obs, player.h, player.z, player.actions, key, greedy=False)
    mask = np.zeros((envs, 1), np.float32)
    out["dv3_player_reset"] = text(player._masked_reset, player.wm_params, player.h, player.z, player.actions, mask)
    out["dv3_target_ema"] = text(program.dv3_target_ema, critic_p, target_p, 0.02)

    rb = DeviceReplayBuffer(8, n_envs=envs, obs_keys=("rgb",))
    step = {
        "rgb": np.zeros((1, envs, size, size, 3), np.uint8),
        "actions": np.zeros((1, envs, act_dim), np.float32),
        **{k: np.zeros((1, envs, 1), np.float32) for k in ("rewards", "terminated", "truncated", "is_first")},
    }
    rb.add(step)  # allocates the ring and its staging arrays (the stored form of one step)
    out["ring_write"] = text(rb._write, rb._bufs, rb._stage_pixels, rb._stage_smalls, rb._stage_pos)
    out["ring_amend"] = text(rb._amend, rb._bufs, jnp.int32(0), jnp.int32(0), jnp.float32(0), jnp.float32(1), jnp.float32(0))
    out["ring_gather_sequences"] = text(rb._gather, rb._bufs, jnp.zeros((B,), jnp.int32), jnp.zeros((B, T), jnp.int32))
    return out


@pytest.mark.parametrize("program,scope", CASES, ids=[scope or program for program, scope in CASES])
def test_the_lowered_program_carries_the_name(lowered, program, scope):
    text = lowered[program]
    assert f"@jit_{program} " in text, text[:200]
    if scope is not None:
        # the op_name path of an op: jit(<program>)/.../<scope>/...
        assert f"/{scope}/" in text or f"{scope})" in text, f"no op of {program} is under {scope}"


def test_a_scope_marks_its_backward_ops_too(lowered):
    """The world model's scopes sit inside ``value_and_grad``: their forward
    ops carry ``jvp(<scope>)`` and their backward ops ``transpose(jvp(<scope>))``."""
    text = lowered["dv3_train_step"]
    assert "transpose(jvp(dv3/wm/rssm_scan))" in text and "jvp(dv3/wm/rssm_scan)" in text


def test_the_rssm_scans_own_backward_stays_under_its_scope(lowered):
    """``rssm_scan``'s backward is a ``vjp`` of its own inside a ``custom_vjp``
    (``ops/hoisted_scan.py``): its loop (``transpose(jvp())``) and the kernels'
    contractions after the loop (``ni,no->io``) are ops of ``dv3/wm/rssm_scan``
    too, or ``train_step.rssm_scan_device_ms`` would fall without the step falling."""
    import re

    paths = set(re.findall(r'loc\("([^"]*)"', lowered["dv3_train_step"]))
    loop = [p for p in paths if "transpose(jvp())/while" in p]  # the step's one loop under a gradient of its own
    contractions = [p for p in paths if "ni,no->io" in p]
    assert any("/while/body/" in p for p in loop) and any(p.endswith("/dot_general") for p in contractions)
    for path in loop + contractions:
        assert "/transpose(jvp(dv3/wm/rssm_scan))/" in path, path
    assert any("/jvp(dv3/wm/rssm_scan)/jvp()/while" in p for p in paths), "the probed forward loop"
    assert all("dv3/" in p for p in paths if "jvp()" in p), "an inner gradient's op outside every scope"


# --------------------------------------------------------------------------- #
# the loop's spans: every turn's work lies in a span other than the two window spans
# --------------------------------------------------------------------------- #

WINDOW_SPANS = ("Time/env_interaction_time", "Time/train_time")
#: span -> the parent it names (the loop's new spans)
DV3_LOOP_SPANS = {"loop/head": None, "player/to_env": "Time/env_interaction_time", "train/plan": None, "train/keys": "Time/train_time",
                  "train/queue_next": "Time/train_time", "loop/tail": None}  # fmt: skip


def loop_iterations(events):
    """``(wall, covered)`` seconds of each loop iteration, from a ``loop/head``
    to the end of the ``loop/tail`` that follows it: its time, and the self
    time (a span's duration less its children's, found by ``parent``:
    ``perfbench/span_tree.py``) of every span inside it but the two window spans."""
    from perfbench import span_tree

    spans = span_tree.of_events(events)
    own = span_tree.self_seconds(spans)
    out = []
    for head in (s for s in spans if s.name == "loop/head"):
        tail = next((t for t in spans if t.name == "loop/tail" and t.start >= head.end), None)
        if tail is not None:
            covered = sum(o for s, o in zip(spans, own) if head.start <= s.start and s.end <= tail.end + 1e3 and s.name not in WINDOW_SPANS)
            out.append(((tail.end - head.start) / 1e9, covered))
    return out


def test_the_loops_spans_name_their_parents_and_cover_each_turn(tmp_path, monkeypatch):
    """A tiny run with telemetry on, on the device ring as the cells run it:
    two random turns, then six that train (two gradient steps each). Every new
    span is there under the parent it names, and the self time of the spans
    but the two window spans covers the turns that train to within a few
    percent (one of five may read less: a pause of the process can fall
    between two spans)."""
    import json
    import os

    from sheeprl_tpu.cli import run
    from tests.test_algos.test_dreamer_v3 import dv3_args

    monkeypatch.chdir(tmp_path)
    args = [a for a in dv3_args(tmp_path) if a not in ("dry_run=True", "algo.learning_starts=0", "algo.run_test=True", "checkpoint.save_last=True")]
    run(args + ["algo.total_steps=16", "algo.learning_starts=4", "algo.run_test=False", "checkpoint.save_last=False", "fabric.devices=1", "buffer.device=True",
                "metric.telemetry.enabled=True", "metric.telemetry.poll_interval=0.0"])  # fmt: skip
    (path,) = [os.path.join(root, f) for root, _, files in os.walk(tmp_path) for f in files if f == "telemetry.jsonl"]
    events = [json.loads(line) for line in open(path) if line.strip()]
    spans = [e for e in events if e["event"] == "span" and "t_mono_ns" in e]
    for name, parent in DV3_LOOP_SPANS.items():
        found = [e for e in spans if e["name"] == name]
        assert found and all(e["parent"] == parent for e in found), (name, {e["parent"] for e in found})
    assert len([e for e in spans if e["name"] == "loop/head"]) == len([e for e in spans if e["name"] == "loop/tail"]) == 8
    assert {e["parent"] for e in spans if e["name"] in ("player/get_actions", "env/step")} == {"Time/env_interaction_time"}
    assert {e["parent"] for e in spans if e["name"] == "ring/add"} <= {"Time/env_interaction_time", "loop/store_step"}  # the reset add
    assert {e["parent"] for e in spans if e["name"] in ("replay/draw", "train/dispatch", "train/block")} == {"Time/train_time"}
    turns = loop_iterations(events)
    assert len(turns) == 8
    # the turns that train, past the first's compiles: what no span covers is what the spans cost to open and close,
    # some 10 to 80 us a span on a CPU shared with XLA's threads, against turns of 7 ms and more here
    shares = [covered / wall for wall, covered in turns[-5:]]
    assert sorted(shares)[1] > 0.9, shares
