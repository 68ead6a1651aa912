"""Checkpoint backends + buffer-consistency fixup (reference:
sheeprl/utils/callback.py:87-148 and fabric.save/load)."""

import os
import pickle

import numpy as np
import pytest

from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, ReplayBuffer, SequentialReplayBuffer
from sheeprl_tpu.utils.callback import CheckpointCallback
from sheeprl_tpu.utils.checkpoint import load_checkpoint, save_checkpoint, select_buffer


class _FakeFabric:
    num_processes = 1
    world_size = 1
    is_global_zero = True


import collections

Opt = collections.namedtuple("Opt", ["mu", "nu"])


def _tree():
    return {
        "params": {"dense": {"kernel": np.random.rand(4, 3).astype(np.float32), "bias": np.zeros(3)}},
        "opt": Opt(mu=np.ones((4, 3)), nu=np.zeros((4, 3))),
        "ratio": {"ratio": 0.5, "prev": 10},
        "update": 7,
        "name": "run",
        "mixed": [np.arange(5), "text", 3],
    }


@pytest.mark.parametrize("backend", ["pickle", "orbax"])
def test_checkpoint_roundtrip(tmp_path, backend):
    state = _tree()
    path = str(tmp_path / ("ck.ckpt" if backend == "pickle" else "ck_dir.ckpt"))
    save_checkpoint(path, state, backend=backend)
    out = load_checkpoint(path)
    np.testing.assert_array_equal(out["params"]["dense"]["kernel"], state["params"]["dense"]["kernel"])
    np.testing.assert_array_equal(out["opt"].mu, state["opt"].mu)
    assert out["ratio"] == state["ratio"] and out["update"] == 7 and out["name"] == "run"
    np.testing.assert_array_equal(out["mixed"][0], np.arange(5))
    assert out["mixed"][1:] == ["text", 3]
    assert type(out["opt"]).__name__ == "Opt"


def test_checkpoint_truncated_fixup(tmp_path):
    """The SAVED buffer ends every env's episode (truncated=1 at the last
    stored step) while the LIVE buffer is untouched (reference
    callback.py:87-142)."""
    rb = EnvIndependentReplayBuffer(8, n_envs=2, buffer_cls=SequentialReplayBuffer, seed=0)
    data = {
        "obs": np.random.rand(3, 2, 4).astype(np.float32),
        "terminated": np.zeros((3, 2, 1), np.float32),
        "truncated": np.zeros((3, 2, 1), np.float32),
    }
    rb.add(data)

    cb = CheckpointCallback()
    ckpt_path = str(tmp_path / "ck.ckpt")
    cb.on_checkpoint_coupled(_FakeFabric(), ckpt_path, {"update": 1}, replay_buffer=rb)

    # live buffer: unchanged
    for b in rb.buffer:
        assert b["truncated"][(b._pos - 1) % b.buffer_size].sum() == 0
    # stored buffer: last step truncated for every env
    saved = load_checkpoint(ckpt_path)["rb"]
    for b in saved.buffer:
        assert b["truncated"][(b._pos - 1) % b.buffer_size].sum() == 1


def test_checkpoint_plain_replay_buffer_fixup(tmp_path):
    rb = ReplayBuffer(8, n_envs=2, seed=0)
    rb.add(
        {
            "observations": np.zeros((3, 2, 4), np.float32),
            "terminated": np.zeros((3, 2, 1), np.float32),
            "truncated": np.zeros((3, 2, 1), np.float32),
        }
    )
    cb = CheckpointCallback()
    ckpt_path = str(tmp_path / "ck.ckpt")
    cb.on_checkpoint_coupled(_FakeFabric(), ckpt_path, {}, replay_buffer=rb)
    assert rb["truncated"][(rb._pos - 1) % rb.buffer_size].sum() == 0
    saved = load_checkpoint(ckpt_path)["rb"]
    assert saved["truncated"][(saved._pos - 1) % saved.buffer_size].sum() == 2


@pytest.mark.slow
def test_dv3_orbax_resume_restores_buffer_and_counters(tmp_path, monkeypatch):
    """End to end: train tiny DV3 with the orbax backend + buffer checkpoint,
    resume, and verify the restored buffer contents and counters match the
    saved run."""
    from sheeprl_tpu.cli import run

    args = [
        "exp=dreamer_v3",
        "env=dummy",
        "env.id=dummy_discrete",
        # a real (non-dry) 2-update run so the resume has budget left
        "algo.total_steps=4",
        "checkpoint.every=2",
        "env.capture_video=False",
        "buffer.memmap=False",
        "buffer.checkpoint=True",
        "checkpoint.backend=orbax",
        "algo.per_rank_batch_size=1",
        "algo.per_rank_sequence_length=1",
        "buffer.size=10",
        "algo.learning_starts=0",
        "algo.replay_ratio=1",
        "algo.per_rank_pretrain_steps=1",
        "algo.horizon=4",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.world_model.encoder.cnn_channels_multiplier=2",
        "algo.world_model.recurrent_model.recurrent_state_size=8",
        "algo.world_model.representation_model.hidden_size=8",
        "algo.world_model.transition_model.hidden_size=8",
        "algo.world_model.discrete_size=4",
        "algo.world_model.stochastic_size=4",
        "algo.cnn_keys.encoder=[rgb]",
        "algo.mlp_keys.encoder=[state]",
        "env.num_envs=2",
        "algo.run_test=False",
        "checkpoint.save_last=True",
        "metric.log_level=0",
        f"log_base_dir={tmp_path}/logs",
    ]
    def find_ckpt_dirs():
        found = []
        for root, dirs, _ in os.walk(tmp_path):
            found += [os.path.join(root, d) for d in dirs if d.endswith(".ckpt")]
        return sorted(found)

    monkeypatch.chdir(tmp_path)
    run(args)
    ckpts = find_ckpt_dirs()
    assert ckpts and all(os.path.isdir(c) for c in ckpts)  # orbax ckpts are dirs

    # pretend the run died after update 1: resume from the earliest checkpoint
    first = min(ckpts, key=lambda c: int(os.path.basename(c).split("_")[1]))
    state = load_checkpoint(first)
    assert state["update"] == 1
    rb = select_buffer(state["rb"], 0, 1)
    saved_pos = [b._pos for b in rb.buffer]
    # the stored copy ends every env's episode
    for b in rb.buffer:
        assert b["truncated"][(b._pos - 1) % b.buffer_size].sum() == 1

    run(args + [f"checkpoint.resume_from={first}"])
    new = [c for c in find_ckpt_dirs() if c not in ckpts]
    assert new, "resume did not write a new checkpoint"
    last = max(new, key=lambda c: int(os.path.basename(c).split("_")[1]))
    state2 = load_checkpoint(last)
    assert state2["update"] == 2  # counters continued exactly from update 1
    rb2 = select_buffer(state2["rb"], 0, 1)
    # the restored buffer kept the saved contents and grew by the new steps
    for b2, p in zip(rb2.buffer, saved_pos):
        assert b2._pos == p + 1


def test_select_buffer():
    assert select_buffer("rb", 0, 1) == "rb"
    assert select_buffer(["a", "b"], 1, 2) == "b"
    assert select_buffer(["a"], 0, 1) == "a"
    with pytest.raises(RuntimeError):
        select_buffer(["a", "b", "c"], 0, 2)


def test_elastic_per_rank_batch_size():
    """Elastic resume re-splits the checkpoint's GLOBAL batch over the new
    mesh and fails fast instead of silently flooring (ISSUE satellite)."""
    from sheeprl_tpu.utils.checkpoint import elastic_per_rank_batch_size

    assert elastic_per_rank_batch_size(64, 8) == 8
    assert elastic_per_rank_batch_size(64, 1) == 64
    assert elastic_per_rank_batch_size(8, 8) == 1
    with pytest.raises(ValueError, match="does not split"):
        elastic_per_rank_batch_size(64, 6)  # non-dividing
    with pytest.raises(ValueError, match="does not split"):
        elastic_per_rank_batch_size(4, 8)  # would divide to zero
    with pytest.raises(ValueError, match="does not split"):
        elastic_per_rank_batch_size(0, 4)  # degenerate stored batch
    with pytest.raises(ValueError):
        elastic_per_rank_batch_size(64, 0)  # degenerate world size


def test_orbax_saves_sharded_jax_arrays_without_host_copy(tmp_path):
    """jax.Array leaves (incl. sharded ones) ride the orbax store directly;
    restore materializes them back to numpy."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    mesh = Mesh(np.array(devs), ("d",))
    sharded = jax.device_put(
        jnp.arange(len(devs) * 4, dtype=jnp.float32).reshape(len(devs), 4),
        NamedSharding(mesh, P("d", None)),
    )
    state = {"w": sharded, "b": jnp.ones(3), "n": 5}
    path = str(tmp_path / "sharded.ckpt")
    save_checkpoint(path, state, backend="orbax")
    out = load_checkpoint(path)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(sharded))
    np.testing.assert_array_equal(np.asarray(out["b"]), np.ones(3))
    assert out["n"] == 5


def test_orbax_per_process_sidecars_single(tmp_path):
    """per_process_state rides objects_rank_{i}.pkl and reloads as a
    one-entry-per-process list for select_buffer."""
    rb = ReplayBuffer(8, 1, obs_keys=("observations",))
    rb.add({"observations": np.ones((1, 1, 3), np.float32)})
    path = str(tmp_path / "rank.ckpt")
    save_checkpoint(path, {"update": 3}, backend="orbax", per_process_state={"rb": rb})
    assert os.path.exists(os.path.join(path, "objects_rank_0.pkl"))
    out = load_checkpoint(path)
    assert isinstance(out["rb"], list) and len(out["rb"]) == 1
    picked = select_buffer(out["rb"], 0, 1)
    np.testing.assert_array_equal(picked["observations"][0], np.ones((1, 3), np.float32))


def test_orbax_multiprocess_per_rank_buffers(tmp_path):
    """2 real processes save ONE orbax checkpoint: shared arrays plus one
    buffer sidecar per process; the reload yields a 2-entry rb list
    (no gathered process-0 pickle)."""
    from tests.conftest import run_multi_process

    code = """
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address=os.environ["TEST_COORD"],
    num_processes=int(os.environ["TEST_NPROC"]),
    process_id=int(os.environ["TEST_PID"]),
)
import numpy as np
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.utils.checkpoint import save_checkpoint

pid = jax.process_index()
rb = ReplayBuffer(8, 1, obs_keys=("observations",))
rb.add({"observations": np.full((1, 1, 3), pid, np.float32)})
save_checkpoint(
    sys.argv[1], {"update": 2}, backend="orbax", per_process_state={"rb": rb}
)
"""
    path = str(tmp_path / "multi.ckpt")
    run_multi_process(code, argv=[path], cwd=str(tmp_path), nproc=2)
    out = load_checkpoint(path)
    assert out["update"] == 2
    assert isinstance(out["rb"], list) and len(out["rb"]) == 2
    for rank in (0, 1):
        picked = select_buffer(out["rb"], rank, 2)
        np.testing.assert_array_equal(
            picked["observations"][0], np.full((1, 3), rank, np.float32)
        )
