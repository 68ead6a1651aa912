"""Elastic checkpoint restore: a run saved on one mesh size resumes on
another (reference semantics: the checkpoint stores
the GLOBAL batch — ``dreamer_v3.py`` writes ``batch_size = per_rank *
world_size`` and resume divides by the NEW world size — while the reference
itself refuses world-size changes, callback.py:87-142).

Device elasticity is the TPU-native win: params checkpoint as host arrays
(sharding-free), so an 8-chip run's state reshards onto any divisor mesh at
resume. These tests drive DV3 end to end on the virtual CPU mesh: shrink
8 -> 4, grow 4 -> 8, and cross mesh KINDS (param-sharded -> pure DP).
"""

import os
import pytest

from sheeprl_tpu.cli import run
from sheeprl_tpu.utils.checkpoint import load_checkpoint
from tests.conftest import find_checkpoints
from tests.test_algos.test_dreamer_v3 import dv3_args


def _elastic_args(tmp_path):
    # a REAL (non-dry_run) schedule so the resumed half actually trains:
    # 2 envs -> 2 policy steps/update, total 8 steps = 4 updates, mid-run
    # checkpoint at update 2. per_rank_batch_size is per DEVICE: 8 devices
    # x 1 -> global batch 8, which resharding onto 4 devices turns into
    # per-device 2.
    args = [a for a in dv3_args(tmp_path) if a != "dry_run=True"]
    return args + [
        "buffer.checkpoint=True",
        "algo.total_steps=8",
        "algo.learning_starts=2",
        "checkpoint.every=4",
        "algo.run_test=False",
    ]


def _save_then_resume(tmp_path, save_overrides, resume_overrides):
    """Save a mid-run checkpoint with one topology, resume with another;
    assert the resumed run genuinely trained (updates progressed, a newer
    checkpoint landed) and return ``(saved, resumed)`` states."""
    run(_elastic_args(tmp_path) + save_overrides)
    ckpt = min(find_checkpoints(tmp_path), key=os.path.getmtime)  # the mid-run one
    saved = load_checkpoint(ckpt)
    latest_before = max(os.path.getmtime(p) for p in find_checkpoints(tmp_path))
    run(_elastic_args(tmp_path) + resume_overrides + [f"checkpoint.resume_from={ckpt}"])
    newest = max(find_checkpoints(tmp_path), key=os.path.getmtime)
    assert os.path.getmtime(newest) > latest_before, "resumed run wrote no checkpoint"
    resumed = load_checkpoint(newest)
    assert resumed["update"] > saved["update"], "resume restored state but trained no updates"
    return saved, resumed


@pytest.mark.slow
def test_dv3_save_on_8_resume_on_4(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    saved, resumed = _save_then_resume(tmp_path, ["fabric.devices=8"], ["fabric.devices=4"])
    # global batch recorded (not per-device) and preserved across the change
    assert saved["batch_size"] == 8
    assert resumed["batch_size"] == 8


@pytest.mark.slow
def test_dv3_save_on_4_resume_on_8(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    saved, resumed = _save_then_resume(
        tmp_path, ["fabric.devices=4", "algo.per_rank_batch_size=2"], ["fabric.devices=8"]
    )
    assert saved["batch_size"] == 8
    assert resumed["batch_size"] == 8


@pytest.mark.slow
def test_dv3_model_axis_checkpoint_resumes_on_dp_mesh(tmp_path, monkeypatch):
    """Topology change ACROSS mesh kinds: a checkpoint trained with param
    sharding on a (data=2, model=4) mesh resumes on a plain 8-wide DP mesh —
    possible because checkpoints store host-layout arrays, and because
    explicitly-passed fabric.* overrides (including mesh_axes) win over the
    stored fabric section at resume (cli.resume_from_checkpoint)."""
    monkeypatch.chdir(tmp_path)
    saved, resumed = _save_then_resume(
        tmp_path,
        [
            "fabric.mesh_axes=[data,model]",
            "fabric.mesh_shape=[2,4]",
            "algo.per_rank_batch_size=4",  # data width 2 -> global batch 8
            "algo.dense_units=16",
            "algo.world_model.recurrent_model.recurrent_state_size=16",
        ],
        ["fabric.mesh_axes=[data]", "fabric.mesh_shape=null", "fabric.devices=8"],
    )
    assert saved["batch_size"] == 8
    assert resumed["batch_size"] == 8
