"""``chip_smoke.py`` without the chip (on-chip-measurement guide, section 2,
first and second rehearsals): the script's own phase functions, at tiny
sizes, on the CPU and on four of the suite's virtual devices, with the Pallas
kernel interpreted — so wrong paths, arguments, control flow, meshes and
sharding rules are found here and not on chip time. The script itself has no
CPU switch: run as the driver runs it, it must fail here.
"""

import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)
import chip_smoke  # noqa: E402

sys.path.pop(0)

# the dv3_args sizes of tests/test_algos/test_dreamer_v3.py, as overrides of
# the flagship recipe; fabric.accelerator=cpu because the recipe demands tpu
TINY = [
    "algo.per_rank_batch_size=2",
    "algo.per_rank_sequence_length=8",
    "algo.horizon=4",
    "algo.dense_units=8",
    "algo.mlp_layers=1",
    "algo.world_model.encoder.cnn_channels_multiplier=2",
    "algo.world_model.recurrent_model.recurrent_state_size=8",
    "algo.world_model.representation_model.hidden_size=8",
    "algo.world_model.transition_model.hidden_size=8",
    "algo.world_model.discrete_size=4",
    "algo.world_model.stochastic_size=4",
    "buffer.memmap=False",
    "env.screen_size=16",
    "fabric.accelerator=cpu",
]


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]], ids=["one_chip", "four_chips"])
def test_smoke_script_fails_without_a_tpu(argv):
    """Exit code not 0, and never the contract's ``"ok": true`` line."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py"), *argv],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=180,
    )
    assert proc.returncode != 0, proc.stdout[-2000:]
    assert '"ok"' not in proc.stdout, proc.stdout[-2000:]
    assert "not a TPU" in proc.stderr, proc.stderr[-2000:]


def test_kernel_phase_interpreted():
    out = chip_smoke.phase_kernel(
        in_dim=12, dense=16, hidden=8, batches=(5, 16), fwd_atol=1e-5, grad_rtol=1e-4, interpret=True
    )
    assert out["mode"] == "interpret" and len(out["cases"]) == 2
    # a phase that finds something wrong raises — nothing turns it into exit 0
    with pytest.raises(RuntimeError, match="kernel forward off"):
        chip_smoke.phase_kernel(
            in_dim=12, dense=16, hidden=8, batches=(5,), fwd_atol=0.0, grad_rtol=1e-4, interpret=True
        )


def test_train_then_eval_phases_tiny(tmp_path, monkeypatch):
    """train -> two checkpoints -> eval through the two CLI entry points,
    with everything the phase reads back from the run record: placement of
    params / optimizer / replay ring (forced onto the device here: `auto`
    keeps the ring on the host when the backend IS the host), finite losses,
    params that moved, compile counts, no recompile."""
    monkeypatch.chdir(tmp_path)
    work = str(tmp_path / "work")
    out = chip_smoke.phase_train(
        work,
        platform="cpu",
        total_steps=64,
        learning_starts=40,
        buffer_size=400,
        checkpoint_every=52,
        expect_warm=False,  # the warm point is 64 updates past the first train window
        overrides=TINY + ["fabric.devices=1", "buffer.device=true"],
    )
    rec = out["record"]
    assert rec["accelerator"] == {"requested": "cpu", "platform": "cpu"}
    assert rec["resolved"]["buffer_device"]["value"] == "device"
    assert rec["resolved"]["player_device"] == {"value": "cpu", "spec": "auto"}
    assert rec["resolved"]["state_devices"]["value"]["replay"] == ["cpu:0"]
    assert rec["train_gradient_steps"] >= 9 and rec["recompiles"] == 0
    assert rec["native_gather"] in ("native", "not loaded") or rec["native_gather"].startswith("numpy (")
    assert out["checkpoint"].endswith("ckpt_64_0.ckpt")
    evaluated = chip_smoke.phase_eval(out["checkpoint"], work, platform="cpu")
    assert evaluated["record"]["kind"] == "eval"


def test_mesh_phases_tiny_on_virtual_devices():
    """The two four-chip comparisons on four virtual CPU devices: the [4]
    data mesh against vmap(local_train, axis_name=data) on one device, and
    the [2, 2] (data, model) mesh against a one-device fabric."""
    import jax

    if len(jax.devices()) < 4:
        pytest.fail("the suite runs on 8 virtual CPU devices (tests/conftest.py)")
    dp = chip_smoke.phase_data_parallel(
        chip_smoke._compose_cfg(TINY), n_devices=4, loss_rtol=2e-2, min_cosine=0.9
    )
    assert dp["max_loss_rel"] < 2e-2
    # widths that divide by the model axis, so the kernels genuinely split
    wide = [o for o in TINY if "dense_units" not in o and "recurrent_state_size" not in o]
    wide += ["algo.dense_units=16", "algo.world_model.recurrent_model.recurrent_state_size=16"]
    mp = chip_smoke.phase_model_parallel(
        chip_smoke._compose_cfg(wide), mesh_shape=(2, 2), loss_rtol=2e-2, min_cosine=0.9
    )
    assert min(mp["world_model"], mp["actor"], mp["critic"]) >= 0.9


def test_last_line_contract(capsys, tmp_path, monkeypatch):
    """``finish`` prints the contract's object as the LAST line, built from
    the devices JAX reports."""
    import jax

    monkeypatch.setattr(chip_smoke, "REPO", str(tmp_path))
    chip_smoke.finish(jax.devices()[:1], {"phase": "x"}, "out.json")
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert json.load(open(tmp_path / "chiprun_out" / "out.json")) == {"phase": "x"}
