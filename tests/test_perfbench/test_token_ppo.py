"""The token policy's cell, tiny on the CPU: its run through the harness to a
result line (``correct`` true; false under each planted fault), the per-layer
metrics that read the loop's counters, and the FLOP and byte counters of its
algorithm module against hand counts."""

import contextlib
import json

import numpy as np
import pytest

from perfbench import loader, run
from perfbench.algorithms import token_ppo
from tests.test_perfbench import tiny

CELL = "glm47_flash_ep8.train"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("perfbench_token_root")))


def test_a_traced_run_reaches_a_correct_result_line(root):
    line = json.loads(json.dumps(run.run_cell(CELL, 2**31 + 29, 1.5, True, root=root, require_tpu=False)))
    assert list(line)[:3] == ["correct", "attempted", "failed"] and list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    # the device metrics and the shares of a peak need a chip; the host's spans and the loop's counters are read on any machine
    assert {"compile.in_window", "env.step_share", "loop.env_interaction_ms", "loop.action_fetch_ms", "loop.env_step_host_ms", "loop.train_block_ms",
            "moe.held_pair_share", "moe.max_expert_load", "update.padding_share"} <= set(line["metrics"])  # fmt: skip
    with open(f"{root}/BENCHMARK.json") as f:
        listed = {m["name"] for m in json.load(f)["per_layer"] if CELL in m["workloads"]}
    assert set(line["metrics"]) <= listed and len(listed) == 24
    values = {k: v["value"] for k, v in line["metrics"].items()}
    assert values["compile.in_window"] == 0.0
    assert 0.0 < values["moe.held_pair_share"] < 100.0 and values["moe.max_expert_load"] >= 1.0
    assert 0.0 < values["update.padding_share"] < 100.0
    assert set(line["compared"]) == set(tiny.rule("token_ppo").LIMITS)


def _faulty(fault):
    """A context manager that breaks the program underneath the harness: one
    held expert computes nothing, or the update trains on half its minibatch."""

    @contextlib.contextmanager
    def patch():
        import jax.numpy as jnp

        from sheeprl_tpu.algos.ppo_recurrent import token_policy as program
        from sheeprl_tpu.models import seqpol

        real_slot, real_make = seqpol._held_slot, program.make_token_train_fn

        def held_slot(cfg, chosen):
            slot = real_slot(cfg, chosen)
            return jnp.where(slot == 1, -1, slot) if fault == "expert_left_out" else slot

        def make_token_train_fn(*args, **kwargs):
            fn = real_make(*args, **kwargs)
            if fault != "half_batch":
                return fn

            def half(params, opt_state, params_lo, batch, *rest):
                mask = np.array(batch["mask"])
                mask[mask.shape[0] // 2 :] = 0.0
                return fn(params, opt_state, params_lo, {**batch, "mask": mask}, *rest)

            half.__name__ = fn.__name__
            return half

        seqpol._held_slot, program.make_token_train_fn = held_slot, make_token_train_fn
        try:
            yield
        finally:
            seqpol._held_slot, program.make_token_train_fn = real_slot, real_make

    return patch


@pytest.mark.parametrize("fault", ["expert_left_out", "half_batch"])
def test_a_planted_fault_is_not_correct(root, fault):
    line = run.run_cell(CELL, 7, 1.0, False, root=root, require_tpu=False, program_patch=_faulty(fault))
    assert line["correct"] is False, line["compared"]
    failed = {k for k, v in line["compared"].items() if not v["value"] <= v["limit"]}
    if fault == "expert_left_out":
        assert {"player_logits", "first_grad"} <= failed, failed  # the player and the update both lack the expert
    else:
        assert "player_logits" not in failed and {"policy_loss", "first_grad"} & failed, failed  # the player is sound, the update is not
    assert line["compared"]["rollout_rows"]["value"] == 0


def test_a_wrong_row_of_the_rollout_is_seen(root, monkeypatch):
    """The look at the rollout fails alone when the update is fed another env's rewards."""
    cell = loader.Cell(CELL, root)
    algorithm = loader.algorithm(cell)
    kept = {}

    def verify(cfg, seed, capture, limits, stamps):
        kept["sound"] = algorithm.rollout_rows(cfg, seed, capture.rollout, stamps)
        swapped = {**capture.rollout, "rewards": capture.rollout["rewards"][:, ::-1]}
        kept["swapped"] = algorithm.rollout_rows(cfg, seed, swapped, stamps)
        return True, {}, {}

    run.run_cell(CELL, 11, 0.5, False, root=root, require_tpu=False, verify=verify)
    assert kept["sound"] == 0 and kept["swapped"] > 0


# --------------------------------------------------------------------------- #
# the FLOP and byte counters against hand counts
# --------------------------------------------------------------------------- #

SMALL = {"model": {"hidden_size": 8, "num_attention_heads": 2, "q_lora_rank": 3, "kv_lora_rank": 4, "qk_nope_head_dim": 2, "qk_rope_head_dim": 2,
                   "v_head_dim": 3, "intermediate_size": 5, "moe_intermediate_size": 6, "n_routed_experts": 4, "held_experts": [0, 1],
                   "num_experts_per_tok": 2, "n_shared_experts": 1, "first_k_dense_replace": 1, "num_hidden_layers": 2,
                   "num_nextn_predict_layers": 0, "vocab_rows": 10, "context": 7, "prompt_max": 3},
         "algo": {"num_envs": 2, "batch_size": 2, "sequence_length": 5, "prefill_rows": 1}}  # fmt: skip


def test_decode_counters_by_hand():
    # per row and layer: q_a 8x3, q_b 3x(2x4), kv_a 8x(4+2), o (2x3)x8; absorbed: 2 heads x 4 x (2+3) and 7 cache entries x 2 heads x (2x4+2)
    attn = 8 * 3 + 3 * 8 + 8 * 6 + 6 * 8 + 2 * 4 * 5 + 7 * 2 * 10
    dense, expert = 3 * 8 * 5, 3 * 8 * 6
    moe = 8 * 4 + expert + 2 * expert  # router, shared, both held experts on every row
    head = 8 * 10 + 8
    assert token_ppo.decode_flops(SMALL) == 2 * 2 * (2 * attn + dense + moe + head)
    # bytes: 2 a weight (both layers' attention with W_kvb 4x2x5, the dense MLP, router, shared and two experts, the head, 2 embedding rows),
    # 2 a cache number (9 entries x 2 layers x 6 wide), 4 a logit (2 rows x 10)
    weights = 2 * (8 * 3 + 3 * 8 + 8 * 6 + 6 * 8 + 4 * 2 * 5) + dense + (8 * 4 + 3 * expert) + head + 2 * 8
    assert token_ppo.decode_bytes(SMALL, 9) == 2 * (weights + 9 * 2 * 6) + 4 * 2 * 10


def test_train_step_counters_by_hand():
    rows, slots, ctx, steps = 2, 5, 7, 2  # minibatches of 2 sequences; 5 slots of which 5 - 3 are steps
    per_position = 8 * 3 + 3 * 8 + 8 * 6 + 6 * 8
    layer = rows * slots * per_position + rows * (slots + ctx) * (4 * 2 * 5) + rows * slots * (slots + ctx) * 2 * (4 + 3)
    pairs = rows * slots * 2 * 2 / 4  # one expert layer, 2 a token, 2 of 4 held
    total = 2 * layer + rows * slots * 3 * 8 * 5 + rows * slots * (8 * 4 + 3 * 8 * 6) + pairs * 3 * 8 * 6 + rows * steps * (8 * 10 + 8)
    assert token_ppo.train_step_flops(SMALL) == pytest.approx(3 * 2 * total)
    assert token_ppo.train_step_flops(SMALL, held_pairs=0.0) == pytest.approx(3 * 2 * (total - pairs * 3 * 8 * 6))
    # the prefill: one row of 3 slots, no context, no head
    layer = 3 * per_position + 3 * (4 * 2 * 5) + 3 * 3 * 2 * (4 + 3)
    assert token_ppo.prefill_flops(SMALL, held_pairs=0.0) == pytest.approx(2 * (2 * layer + 3 * 3 * 8 * 5 + 3 * (8 * 4 + 3 * 8 * 6)))


def test_the_configurations_count_is_the_algorithms():
    config = loader.Cell(CELL).config
    assert config["model_flops_per_grad_step"] == token_ppo.model_flops(config)
    # 706.5 M parameters held here: the arithmetic of the cut
    from perfbench.references import token_ppo as reference

    assert sum(int(np.prod(shape)) for shape in reference._shapes(config["model"]).values()) == pytest.approx(706.5e6, rel=2e-3)


def test_the_train_step_is_read_over_the_traced_cycle(tmp_path, monkeypatch):
    """The traced stretch of a cell that names a cycle runs from one rollout
    end to the next (``run.trace_stretch``), so it holds the update's train
    steps whole beside the rollout's decodes: the cell's train-step readers
    read them there, and so would ``train_step.device_ms``."""
    from perfbench import device_time, token_counters

    ms = 1e6
    sync_ns = 5e9  # the host's clock at the sync annotation; the trace's own clock starts at 0 there
    modules = [["jit_seqpol_decode(1)", 10 * ms, 3 * ms], ["jit_seqpol_train_step(2)", 100 * ms, 40 * ms], ["jit_seqpol_train_step(2)", 150 * ms, 60 * ms]]
    ops = [["%a", 10 * ms, 3 * ms, "jit(seqpol_decode)/seqpol/attn/dot"],
           ["%b", 100 * ms, 30 * ms, "jit(seqpol_train_step)/jvp(seqpol/attn)/dot"],
           ["%c", 130 * ms, 10 * ms, "jit(seqpol_train_step)/transpose(jvp(seqpol/moe/experts))/ragged_dot"],
           ["%d", 150 * ms, 50 * ms, "jit(seqpol_train_step)/transpose(jvp(seqpol/attn))/dot"],
           ["%e", 200 * ms, 10 * ms, "jit(seqpol_train_step)/jvp(seqpol/moe/experts)/ragged_dot"]]  # fmt: skip
    monkeypatch.setattr(device_time, "load", lambda path: {"sync": [0.0, 0.0], "modules": modules, "ops": ops})
    trace = tmp_path / "trace" / "plugins" / "profile" / "t"
    trace.mkdir(parents=True)
    (trace / "host.xplane.pb").write_bytes(b"")

    class Watcher:  # the traced cycle: a rollout end 5 ms after the sync, an update, the next rollout's end 215 ms after it
        sync = {"before_ns": sync_ns, "inside_ns": sync_ns}
        traced_from, cycle = 0, 1

    cell = loader.Cell(CELL)
    exit_ns = np.asarray([sync_ns + 5 * ms, sync_ns + 215 * ms])
    window = {"open_ns": int(sync_ns - 27e9), "close_ns": int(sync_ns + 215 * ms)}
    facts = dict(cell=cell, run_dir=str(tmp_path), watcher=Watcher(), peak={"bf16_flops_per_s": 197e12}, telemetry_events=[], window=window,
                 entry_ns=exit_ns - ms, exit_ns=exit_ns, stretch_ns=run.trace_stretch(Watcher(), exit_ns, window))  # fmt: skip
    made = run.RunFacts(**facts)
    readers = loader.layer_readers(cell)
    assert readers["train_step.seqpol_device_ms"](made) == pytest.approx(50.0)
    assert readers["train_step.seqpol_device_mfu"](made) == pytest.approx(100 * cell.config["model_flops_per_grad_step"] / (0.050 * 197e12))
    assert readers["train_step.attn_device_ms"](made) == pytest.approx(40.0) and readers["train_step.moe_experts_device_ms"](made) == pytest.approx(10.0)
    assert readers["player.decode_device_ms"](made) == pytest.approx(3.0)  # the rollout's decode, in the same stretch
    assert device_time.train_ms(made) == pytest.approx(50.0)  # the stretch's own reduction holds the train steps
