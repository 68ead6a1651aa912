"""The hybrid token policy's cell, tiny on the CPU: its run through the harness
to a result line (``correct`` true; false under each planted fault), the
per-layer metrics that read the loop's counters, the four new readers on a
made-up trace, and the FLOP and byte counters of its algorithm module against
hand counts."""

import contextlib
import json

import numpy as np
import pytest

from perfbench import check_line, loader, run
from perfbench.algorithms import token_ppo_lfm2
from tests.test_algos.test_token_policy import conv_state_kept
from tests.test_perfbench import tiny
from tests.test_perfbench.test_token_ppo import _faulty

CELL = "lfm2_24b_a2b_ep8.train"
NEW = {"loop.cycle_mfu", "train_step.conv_device_ms", "train_step.conv_mix_device_ms", "player.decode_state_hbm_share"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("perfbench_lfm2_root")))


def test_a_traced_run_reaches_a_correct_result_line(root):
    line = json.loads(json.dumps(run.run_cell(CELL, 2**31 + 31, 1.5, True, root=root, require_tpu=False)))
    assert list(line)[:3] == ["correct", "attempted", "failed"] and list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    # the device metrics and the shares of a peak need a chip; the host's spans and the loop's counters are read on any machine
    assert {"compile.in_window", "env.step_share", "loop.env_interaction_ms", "loop.action_fetch_ms", "loop.env_step_host_ms", "loop.train_block_ms",
            "moe.held_pair_share", "moe.max_expert_load", "update.padding_share"} <= set(line["metrics"])  # fmt: skip
    with open(f"{root}/BENCHMARK.json") as f:
        listed = {m["name"] for m in json.load(f)["per_layer"] if CELL in m["workloads"]}
    assert set(line["metrics"]) <= listed and len(listed) == 24 and NEW <= listed
    values = {k: v["value"] for k, v in line["metrics"].items()}
    assert values["compile.in_window"] == 0.0
    assert 0.0 < values["moe.held_pair_share"] < 100.0 and values["moe.max_expert_load"] >= 1.0
    assert 0.0 < values["update.padding_share"] < 100.0
    assert set(line["compared"]) == set(tiny.rule("token_ppo_lfm2").LIMITS)
    # the line check knows this run for what it is: no chip, so no device metric, no peak and no busy time
    faults = check_line.faults(json.dumps(line), CELL, True, root)
    assert faults and all(f.startswith(("metrics.", "device.")) for f in faults), faults


def _state_unchanged():
    """The update computes its step and hands the weights back as they came."""

    @contextlib.contextmanager
    def patch():
        import jax
        import jax.numpy as jnp

        from sheeprl_tpu.algos.ppo_recurrent import token_policy as program

        real = program.make_token_train_fn

        def make_token_train_fn(*args, **kwargs):
            fn = real(*args, **kwargs)

            def unchanged(params, opt_state, params_lo, *rest):
                kept = jax.tree.map(jnp.copy, (params, params_lo))  # the step takes its arguments donated
                _, opt_state, _, metrics = fn(params, opt_state, params_lo, *rest)
                return kept[0], opt_state, kept[1], metrics

            unchanged.__name__ = fn.__name__
            return unchanged

        program.make_token_train_fn = make_token_train_fn
        try:
            yield
        finally:
            program.make_token_train_fn = real

    return patch


#: what is planted underneath the harness -> the numbers that have to fail, and those that must not
PLANTED = {
    # the decode behind a prefill reads what the episode before left: the player's numbers fail, the update's do not
    "conv_state_kept": (conv_state_kept, {"player_logits", "player_reset_logits"}, {"first_grad", "change"}),
    # the player and the update both lack the expert
    "expert_left_out": (_faulty("expert_left_out"), {"player_logits", "first_grad"}, set()),
    # the player is sound, the update trains on half its minibatch
    "half_batch": (_faulty("half_batch"), {"first_grad", "grad_direction", "change"}, {"player_logits", "player_reset_logits"}),
    # the first gradient is sound, the weights did not move: ``change`` reads 1 (and the second step's losses are the first's again)
    "state_unchanged": (_state_unchanged(), {"change"}, {"player_logits", "first_grad"}),
}


@pytest.mark.parametrize("fault", list(PLANTED))
def test_a_planted_fault_is_not_correct(root, fault):
    patch, fails, holds = PLANTED[fault]
    line = run.run_cell(CELL, 7, 1.0, False, root=root, require_tpu=False, program_patch=patch)
    assert line["correct"] is False, line["compared"]
    failed = {k for k, v in line["compared"].items() if not v["value"] <= v["limit"]}
    assert fails <= failed and not holds & failed, failed
    if fault == "state_unchanged":
        assert line["compared"]["change"]["value"] == pytest.approx(1.0, abs=1e-6)
    assert line["compared"]["rollout_rows"]["value"] == 0


def test_the_reference_with_the_fault_planted_departs_behind_a_prefill(root):
    """``conv_state_kept`` as the calibration plants it, in the reference: the forwards right behind a prefill move
    the most (at these lengths, 2 to 8 steps an episode, most forwards stand one or two positions behind one)."""
    kept = {}

    def verify(cfg, seed, capture, limits, stamps):
        import jax

        weights = jax.device_put(capture.seeded)
        _, arrays = token_ppo_lfm2.player_gaps(cfg["model"], weights, capture.player, conv_state_kept=True)
        kept.update(token_ppo_lfm2.player_gaps(cfg["model"], weights, capture.player, against=arrays)[0])
        return True, {}, {}

    run.run_cell(CELL, 11, 0.5, False, root=root, require_tpu=False, verify=verify)
    assert kept["player_reset_logits"] > 0.05 and kept["player_reset_logits"] > kept["player_logits"]


def test_the_calibration_judges_every_control_and_fault_not_correct(root, capsys):
    """``calibrate_token_ppo_lfm2.readings`` in ``verify``'s place: the program's numbers pass the cell's limits, and
    each control and fault, put through the same limits, comes out not correct by at least one."""
    from perfbench import calibrate_token_ppo_lfm2 as tool

    line = run.run_cell(CELL, 13, 0.5, False, root=root, require_tpu=False, verify=tool.readings)
    sides = {d["side"]: d for d in map(json.loads, (s for s in capsys.readouterr().out.splitlines() if s.startswith('{"side"')))}
    assert set(sides) == {"program", "state_unchanged", "verdict", *tool.SIDES}
    assert sides["program"]["correct"] is True and line["correct"] is True
    for name in ("state_unchanged", *tool.SIDES):
        assert sides[name]["correct"] is False and sides[name]["fails_by"], sides[name]
    assert sides["conv_state_kept"]["fails_by"] == ["player_logits", "player_reset_logits", "player_values"]
    assert not set(sides["half_batch"]) & {"player_logits", "player_reset_logits"}  # a side is judged by the numbers it can move
    assert sides["verdict"] == {"side": "verdict", "program_correct": True, "controls_and_faults_that_pass": []}
    # a limit so wide that a fault passes it turns the run's own verdict
    assert tool.judged("half_batch", {"first_grad": 0.5}, {"first_grad": 1.0, "change": 0.1})["correct"] is True


# --------------------------------------------------------------------------- #
# the FLOP and byte counters against hand counts
# --------------------------------------------------------------------------- #

SMALL = {"model": {"hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 3, "conv_L_cache": 3,
                   "layer_types": ["conv", "full_attention"], "intermediate_size": 5, "moe_intermediate_size": 6, "n_routed_experts": 4,
                   "held_experts": [0, 1], "num_experts_per_tok": 2, "first_k_dense_replace": 1, "num_hidden_layers": 2,
                   "tie_word_embeddings": True, "vocab_rows": 10, "context": 7, "prompt_max": 3},
         "algo": {"num_envs": 2, "batch_size": 2, "sequence_length": 5, "prefill_rows": 1}}  # fmt: skip
# per position: the convolution's W_in 8x24, W_out 8x8 and 3 taps of 8; the attention's W_q 8x12, W_k and W_v 8x6, W_o 12x8
CONV, ATTN = 8 * 24 + 8 * 8 + 3 * 8, 8 * 12 + 2 * 8 * 6 + 12 * 8
SCORE = 2 * 4 * 3  # per query and key: 4 heads of 3, q k^T and the weighted values
DENSE, ROUTER, EXPERT, HEAD = 3 * 8 * 5, 8 * 4, 3 * 8 * 6, 8 * 10 + 8


def test_decode_counters_by_hand():
    # one convolution layer over the dense MLP, one attention layer (7 cache entries scored) over the router and both held experts on every row
    per_row = CONV + (ATTN + 7 * SCORE) + DENSE + (ROUTER + 2 * EXPERT) + HEAD
    assert token_ppo_lfm2.decode_flops(SMALL) == 2 * 2 * per_row
    # bytes: 2 a weight (tied: the embedding's rows are the head, read once), 2 a cache number (9 entries x keys and values of 2 heads of 3),
    # 2 a state number (2 rows x 3 x 8, read and written), 4 a logit (2 rows x 10)
    weights = CONV + ATTN + DENSE + ROUTER + 2 * EXPERT + HEAD
    assert token_ppo_lfm2.decode_bytes(SMALL, 9) == 2 * (weights + 9 * 2 * 6 + 2 * 2 * 3 * 8) + 4 * 2 * 10
    untied = {**SMALL, "model": {**SMALL["model"], "tie_word_embeddings": False}}
    assert token_ppo_lfm2.decode_bytes(untied, 9) - token_ppo_lfm2.decode_bytes(SMALL, 9) == 2 * 2 * 8  # an embedding row a token


def test_train_step_counters_by_hand():
    rows, slots, ctx, steps = 2, 5, 7, 2  # minibatches of 2 sequences; 5 slots of which 5 - 3 are steps
    pairs = rows * slots * 2 * 2 / 4  # one expert layer, 2 a token, 2 of 4 held
    total = rows * slots * (CONV + ATTN + DENSE + ROUTER) + rows * slots * (slots + ctx) * SCORE + pairs * EXPERT + rows * steps * HEAD
    assert token_ppo_lfm2.train_step_flops(SMALL) == pytest.approx(3 * 2 * total)
    assert token_ppo_lfm2.train_step_flops(SMALL, held_pairs=0.0) == pytest.approx(3 * 2 * (total - pairs * EXPERT))
    # the prefill: one row of 3 slots, no context, no head
    assert token_ppo_lfm2.prefill_flops(SMALL, held_pairs=0.0) == pytest.approx(2 * (3 * (CONV + ATTN + DENSE + ROUTER) + 3 * 3 * SCORE))


def test_the_configurations_count_is_the_algorithms():
    config = loader.Cell(CELL).config
    assert config["model_flops_per_grad_step"] == token_ppo_lfm2.model_flops(config)
    # 469.3 M parameters held here, the embedding's rows counted once: the arithmetic of the cut
    from perfbench.references import token_ppo_lfm2 as reference

    assert sum(int(np.prod(shape)) for shape in reference._shapes(config["model"]).values()) == pytest.approx(469.3e6, rel=1e-3)
    assert config["layer_types"] == config["published"]["layer_types"] and len(config["layer_types"]) == 40  # the published list, whole
    kept = [config["layer_types"][i] for i in (0, 2, 3, 4, 5)]
    assert kept == config["model"]["layer_types"]  # the leading dense layer and the first whole period


def test_the_new_readers_on_a_made_up_trace(tmp_path, monkeypatch):
    """The four readers this configuration brought, on a trace whose times are
    known: none reads a share over 100% of what its count allows, and each
    returns nothing, without raising, where the run has nothing to read."""
    from perfbench import device_time

    ms = 1e6
    sync_ns = 5e9
    modules = [["jit_seqpol_decode(1)", 10 * ms, 4 * ms], ["jit_seqpol_train_step(2)", 100 * ms, 50 * ms]]
    ops = [["%a", 10 * ms, 4 * ms, "jit(seqpol_decode)/seqpol/conv/proj/dot"],
           ["%b", 100 * ms, 20 * ms, "jit(seqpol_train_step)/jvp(seqpol/conv/proj)/dot_general"],
           ["%c", 120 * ms, 10 * ms, "jit(seqpol_train_step)/transpose(jvp(seqpol/conv/mix))/mul"],
           ["%d", 130 * ms, 5 * ms, "jit(seqpol_train_step)/jvp(seqpol/conv)/add"],
           ["%e", 135 * ms, 15 * ms, "jit(seqpol_train_step)/jvp(seqpol/attn)/dot_general"]]  # fmt: skip
    monkeypatch.setattr(device_time, "load", lambda path: {"sync": [0.0, 0.0], "modules": modules, "ops": ops})
    trace = tmp_path / "trace" / "plugins" / "profile" / "t"
    trace.mkdir(parents=True)
    (trace / "host.xplane.pb").write_bytes(b"")

    class Watcher:  # the traced cycle runs from the rollout end 5 ms after the sync to the next, 300 ms after it
        sync = {"before_ns": sync_ns, "inside_ns": sync_ns}
        traced_from, cycle = 0, 1

    cell = loader.Cell(CELL)
    config = cell.config
    open_ns = int(sync_ns - 27e9)
    exit_ns = np.asarray([sync_ns + 5 * ms, sync_ns + 300 * ms])
    counters = [{"event": "counters", "name": "seqpol/update", "t_mono_ns": open_ns + int(i * 5e9), "gradient_steps": 8, "held_pairs": 8 * 49152.0,
                 "routed_pairs": 8 * 393216.0, "tokens_decoded": 256 * 128, "rows_prefilled": 40, "cache_positions": 256 * 128 * 900} for i in range(3)]  # fmt: skip
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    window = {"open_ns": open_ns, "close_ns": int(sync_ns + 300 * ms)}
    facts = dict(cell=cell, run_dir=str(tmp_path), watcher=Watcher(), peak=peak, telemetry_events=counters, window=window,
                 entry_ns=exit_ns - ms, exit_ns=exit_ns, stretch_ns=run.trace_stretch(Watcher(), exit_ns, window))  # fmt: skip
    made = run.RunFacts(**facts)
    readers = loader.layer_readers(cell)
    assert readers["train_step.conv_device_ms"](made) == pytest.approx(35.0)  # 20 + 10 + 5: the attention's 15 are not its
    assert readers["train_step.conv_mix_device_ms"](made) == pytest.approx(10.0)  # the gates and taps alone
    assert readers["player.decode_state_hbm_share"](made) == pytest.approx(100 * token_ppo_lfm2.decode_bytes(config, 128 * 900) / (0.004 * 819e9))
    cycle = (8 * token_ppo_lfm2.train_step_flops(config, 49152.0) + 256 * token_ppo_lfm2.decode_flops(config) + 5 * token_ppo_lfm2.prefill_flops(config))
    assert readers["loop.cycle_mfu"](made) == pytest.approx(100 * 2 * cycle / (10.0 * 197e12))
    assert all(0.0 < readers[name](made) < 100.0 for name in ("loop.cycle_mfu", "player.decode_state_hbm_share"))
    # a program that counts nothing and a run without a trace: nothing to read, nothing raised
    empty = run.RunFacts(**{**facts, "telemetry_events": [], "run_dir": str(tmp_path / "none")})
    assert all(readers[name](empty) is None for name in NEW)
