"""The window-and-full-attention token policy's cell, tiny on the CPU: its run
through the harness to a result line (``correct`` true; false under each
planted fault), the per-layer metrics that read the loop's counters, the three
new readers on a made-up trace, and the FLOP, byte and key counters of its
algorithm module against hand counts."""

import json

import numpy as np
import pytest

from perfbench import check_line, loader, run
from perfbench.algorithms import token_ppo_mellum2
from tests.test_algos.test_token_policy import ring_kept, window_ignored, yarn_left_out
from tests.test_perfbench import tiny
from tests.test_perfbench.test_token_ppo import _faulty
from tests.test_perfbench.test_token_ppo_lfm2 import _state_unchanged

CELL = "mellum2_12b_ep8.train"
NEW = {"train_step.attn_window_device_ms", "train_step.attn_window_mfu", "player.ring_wrapped_share"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("perfbench_mellum2_root")))


def test_a_traced_run_reaches_a_correct_result_line(root):
    line = json.loads(json.dumps(run.run_cell(CELL, 2**31 + 35, 1.5, True, root=root, require_tpu=False)))
    assert list(line)[:3] == ["correct", "attempted", "failed"] and list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    # the device metrics and the shares of a peak need a chip; the host's spans and the loop's counters are read on any machine
    assert {"compile.in_window", "env.step_share", "loop.env_interaction_ms", "loop.action_fetch_ms", "loop.env_step_host_ms", "loop.train_block_ms",
            "moe.held_pair_share", "moe.max_expert_load", "update.padding_share", "player.ring_wrapped_share"} <= set(line["metrics"])  # fmt: skip
    with open(f"{root}/BENCHMARK.json") as f:
        listed = {m["name"] for m in json.load(f)["per_layer"] if CELL in m["workloads"]}
    assert set(line["metrics"]) <= listed and len(listed) == 25 and NEW <= listed
    values = {k: v["value"] for k, v in line["metrics"].items()}
    assert values["compile.in_window"] == 0.0
    assert 0.0 < values["moe.held_pair_share"] < 100.0 and values["moe.max_expert_load"] >= 1.0
    assert 0.0 < values["update.padding_share"] < 100.0 and 0.0 < values["player.ring_wrapped_share"] <= 100.0
    assert set(line["compared"]) == set(tiny.rule("token_ppo_mellum2").LIMITS) and "player_window_logits" in line["compared"]
    # the line check knows this run for what it is: no chip, so no device metric, no peak and no busy time
    faults = check_line.faults(json.dumps(line), CELL, True, root)
    assert faults and all(f.startswith(("metrics.", "device.")) for f in faults), faults


#: what is planted underneath the harness -> the numbers that have to fail, and those that must not
PLANTED = {
    # the window layers of player and update attend to the whole episode: the rows past the window show it
    "window_ignored": (window_ignored, {"player_window_logits", "first_grad"}, set()),
    # the full layer rotated by the default table, in player and update
    "yarn_left_out": (yarn_left_out, {"player_logits", "player_window_logits", "first_grad"}, set()),
    # the decode behind a prefill reads what the episode before left in the rings and no prompt: the player's numbers fail, the update's do not
    "ring_kept": (ring_kept, {"player_logits", "player_reset_logits", "player_window_logits"}, {"first_grad", "change"}),
    # the player and the update both lack the expert
    "expert_left_out": (_faulty("expert_left_out"), {"player_logits", "first_grad"}, set()),
    # the player is sound, the update trains on half its minibatch
    "half_batch": (_faulty("half_batch"), {"first_grad", "grad_direction", "change"}, {"player_logits", "player_window_logits"}),
    # the first gradient is sound, the weights did not move: ``change`` reads 1
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
    assert line["compared"]["rollout_rows"]["value"] == 0 and line["compared"]["wraps_missing"]["value"] == 0


def test_the_calibration_judges_every_control_and_fault_not_correct(root, capsys):
    """``calibrate_token_ppo_mellum2.readings`` in ``verify``'s place: the program's numbers pass the cell's limits, and
    each control and fault, put through the same limits, comes out not correct by at least one."""
    from perfbench import calibrate_token_ppo_mellum2 as tool

    line = run.run_cell(CELL, 13, 0.5, False, root=root, require_tpu=False, verify=tool.readings)
    sides = {d["side"]: d for d in map(json.loads, (s for s in capsys.readouterr().out.splitlines() if s.startswith('{"side"')))}
    assert set(sides) == {"program", "state_unchanged", "verdict", *tool.SIDES}
    assert sides["program"]["correct"] is True and line["correct"] is True, sides["program"]
    for name in ("state_unchanged", *tool.SIDES):
        assert sides[name]["correct"] is False and sides[name]["fails_by"], sides[name]
    # the reference with the fault planted departs where the program with it does: a kept ring behind every prefill,
    # an ignored window on the rows that stand past it
    assert {"player_logits", "player_reset_logits", "player_window_logits"} <= set(sides["ring_kept"]["fails_by"])
    assert "player_window_logits" in sides["window_ignored"]["fails_by"] and "player_logits" in sides["yarn_left_out"]["fails_by"]
    assert not set(sides["half_batch"]) & {"player_logits", "player_window_logits"}  # a side is judged by the numbers it can move
    assert sides["verdict"] == {"side": "verdict", "program_correct": True, "controls_and_faults_that_pass": []}
    # a limit so wide that a fault passes it turns the run's own verdict
    assert tool.judged("window_ignored", {"player_window_logits": 0.5}, {"player_window_logits": 1.0, "change": 0.1})["correct"] is True


# --------------------------------------------------------------------------- #
# the FLOP, byte and key counters against hand counts
# --------------------------------------------------------------------------- #

SMALL = {"model": {"hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 3, "sliding_window": 4,
                   "layer_types": ["sliding_attention", "sliding_attention", "full_attention"], "moe_intermediate_size": 6, "n_routed_experts": 4,
                   "held_experts": [0, 1], "num_experts_per_tok": 2, "first_k_dense_replace": 0, "num_hidden_layers": 3,
                   "vocab_rows": 10, "context": 7, "prompt_max": 3},
         "algo": {"num_envs": 2, "batch_size": 2, "sequence_length": 5, "prefill_rows": 1}}  # fmt: skip
# per position and layer: the attention's W_q 8x12, W_k and W_v 8x6, W_o 12x8
ATTN = 8 * 12 + 2 * 8 * 6 + 12 * 8
SCORE = 2 * 4 * 3  # per query and key: 4 heads of 3, q k^T and the weighted values
ROUTER, EXPERT, HEAD = 8 * 4, 3 * 8 * 6, 8 * 10 + 8


def test_decode_counters_by_hand():
    # three attention layers, the full one scoring its 7 cache entries and each window layer its ring of 4; every layer's router and both held experts; the head
    per_row = 3 * ATTN + (7 + 2 * 4) * SCORE + 3 * (ROUTER + 2 * EXPERT) + HEAD
    assert token_ppo_mellum2.decode_flops(SMALL) == 2 * 2 * per_row
    # bytes: 2 a weight (an embedding row a token beside the head), 2 a cache number (keys and values of 2 heads of 3, in three layers,
    # at the counter's mean of 9 entries a layer and step), 4 a logit (2 rows x 10)
    weights = 3 * ATTN + 3 * (ROUTER + 2 * EXPERT) + HEAD + 2 * 8
    assert token_ppo_mellum2.decode_bytes(SMALL, 9) == 2 * (weights + 9 * 3 * 2 * 6) + 4 * 2 * 10


def test_train_step_counters_by_hand():
    rows, slots, steps = 2, 5, 2  # minibatches of 2 sequences; 5 slots of which 5 - 3 are steps
    pairs = rows * slots * 3 * 2 * 2 / 4  # three expert layers, 2 a token, 2 of 4 held
    keys = (5 + 7) + 2 * (5 + 4)  # a query scores its row's 5 slots and the full layer's 7 cache entries, or a window layer's ring of 4
    total = rows * slots * (3 * ATTN + 3 * ROUTER) + rows * slots * keys * SCORE + pairs * EXPERT + rows * steps * HEAD
    assert token_ppo_mellum2.train_step_flops(SMALL) == pytest.approx(3 * 2 * total)
    assert token_ppo_mellum2.train_step_flops(SMALL, held_pairs=0.0) == pytest.approx(3 * 2 * (total - pairs * EXPERT))
    # the prefill: one row of 3 slots, nothing behind it, no head
    assert token_ppo_mellum2.prefill_flops(SMALL, held_pairs=0.0) == pytest.approx(2 * (3 * (3 * ATTN + 3 * ROUTER) + 3 * 3 * 3 * SCORE))
    assert token_ppo_mellum2.scored_keys(SMALL) == 2 * 2 * 5 * (5 + 4)


def test_the_window_keys_by_hand():
    """``token_policy.window_keys`` (the counter) and ``window_flops`` (its FLOPs): a sequence that begins with a prompt of 3 and
    takes 4 steps has real queries at positions 0..5, a window of 4 gives them 1 + 2 + 3 + 4 + 4 + 4 keys; one that continues
    from position 6 for 2 steps 4 + 4; a padding sequence none; in each of the two window layers."""
    from sheeprl_tpu.algos.ppo_recurrent.token_policy import window_keys
    from tests.test_algos.test_token_policy import MELLUM2_SIZES, core

    seqs = {"n0": np.asarray([3, 1, 1]), "len0": np.asarray([0, 6, 0]), "mask": np.asarray([[1, 1, 1, 1, 0], [1, 1, 0, 0, 0], [0, 0, 0, 0, 0]], np.float32)}
    assert window_keys(seqs, core(MELLUM2_SIZES, sliding_window=4)) == 2 * (18 + 8)
    assert window_keys(seqs, core()) == 0  # a model without a window layer counts none
    assert token_ppo_mellum2.window_flops(SMALL, 52) == 3 * 2 * 52 * SCORE
    # at the cell's sizes a full window of keys for every slot is the most the counter can read, and the program scores more
    config = loader.Cell(CELL).config
    a, m = config["algo"], config["model"]
    most = 3 * a["batch_size"] * a["sequence_length"] * m["sliding_window"]
    assert most < token_ppo_mellum2.scored_keys(config) == 3 * 32 * a["sequence_length"] * (a["sequence_length"] + 1024)


def test_the_configurations_count_is_the_algorithms():
    config = loader.Cell(CELL).config
    assert config["model_flops_per_grad_step"] == token_ppo_mellum2.model_flops(config)
    # the arithmetic of the cut: 21.23 M of attention and 49.70 M of router and 8 held experts a layer, 28.31 M each of embedding and head
    from perfbench.references import token_ppo_mellum2 as reference

    shapes = reference._shapes(config["model"])
    count = lambda prefix: sum(int(np.prod(shape)) for path, shape in shapes.items() if path.startswith(prefix))  # noqa: E731
    assert count("layers/0/attn/") == 21_233_920 and count("layers/0/") == 70_931_200 and count("embed/") == count("head/") == 28_311_552
    assert count("") == 340_352_512 == 4 * 70_931_200 + 2 * 28_311_552 + 2_304 + 2_304
    published = config["published"]
    assert config["layer_types"] == published["layer_types"] and len(config["layer_types"]) == 28  # the published list, whole
    assert config["layer_types"][:4] == config["model"]["layer_types"]  # layers 0..3: one whole period
    # no width, window or rotary value departs from the published row
    for ours, theirs in {"hidden_size": "hidden_size", "num_attention_heads": "num_attention_heads", "num_key_value_heads": "num_key_value_heads",
                         "head_dim": "head_dim", "moe_intermediate_size": "moe_intermediate_size", "n_routed_experts": "num_experts",
                         "num_experts_per_tok": "num_experts_per_tok", "sliding_window": "sliding_window", "rms_norm_eps": "rms_norm_eps",
                         "intermediate_size": "intermediate_size", "norm_topk_prob": "norm_topk_prob"}.items():  # fmt: skip
        assert config["model"][ours] == published[theirs], ours
    assert config["model"]["rope_parameters"] == published["rope_parameters"] == config["rope_parameters"]
    assert {k for k in published if config[k] != published[k]} == set(config["reduced"])


def test_the_new_readers_on_a_made_up_trace(tmp_path, monkeypatch):
    """The three readers this configuration brought, and the two it lists itself under, on a trace whose times are
    known: no share reads over 100% of what its count allows, and each returns nothing, without raising, where the
    run has nothing to read."""
    from perfbench import device_time

    ms = 1e6
    sync_ns = 5e9
    modules = [["jit_seqpol_decode(1)", 10 * ms, 4 * ms], ["jit_seqpol_train_step(2)", 100 * ms, 1500 * ms]]
    ops = [["%a", 10 * ms, 4 * ms, "jit(seqpol_decode)/seqpol/attn/window/dot"],
           ["%b", 100 * ms, 300 * ms, "jit(seqpol_train_step)/jvp()/while/body/closed_call/seqpol/attn/window/closed_call/while/body/closed_call/checkpoint/dot_general"],
           ["%c", 400 * ms, 500 * ms, "jit(seqpol_train_step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/seqpol/attn/window/exp"],
           ["%d", 900 * ms, 200 * ms, "jit(seqpol_train_step)/jvp()/while/body/closed_call/seqpol/attn/dot_general"],
           ["%e", 1100 * ms, 100 * ms, "jit(seqpol_train_step)/jvp(seqpol/attn)/window/checkpoint/exp"],
           ["%f", 1200 * ms, 50 * ms, "jit(seqpol_train_step)/jvp()/seqpol/moe/experts/reduce_window/add"]]  # fmt: skip
    monkeypatch.setattr(device_time, "load", lambda path: {"sync": [0.0, 0.0], "modules": modules, "ops": ops})
    trace = tmp_path / "trace" / "plugins" / "profile" / "t"
    trace.mkdir(parents=True)
    (trace / "host.xplane.pb").write_bytes(b"")

    class Watcher:  # the traced cycle runs from the rollout end 5 ms after the sync to the next, 1.7 s after it
        sync = {"before_ns": sync_ns, "inside_ns": sync_ns}
        traced_from, cycle = 0, 1

    cell = loader.Cell(CELL)
    config = cell.config
    open_ns = int(sync_ns - 27e9)
    exit_ns = np.asarray([sync_ns + 5 * ms, sync_ns + 1700 * ms])
    keys = 3 * 60_000_000.0  # three gradient steps' worth, under the most the cell's shapes allow
    counters = [{"event": "counters", "name": "seqpol/update", "t_mono_ns": open_ns + int(i * 7e9), "gradient_steps": 3, "held_pairs": 3 * 188416.0,
                 "routed_pairs": 3 * 1507328.0, "tokens_decoded": 192 * 64, "rows_prefilled": 19, "cache_positions": 192 * 64 * 1100.5,
                 "window_keys": keys, "ring_wrapped_rows": 40 + i} for i in range(3)]  # fmt: skip
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    window = {"open_ns": open_ns, "close_ns": int(sync_ns + 1700 * ms)}
    facts = dict(cell=cell, run_dir=str(tmp_path), watcher=Watcher(), peak=peak, telemetry_events=counters, window=window,
                 entry_ns=exit_ns - ms, exit_ns=exit_ns, stretch_ns=run.trace_stretch(Watcher(), exit_ns, window))  # fmt: skip
    made = run.RunFacts(**facts)
    readers = loader.layer_readers(cell)
    assert readers["train_step.attn_window_device_ms"](made) == pytest.approx(900.0)  # 300 + 500 + 100: the projections' 200 and the experts' 50 are not its
    assert readers["train_step.attn_device_ms"](made) == pytest.approx(1100.0)  # the attention of both kinds, whole, by the unedited reader
    assert readers["train_step.attn_window_mfu"](made) == pytest.approx(100 * 3 * 2 * 60e6 * 2 * 32 * 128 / (0.9 * 197e12))
    assert readers["player.ring_wrapped_share"](made) == pytest.approx(100 * 41 / 64)
    assert readers["player.decode_state_hbm_share"](made) == pytest.approx(100 * token_ppo_mellum2.decode_bytes(config, 64 * 1100.5) / (0.004 * 819e9))
    cycle = 3 * token_ppo_mellum2.train_step_flops(config, 188416.0) + 192 * token_ppo_mellum2.decode_flops(config) + 19 / 8 * token_ppo_mellum2.prefill_flops(config)
    assert readers["loop.cycle_mfu"](made) == pytest.approx(100 * 2 * cycle / (14.0 * 197e12))
    assert all(0.0 < readers[name](made) < 100.0 for name in ("loop.cycle_mfu", "player.decode_state_hbm_share", "train_step.attn_window_mfu", "player.ring_wrapped_share"))
    # a program that counts nothing and a run without a trace (the parent commit under these files): nothing to read, nothing raised
    empty = run.RunFacts(**{**facts, "telemetry_events": [], "run_dir": str(tmp_path / "none")})
    assert all(readers[name](empty) is None for name in NEW)
    old = run.RunFacts(**{**facts, "telemetry_events": [{k: v for k, v in e.items() if k not in ("window_keys", "ring_wrapped_rows")} for e in counters]})
    assert readers["train_step.attn_window_mfu"](old) is None and readers["player.ring_wrapped_share"](old) is None
