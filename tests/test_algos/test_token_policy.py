"""The decoder core of recurrent PPO (a policy over tokens) against the plain
references, tiny on the CPU in float32, for the three models it runs: ``glm``
(the ratios of GLM-4.7-Flash: latent attention in every layer, 1 dense + 2
expert layers, 8 routed experts of which 4 a token, 1 shared, every latent and
head dim distinct, the multi-token-prediction module on), ``lfm2`` (the ratios
of LFM2-24B-A2B: a gated short convolution over the dense layer, then
grouped-query attention and a convolution over expert layers with no shared
expert, 2 query heads a key-value head, embedding and head tied) and
``mellum2`` (the ratios of Mellum2-12B-A2.5B: two sliding-window layers whose
state is a ring of 8 positions a row and a full-attention layer under the
published YaRN table, every layer over 8 experts of which 2 a token by a
softmax, no dense layer, no shared expert, embedding and head untied). Every
tolerance is float32 round-off (readings are 1e-6 or under) with room for the
order of sums; the same numbers computed with bfloat16 operands read 1e-2 and
fail each of them, which ``test_bfloat16_fails_the_tolerances`` holds.
"""

import contextlib
import dataclasses
import os
import signal
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.references import token_ppo as reference
from perfbench.references import token_ppo_lfm2 as reference_lfm2
from perfbench.references import token_ppo_mellum2 as reference_mellum2
from sheeprl_tpu.algos.ppo_recurrent import token_policy
from sheeprl_tpu.cli import run
from sheeprl_tpu.models import seqpol

SIZES = dict(hidden_size=32, num_attention_heads=2, q_lora_rank=12, kv_lora_rank=10, qk_nope_head_dim=6, qk_rope_head_dim=4, v_head_dim=8,
             intermediate_size=48, moe_intermediate_size=16, n_routed_experts=8, held_experts=[0, 1, 2, 3], num_experts_per_tok=4,
             n_shared_experts=1, routed_scaling_factor=1.8, norm_topk_prob=True, first_k_dense_replace=1, num_hidden_layers=3,
             num_nextn_predict_layers=1, vocab_rows=24, context=32, rope_theta=1e6, rms_norm_eps=1e-5)  # fmt: skip
LFM2_SIZES = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2, head_dim=6, conv_L_cache=3,
                  layer_types=["conv", "full_attention", "conv"], intermediate_size=48, moe_intermediate_size=16, n_routed_experts=8,
                  held_experts=[0, 1, 2, 3], num_experts_per_tok=4, n_shared_experts=0, routed_scaling_factor=1.0, norm_topk_prob=True,
                  router_eps=1e-6, first_k_dense_replace=1, num_hidden_layers=3, num_nextn_predict_layers=0, tie_word_embeddings=True,
                  vocab_rows=24, context=32, rope_theta=1e6, rms_norm_eps=1e-5)  # fmt: skip
ROPE_PARAMETERS = {"full_attention": {"rope_type": "yarn", "rope_theta": 500000.0, "factor": 16.0, "original_max_position_embeddings": 8192,
                                      "beta_fast": 32.0, "beta_slow": 1.0, "attention_factor": 1.2772588722239782},
                   "sliding_attention": {"rope_type": "default", "rope_theta": 500000.0}}  # fmt: skip
MELLUM2_SIZES = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2, head_dim=8, sliding_window=8,
                     layer_types=["sliding_attention", "sliding_attention", "full_attention"], rope_parameters=ROPE_PARAMETERS,
                     intermediate_size=48, moe_intermediate_size=16, n_routed_experts=8, held_experts=[0, 1, 2, 3], num_experts_per_tok=2,
                     n_shared_experts=0, routed_scaling_factor=1.0, norm_topk_prob=True, router_scoring="softmax", first_k_dense_replace=0,
                     num_hidden_layers=3, num_nextn_predict_layers=0, tie_word_embeddings=False, vocab_rows=24, context=32,
                     rms_norm_eps=1e-6)  # fmt: skip
#: float32 round-off at these widths reads 1e-6; a bfloat16 operand anywhere reads 1e-2
TOL = 2e-5
VOCAB, CONTEXT = SIZES["vocab_rows"], SIZES["context"]


def core(sizes=SIZES, **changes):
    return seqpol.config_from({**sizes, **changes})


class Model:
    """One of the three models: its tiny sizes, its plain reference and its seeded weights."""

    def __init__(self, name):
        self.name = name
        self.sizes, self.reference = {"glm": (SIZES, reference), "lfm2": (LFM2_SIZES, reference_lfm2), "mellum2": (MELLUM2_SIZES, reference_mellum2)}[name]
        self.weights = self.reference.init_weights({"model": self.sizes}, 3)
        self._forward = jax.jit(lambda w, tokens: self.reference.forward(w, self.sizes, tokens)[:2])
        self.mtp_coef = 0.1 if self.sizes["num_nextn_predict_layers"] else 0.0

    def core(self, **changes):
        return core(self.sizes, **changes)

    def forward(self, tokens, size=16):
        """The reference's full forward on ``tokens``, padded to one length (causal: the padding changes nothing before it)."""
        padded = np.zeros((size,), np.int32)
        padded[: len(tokens)] = tokens
        logits, values = self._forward(self.weights, padded)
        return logits[: len(tokens)], values[: len(tokens)]

    def losses(self, aligned, a):
        return self.reference.losses_only(self.weights, self.sizes, a, aligned)


@pytest.fixture(scope="module", params=["glm", "lfm2", "mellum2"])
def model(request):
    return Model(request.param)


@pytest.fixture(scope="module")
def weights():
    """``glm``'s weights, for the tests of the expert layer alone: it is one layer for both models."""
    return reference.init_weights({"model": SIZES}, 3)


def gap(ours, theirs):
    return float(jnp.linalg.norm(jnp.asarray(ours) - jnp.asarray(theirs)) / (jnp.linalg.norm(jnp.asarray(theirs)) + 1e-30))


def whole(weights, tokens, dtype=jnp.float32, cfg=None):
    cfg = cfg or core()
    tokens = np.atleast_2d(tokens)
    positions = np.broadcast_to(np.arange(tokens.shape[1]), tokens.shape)

    @jax.jit
    def run_whole(w):
        h, own, counters = seqpol.forward_sequence(w, cfg, tokens, positions, np.ones(tokens.shape, bool), dtype=dtype)
        return (*seqpol.heads(w, cfg, h), own, counters)

    return run_whole(weights)


@contextlib.contextmanager
def conv_state_kept():
    """A planted fault: the convolution state of the episode before survives a
    reset. No entry of it reads as zero for lying before the row's first
    position, and a prefill leaves it as it was (its own entries for that kind
    of state are none)."""
    real_before, real_op = seqpol.conv_in_episode, seqpol.OPERATORS[seqpol.CONV]

    def sequence(*args, **kwargs):
        out, (tail,) = real_op.sequence(*args, **kwargs)
        return out, (tail[:, :0],)

    seqpol.conv_in_episode = lambda positions, taps: jnp.ones((positions.shape[0], taps), bool)
    seqpol.OPERATORS[seqpol.CONV] = dataclasses.replace(real_op, sequence=sequence)
    try:
        yield
    finally:
        seqpol.conv_in_episode, seqpol.OPERATORS[seqpol.CONV] = real_before, real_op


@contextlib.contextmanager
def _config_changed(change):
    """While open, every decoder core is built from ``change(its configuration)``."""
    real = seqpol.config_from
    seqpol.config_from = lambda node: change(real(node))
    try:
        yield
    finally:
        seqpol.config_from = real


def window_ignored():
    """A planted fault: the window layers attend to the whole episode (a ring of ``context`` entries never wraps, and no key is a window behind its query)."""
    return _config_changed(lambda cfg: dataclasses.replace(cfg, sliding_window=cfg.context if cfg.sliding_window else None))


def yarn_left_out():
    """A planted fault: the full-attention layers are rotated by the window layers' default table, no attention factor."""
    return _config_changed(lambda cfg: dataclasses.replace(cfg, rope_parameters=tuple(
        (kind, cfg.rope(seqpol.SLIDING) if kind == seqpol.ATTENTION else table) for kind, table in cfg.rope_parameters or ())))  # fmt: skip


@contextlib.contextmanager
def ring_kept():
    """A planted fault: a reset leaves the episode before in a window layer's
    ring (every entry is seen, whatever position it holds), and a prefill
    does not overwrite it (its own entries for that state are none)."""
    real_seen, real_op = seqpol.ring_seen, seqpol.OPERATORS[seqpol.SLIDING]

    def sequence(p, cfg, *args, **kwargs):
        out, own = real_op.sequence(p, cfg, *args, **kwargs)
        return out, tuple(e[:, :0] for e in own)

    # a ring is the state that can wrap: a cache of ``context`` positions holds a row's every position
    seqpol.ring_seen = lambda positions, size: real_seen(positions, size) | (size < 16)
    seqpol.OPERATORS[seqpol.SLIDING] = dataclasses.replace(real_op, sequence=sequence)
    try:
        yield
    finally:
        seqpol.ring_seen, seqpol.OPERATORS[seqpol.SLIDING] = real_seen, real_op


@pytest.fixture(params=["blocks_as_they_are", "blocks_of_4"])
def query_blocks(request, monkeypatch):
    """The whole-sequence attention's blocks of queries as they are, or of 4 queries with no row scored in one block:
    the tiny rows then cross several blocks, a window layer's slices of its own keys clamped to the row, a last
    shorter block, and blocks that take the ring beside blocks that do not."""
    if request.param == "blocks_of_4":
        _blocks_of_4(monkeypatch)
    return request.param


def _blocks_of_4(monkeypatch):
    monkeypatch.setattr(seqpol, "QUERY_BLOCK", 4)
    monkeypatch.setattr(seqpol, "WHOLE_UP_TO", 0)


def test_the_programs_own_weights_have_the_references_tree(model):
    ours = seqpol.init_params(jax.random.PRNGKey(0), model.core())
    assert jax.tree.structure(ours) == jax.tree.structure(model.weights)
    assert [a.shape for a in jax.tree.leaves(ours)] == [b.shape for b in jax.tree.leaves(model.weights)]
    assert ("head" in ours) == (model.name != "lfm2")  # tied: the head is the embedding's rows
    assert ("bias" in ours["layers"]["1"]["moe"]["router"]) == (model.name != "mellum2")  # a softmax router has no correction bias


def test_every_leaf_resolves_under_the_partition_rules(model):
    """(fabric) kernel, embedding, scale and bias, stacked expert kernels and the depthwise kernel among them: no unmatched-leaf warning."""
    import warnings

    from sheeprl_tpu.parallel.fabric import Fabric, reset_partition_rule_warnings

    reset_partition_rule_warnings()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        specs = Fabric(devices=1, accelerator="cpu").match_partition_rules(model.weights)
    assert len(jax.tree.leaves(specs, is_leaf=lambda s: hasattr(s, "index"))) == len(jax.tree.leaves(model.weights))


# (a) the whole-sequence form against the reference's full forward
@pytest.mark.parametrize("grouped", [False, True], ids=["experts_dense", "experts_grouped"])
def test_whole_sequence_equals_the_reference(model, grouped):
    tokens = np.random.default_rng(0).integers(0, VOCAB, (2, 12)).astype(np.int32)
    logits, values, own, counters = whole(model.weights, tokens, cfg=model.core(dense_pairs_max=0 if grouped else 10**6))
    for b in range(2):
        ref_logits, ref_values = model.forward(tokens[b])
        assert gap(logits[b], ref_logits) < TOL and gap(values[b], ref_values) < TOL
    expert_layers = model.sizes["num_hidden_layers"] - model.sizes["first_k_dense_replace"]
    assert counters[0] == 2 * 12 * model.sizes["num_experts_per_tok"] * expert_layers and 0 < counters[1] < counters[0]  # pairs routed, and those on held experts
    # what each layer's operator declares of its state is what the rows' own entries fit into
    for entries, shapes in zip(own, seqpol.state_shapes(model.core(), 2)):
        assert [e.shape[2:] for e in entries] == [s[2:] for s in shapes] and all(e.shape[1] in (12, s[1]) for e, s in zip(entries, shapes))


def _play(model, steps=12, prompt=(2, 6)):
    """Prefill, then decoding through the state, row 1 reset in the middle to a
    new prompt and row 2 to a prompt of one token (which no prefill touches);
    prompts of ``prompt[0]`` to ``prompt[1]`` tokens; returns the worst gap of
    the logits and of the values to the reference's full forward from each
    episode's first token."""
    rng = np.random.default_rng(1)
    agent = token_policy.TokenPolicy(model.core(), prompt_max=prompt[1], dtype=jnp.float32)
    player = token_policy.TokenPlayer(agent, model.weights, num_envs=3, prefill_rows=2)
    key = jax.random.PRNGKey(0)
    inputs = [[], [], []]  # every token each row's policy has been fed, episode by episode
    obs_tokens, n_tokens = np.zeros((3, prompt[1]), np.int32), np.zeros((3,), np.int32)
    prefixes = []  # the tokens of each prompt that a prefill writes: all but its last

    def reset(row, n=None):
        n = int(rng.integers(prompt[0], prompt[1] + 1)) if n is None else n
        obs_tokens[row, :n], n_tokens[row] = rng.integers(0, VOCAB, n), n
        inputs[row] = [int(t) for t in obs_tokens[row, :n]]
        prefixes.append(n - 1)

    for row in range(3):
        reset(row)
    worst_logits = worst_values = 0.0
    for step in range(steps):
        actions, _, values, positions = player.act(obs_tokens, n_tokens, key, step)
        logits = np.asarray(player.last_logits)
        for row in range(3):
            assert positions[row] == len(inputs[row]) - 1
            ref_logits, ref_values = model.forward(inputs[row], size=prompt[1] + steps + 6)
            worst_logits = max(worst_logits, gap(logits[row], ref_logits[-1]))
            worst_values = max(worst_values, abs(float(values[row]) - float(ref_values[-1])) / max(1.0, abs(float(ref_values[-1]))))
        dones = np.zeros((3,), bool)
        dones[1], dones[2] = step == 3, step == 5  # rows 1 and 2 end in the middle: their next prompts go into the slots they leave
        player.reset_rows(dones)
        for row in range(3):
            if dones[row]:
                reset(row, 1 if row == 2 else None)
            else:
                obs_tokens[row, 0], n_tokens[row] = int(actions[row]), 1
                inputs[row].append(int(actions[row]))
    # the three first prompts in a call of two rows and a call of one, row 1's second in a third of one; row 2's
    # second has no prefix
    assert player.rows_prefilled == 4 and player.tokens_decoded == 3 * steps
    assert (player.prefill_calls, player.prefill_slots, player.prefill_tokens) == (3, 4 * prompt[1], sum(prefixes))
    return worst_logits, worst_values


# (b) prefill, then decoding through both kinds of state, rows reset in the middle, against the reference's full forward
def test_prefill_then_decode_across_a_reset_equals_the_reference(model):
    worst_logits, worst_values = _play(model)
    assert worst_logits < TOL and worst_values < TOL


@pytest.mark.parametrize("resetting", [1, 2, 3, 5, 8])
def test_a_prefill_sized_to_the_rows_that_reset_leaves_what_one_full_call_leaves(model, resetting):
    """With ``prefill_rows`` 8, ``resetting`` of 10 rows that hold an episode each take a new prompt: the calls sized to
    them leave every array of the state (a latent or a key-value cache, a ring, a convolution state) as one call of 8
    rows leaves it, the next decode's logits with it, and compile nothing after the player was built."""
    from sheeprl_tpu.obs.recompile import CompileWatchdog

    rng = np.random.default_rng(resetting)
    E, P = 10, 6
    agent = token_policy.TokenPolicy(model.core(), prompt_max=P, dtype=jnp.float32)
    player = token_policy.TokenPlayer(agent, model.weights, num_envs=E, prefill_rows=8)
    assert player.rungs == (1, 2, 4, 8)
    player.snapshot()  # compiled here: the loop takes its first at an update's head
    compiled = []
    dog = CompileWatchdog(lambda kind, **fields: compiled.append(fields.get("name")))
    dog.start()
    try:
        player.prefill(rng.integers(0, VOCAB, (E, P)).astype(np.int32), rng.integers(2, P + 1, E).astype(np.int32))  # 8 rows, then 2
        rows = np.sort(rng.choice(E, resetting, replace=False))
        tokens, n_tokens = rng.integers(0, VOCAB, (E, P)).astype(np.int32), np.ones((E,), np.int32)
        n_tokens[rows] = rng.integers(2, P + 1, resetting)
        before = player.snapshot()
        calls = player.prefill_calls
        player.prefill(tokens, n_tokens)
    finally:
        dog.stop()
    assert dog.compiles == 0, compiled
    rung = next(r for r in player.rungs if r >= resetting)
    assert (player.prefill_calls - calls, player.rows_prefilled) == (1, E + resetting)
    assert player.prefill_slots == (8 + 2 + rung) * P
    idx = np.full((8,), E, np.int32)
    idx[:resetting] = rows
    toks, n_prefix = np.zeros((8, P), np.int32), np.zeros((8,), np.int32)
    toks[:resetting], n_prefix[:resetting] = tokens[rows], n_tokens[rows] - 1
    full, _ = player._prefill(player.params, before, idx, toks, n_prefix)
    for sized_layer, full_layer in zip(player.state, full):
        for sized, whole in zip(sized_layer, full_layer):
            assert gap(sized, whole) < TOL
    current = tokens[np.arange(E), n_tokens - 1]
    outs = [player._decode(player.params, state, current, player.lengths.copy(), jax.random.PRNGKey(0), np.uint32(0)) for state in (player.snapshot(), full)]
    assert gap(outs[0][3], outs[1][3]) < TOL


def test_a_prompt_longer_than_the_window_is_prefilled_then_decoded():
    """Prompts of 9 to 12 tokens behind a window of 8: the prefill leaves the ring wrapped, in ring order, and the
    decodes behind it, across a reset and past a second wrap, read it as the reference's banded forward reads the episode."""
    worst_logits, worst_values = _play(Model("mellum2"), prompt=(9, 12))
    assert worst_logits < TOL and worst_values < TOL


@pytest.mark.parametrize("fault, prompt", [(window_ignored, (2, 6)), (yarn_left_out, (2, 6)), (ring_kept, (2, 6)), (ring_kept, (9, 12))],
                         ids=["window_ignored", "yarn_left_out", "ring_kept", "ring_kept-long_prompts"])  # fmt: skip
def test_a_planted_fault_of_the_window_layers_is_seen(fault, prompt):
    with fault():
        worst_logits, _ = _play(Model("mellum2"), prompt=prompt)
    assert worst_logits > 1000 * TOL


def test_a_convolution_state_that_survives_a_reset_is_seen():
    with conv_state_kept():
        worst_logits, _ = _play(Model("lfm2"))
    assert worst_logits > 1000 * TOL


def test_the_convolution_token_by_token_equals_the_whole_sequence_form():
    """One token at a time through its state, the whole rows at once, and rows that continue from a state: one result."""
    cfg, p = core(LFM2_SIZES), reference_lfm2.init_weights({"model": LFM2_SIZES}, 3)["layers"]["0"]["conv"]
    B, S, D, L = 2, 9, LFM2_SIZES["hidden_size"], LFM2_SIZES["conv_L_cache"]
    x = jax.random.normal(jax.random.PRNGKey(4), (B, S, D))
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    out, (tail,) = seqpol.conv_sequence(p, cfg, x, positions, jnp.ones((B, S), bool))
    state = (jnp.full((B, L, D), 7.0),)  # what an episode before left: position 0 starts from nothing all the same
    for t in range(S):
        step, state = seqpol.conv_decode(p, cfg, x[:, t], jnp.full((B,), t), state)
        assert gap(step, out[:, t]) < TOL, t
        if t == 4:
            halfway = state
    assert gap(state[0], tail) < TOL  # what a prefill of these rows leaves behind is what decoding them leaves
    # the rows' last 4 positions again, swapped: the second continues row 0 of the state after 5 positions, the first
    # begins there, from nothing, whatever row 1 of the state holds
    valid = jnp.ones((B, S - 5), bool)
    later, _ = seqpol.conv_sequence(p, cfg, x[::-1, 5:], positions[:, 5:], valid, ((halfway[0],), jnp.asarray([1, 0]), jnp.asarray([0, 5])))
    assert gap(later[1], out[0, 5:]) < TOL
    alone, _ = seqpol.conv_sequence(p, cfg, x[1:, 5:], positions[:1, 5:], valid[:1])
    assert gap(later[0], alone[0]) < TOL


def test_the_window_layer_token_by_token_through_its_ring_equals_the_whole_sequence_form(query_blocks):
    """One token at a time through the ring past two wraps, the whole rows at once under the band, the reference's
    banded attention, and rows that continue from a wrapped ring: one result."""
    cfg, p = core(MELLUM2_SIZES), reference_mellum2.init_weights({"model": MELLUM2_SIZES}, 3)["layers"]["0"]["attn"]
    B, S, D, W = 2, 20, MELLUM2_SIZES["hidden_size"], MELLUM2_SIZES["sliding_window"]
    x = jax.random.normal(jax.random.PRNGKey(4), (B, S, D))
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    out, ring = seqpol.gqa_sequence(p, cfg, x, positions, jnp.ones((B, S), bool), kind=seqpol.SLIDING)
    for b in range(B):
        assert gap(out[b], reference_mellum2.attention(p, MELLUM2_SIZES, x[b], positions[b], seqpol.SLIDING)[0]) < TOL
    state = tuple(jnp.full((B, W, ring[0].shape[-1]), 7.0) for _ in ring)  # what an episode before left: unseen all the same
    for t in range(S):
        step, state = seqpol.gqa_decode(p, cfg, x[:, t], jnp.full((B,), t), state, kind=seqpol.SLIDING)
        assert gap(step, out[:, t]) < TOL, t
        if t == 10:
            halfway = state  # 11 positions: the ring has wrapped, entries 0..2 hold positions 8..10
    for held, left in zip(state, ring):
        assert held.shape == left.shape == (B, W, 16) and gap(held, left) < TOL  # what a prefill of these rows leaves is what decoding them leaves
    assert seqpol.ring_positions(jnp.asarray([10, 3, -1]), W).tolist() == [[8, 9, 10, 3, 4, 5, 6, 7], [0, 1, 2, 3, -4, -3, -2, -1], [-8, -7, -6, -5, -4, -3, -2, -1]]
    # the rows' last 9 positions again, swapped: the second continues row 0 of the ring after 11 positions and stops
    # seeing its entries one by one, the first begins there, from nothing, whatever row 1 of the ring holds
    valid = jnp.ones((B, S - 11), bool)
    alone, _ = seqpol.gqa_sequence(p, cfg, x[1:, 11:], positions[:1, 11:] - 11, valid[:1], kind=seqpol.SLIDING)
    later = jnp.stack([positions[0, 11:] - 11, positions[1, 11:]])
    begun, _ = seqpol.gqa_sequence(p, cfg, x[::-1, 11:], later, valid, (halfway, jnp.asarray([1, 0]), jnp.asarray([0, 11])), kind=seqpol.SLIDING)
    assert gap(begun[0], alone[0]) < TOL and gap(begun[1], out[0, 11:]) < TOL
    if query_blocks == "blocks_of_4":  # positions 11 to 17 see the ring's newest entry, 10: the blocks of 4 and 4 take it, the last of 1 does not
        reach = seqpol.ring_reach(later, valid, jnp.asarray([0, 11]), W)
        assert [bool(reach[:, at : at + 4].any()) for at in (0, 4, 8)] == [True, True, False]


@pytest.mark.parametrize("S, blocks", [(18, "whole: a short row that is no multiple"), (24, "6 blocks"), (38, "9 blocks and a last one of 2")])
def test_the_query_blocks_give_what_one_block_gives(monkeypatch, S, blocks):
    """Rows of a length that is no multiple of the block, past ``WHOLE_UP_TO``, are scored in whole blocks and a last shorter one."""
    cfg, p = core(MELLUM2_SIZES, context=64), reference_mellum2.init_weights({"model": MELLUM2_SIZES}, 3)["layers"]["0"]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(7), (2, S, MELLUM2_SIZES["hidden_size"]))
    positions, valid = jnp.broadcast_to(jnp.arange(S), (2, S)), jnp.ones((2, S), bool)
    want = [seqpol.gqa_sequence(p, cfg, x, positions, valid, kind=kind)[0] for kind in (seqpol.SLIDING, seqpol.ATTENTION)]
    monkeypatch.setattr(seqpol, "QUERY_BLOCK", 4)
    monkeypatch.setattr(seqpol, "WHOLE_UP_TO", 20)
    calls, real = [], jax.lax.map
    monkeypatch.setattr(seqpol.lax, "map", lambda f, xs: calls.append(jax.tree.leaves(xs)[0].shape[0]) or real(f, xs))
    for kind, one in zip((seqpol.SLIDING, seqpol.ATTENTION), want):
        assert gap(seqpol.gqa_sequence(p, cfg, x, positions, valid, kind=kind)[0], one) < TOL
    assert calls == {18: [], 24: [6, 6], 38: [9, 9]}[S]


def _banded_whole(p, cfg, x, positions, valid, ctx):
    """A window layer over whole rows as one masked computation: every query against every key a row holds, the
    ring's entries (position ``newest - ((newest - j) mod W)`` at entry ``j``, the first ``length`` of them) and the
    row's own, each seen where it exists, lies at or before the query's slot and inside its band."""
    B, S, _ = x.shape
    H, G, hd, W = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim, cfg.sliding_window
    state, row, length = ctx
    q, k, v = seqpol._gqa_qkv(p, cfg, x, positions, cfg.rope(seqpol.SLIDING))
    keys = jnp.concatenate([state[0][row].reshape(B, W, G, hd), k], axis=1)
    values = jnp.concatenate([state[1][row].reshape(B, W, G, hd), v], axis=1)
    newest = (length - 1)[:, None]
    key_position = jnp.concatenate([newest - jnp.mod(newest - jnp.arange(W)[None, :], W), positions], axis=1)
    key_ok = jnp.concatenate([jnp.arange(W)[None, :] < length[:, None], valid], axis=1)
    key_slot = jnp.concatenate([jnp.full((B, W), -1), jnp.broadcast_to(jnp.arange(S), (B, S))], axis=1)
    seen = key_ok[:, None, :] & (key_slot[:, None, :] <= jnp.arange(S)[None, :, None]) & (positions[:, :, None] - key_position[:, None, :] < W)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q.reshape(B, S, G, H // G, hd), keys) * hd**-0.5
    w = jax.nn.softmax(jnp.where(seen[:, None, None], scores, -1e30), axis=-1)
    return jnp.einsum("bgrqk,bkgd->bqgrd", w, values).reshape(B, S, H * hd) @ p["o"]["kernel"]


def test_the_window_layer_scores_its_band_as_the_whole_rows_masked_compute():
    """Rows of 384 slots in three blocks of 128 queries behind a window of 8: a row that begins (``length`` 0), a row
    that continues a ring not yet wrapped (5 positions) and a row that continues a wrapped one (20), their real slots
    from 0, 130 and 256 on. The first block takes no ring, the second and third do. The output at every real query and
    the gradients of the real queries' outputs with respect to the inputs, the ring and the layer's parameters are
    the whole rows' masked computation's."""
    cfg, p = core(MELLUM2_SIZES), reference_mellum2.init_weights({"model": MELLUM2_SIZES}, 3)["layers"]["0"]["attn"]
    B, S, D, W = 3, 384, MELLUM2_SIZES["hidden_size"], MELLUM2_SIZES["sliding_window"]
    assert S == 3 * seqpol.QUERY_BLOCK and S > W + seqpol.QUERY_BLOCK
    keys = jax.random.split(jax.random.PRNGKey(9), 4)
    x = jax.random.normal(keys[0], (B, S, D))
    first, length = jnp.asarray([0, 130, 256]), jnp.asarray([0, 5, 20])
    slot = jnp.arange(S)[None, :]
    valid = (slot >= first[:, None]) & ((slot < 300) | (jnp.arange(B) > 0)[:, None])  # the row that begins ends at slot 299
    positions = jnp.maximum(length[:, None] + slot - first[:, None], 0)
    state = tuple(jax.random.normal(kk, (B, W, 16)) for kk in keys[1:3])  # entries past a ring's length hold what they may
    row = jnp.asarray([2, 0, 1])
    ct = jnp.where(valid[..., None], jax.random.normal(keys[3], (B, S, D)), 0)
    reach = seqpol.ring_reach(positions, valid, length, W)
    assert [bool(reach[:, at : at + 128].any()) for at in (0, 128, 256)] == [False, True, True]

    def loss(form):
        return jax.jit(jax.value_and_grad(lambda p, x, state: jnp.sum(form(p, x, state) * ct), argnums=(0, 1, 2)))

    ours = loss(lambda p, x, state: seqpol.gqa_sequence(p, cfg, x, positions, valid, (state, row, length), kind=seqpol.SLIDING)[0])
    theirs = loss(lambda p, x, state: _banded_whole(p, cfg, x, positions, valid, (state, row, length)))
    out = seqpol.gqa_sequence(p, cfg, x, positions, valid, (state, row, length), kind=seqpol.SLIDING)[0]
    assert gap(out[valid], _banded_whole(p, cfg, x, positions, valid, (state, row, length))[valid]) < 1e-5
    (value, grads), (want, want_grads) = ours(p, x, state), theirs(p, x, state)
    assert abs(float(value) - float(want)) < 1e-5 * abs(float(want))
    gaps = jax.tree.map(gap, grads, want_grads)
    assert max(jax.tree.leaves(gaps)) < 1e-5, gaps


def _primitives(jaxpr, found=None):
    """The names of every primitive in ``jaxpr`` and in the jaxprs inside its equations."""
    found = set() if found is None else found
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _primitives(inner, found)
    return found


def test_the_band_path_is_the_window_layers_alone(monkeypatch):
    """A slice at a block's own slot and a choice of form by ``lax.cond`` are in the window layer's jaxpr, forward and
    backward, and in neither of the other attention forms' with a context (full-attention grouped-query and latent
    attention), whose rows cross blocks of 4 here as the window layer's do."""
    _blocks_of_4(monkeypatch)
    B, S = 2, 12
    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, 32))
    positions = jnp.broadcast_to(jnp.arange(S), (B, S)) + jnp.asarray([[0], [5]])
    valid = jnp.ones((B, S), bool)
    band = {"dynamic_slice", "dynamic_update_slice", "cond"}
    for sizes, weights, layer, form in ((MELLUM2_SIZES, reference_mellum2, 0, partial(seqpol.gqa_sequence, kind=seqpol.SLIDING)),
                                        (LFM2_SIZES, reference_lfm2, 1, seqpol.gqa_sequence), (SIZES, reference, 1, seqpol.mla_sequence)):  # fmt: skip
        cfg, p = core(sizes), weights.init_weights({"model": sizes}, 3)["layers"][str(layer)]["attn"]
        ctx = (tuple(jnp.ones(shape) for shape in seqpol.state_shapes(cfg, B)[layer]), jnp.asarray([1, 0]), jnp.asarray([0, 5]))
        forward = _primitives(jax.make_jaxpr(lambda x: form(p, cfg, x, positions, valid, ctx)[0])(x).jaxpr)
        backward = _primitives(jax.make_jaxpr(jax.grad(lambda x: jnp.sum(form(p, cfg, x, positions, valid, ctx)[0] ** 2)))(x).jaxpr)
        if sizes is MELLUM2_SIZES:
            assert {"dynamic_slice", "cond"} <= forward and band <= backward
        else:
            assert not band & (forward | backward), band & (forward | backward)


def test_yarns_table_against_a_hand_computed_case():
    """The published table at a head of 128: ``c(32) = 18.08`` and ``c(1) = 34.98`` dims turn 32 times and once over
    the original 8,192 positions, so dims under 18 keep their frequency, dims from 35 on have it divided by 16."""
    table = core(MELLUM2_SIZES).rope(seqpol.ATTENTION)
    inv_freq, factor = seqpol.rope_frequencies(table, 128)
    assert factor == 1.2772588722239782 == pytest.approx(0.1 * np.log(16.0) + 1.0)
    want = {0: 1.0, 18: 0.024955408670558694, 19: 0.019208015577607825, 35: 4.7781061769823416e-05, 63: 1.5344629944572555e-07}
    for i, value in want.items():  # 19: the ramp's first step, f (1 - 1/17) + f / 16 / 17 with f = 500000^(-19/64)
        assert float(inv_freq[i]) == pytest.approx(value, rel=2e-6), i
    ours, theirs = seqpol.rope_frequencies(table, 128)[0], reference_mellum2.rotary_table(ROPE_PARAMETERS["full_attention"], 128)[0]
    assert gap(ours, theirs) < 1e-6
    plain, _ = seqpol.rope_frequencies(core(MELLUM2_SIZES).rope(seqpol.SLIDING), 128)
    assert float(plain[63]) == pytest.approx(16 * 1.5344629944572555e-07, rel=2e-6) and seqpol.rope_frequencies(core().rope(seqpol.LATENT), 64)[1] == 1.0
    # cosine and sine both carry the factor: position 0 scales, and a score of two rotated vectors carries its square
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 128))
    assert gap(seqpol.rope(x, jnp.zeros((3,)), table), factor * x) < 1e-6
    a, b = seqpol.rope(x, jnp.asarray([5, 5, 5]), table), seqpol.rope(x, jnp.asarray([5, 5, 5]), dataclasses.replace(table, attention_factor=1.0))
    assert gap((a * a).sum(-1), factor**2 * (b * b).sum(-1)) < 1e-5


def test_the_softmax_router_equals_the_reference():
    layer = reference_mellum2.init_weights({"model": MELLUM2_SIZES}, 3)["layers"]["1"]["moe"]
    cfg = core(MELLUM2_SIZES)
    x = jax.random.normal(jax.random.PRNGKey(6), (40, MELLUM2_SIZES["hidden_size"]))
    chosen, weights = seqpol.route(layer, cfg, x)
    score = jax.nn.softmax(x @ layer["router"]["kernel"], -1)
    assert chosen.shape == (40, 2) and np.allclose(np.asarray(weights).sum(-1), 1.0, atol=1e-6)  # the chosen scores over their sum, no scaling
    assert np.array_equal(np.sort(np.asarray(chosen), -1), np.sort(np.asarray(jax.lax.top_k(score, 2)[1]), -1)) and "bias" not in layer["router"]
    for grouped in (False, True):
        y, counters = seqpol.moe(layer, core(MELLUM2_SIZES, dense_pairs_max=0 if grouped else 10**6), x)
        assert gap(y, reference_mellum2.expert_layer(layer, MELLUM2_SIZES, x)) < TOL and counters[0] == 80


# (c) the shares add up: an uncut layer of 8 experts in shares of 2 (with a shared expert, counted once) and of 1 (with none)
def test_the_shares_of_a_layer_add_up_to_the_uncut_reference(model):
    everything = {**model.sizes, "held_experts": list(range(8))}
    uncut = model.reference.init_weights({"model": everything}, 5)["layers"]["1"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(2), (20, SIZES["hidden_size"]))
    want = model.reference.expert_layer(uncut, everything, x)
    shared = seqpol.swiglu(uncut["shared"], x) if "shared" in uncut else jnp.zeros_like(x)
    assert ("shared" in uncut) == (model.name == "glm")
    routed = jnp.zeros_like(x)
    shares = seqpol.held_shares(model.core(), 2 if model.name == "glm" else 1)
    assert len(shares) == (4 if model.name == "glm" else 8)
    for held in shares:
        share = {**uncut, "experts": {k: {"kernel": uncut["experts"][k]["kernel"][np.asarray(held)]} for k in ("gate", "up", "down")}}
        y, counters = seqpol.moe(share, model.core(held_experts=held), x)
        routed = routed + (y - shared)  # what this share's own experts gave: the shared expert is counted once, below
    assert gap(shared + routed, want) < TOL
    assert gap(seqpol.moe(uncut, model.core(held_experts=tuple(range(8))), x)[0], want) < TOL


# (f) routing under a planted imbalance drops nothing and computes nothing wrong
@pytest.mark.parametrize("planted", ["all_to_one_held_expert", "none_to_any_held_expert"])
@pytest.mark.parametrize("grouped", [False, True], ids=["experts_dense", "experts_grouped"])
def test_routing_under_a_planted_imbalance(weights, planted, grouped):
    layer = jax.tree.map(lambda a: a, weights["layers"]["1"]["moe"])
    bias = np.zeros((8,), np.float32)
    # the correction bias decides the choice alone: expert 2 (held) and three absent ones, or the four absent ones
    bias[[2, 5, 6, 7] if planted == "all_to_one_held_expert" else [4, 5, 6, 7]] = 10.0
    layer["router"] = {**layer["router"], "bias": jnp.asarray(bias)}
    x = jax.random.normal(jax.random.PRNGKey(3), (40, SIZES["hidden_size"]))
    y, counters = seqpol.moe(layer, core(dense_pairs_max=0 if grouped else 10**6), x)
    assert gap(y, reference.expert_layer(layer, SIZES, x)) < TOL
    held_pairs = 40 if planted == "all_to_one_held_expert" else 0
    assert seqpol.counters_of(counters).tolist() == [160.0, held_pairs, held_pairs]  # every pair on the held expert is counted, none dropped


def _ragged_dot_as_on_the_chip():
    """On the chip ``lax.ragged_dot`` leaves the rows that fall in no group (pairs
    on absent experts) as the memory held them, forward and backward: NaN here."""
    from jax import lax

    real = lax.ragged_dot

    @jax.custom_vjp
    def as_on_the_chip(rows, kernel, sizes):
        return jnp.where((jnp.arange(rows.shape[0]) < sizes.sum())[:, None], real(rows, kernel, sizes), jnp.nan)

    def fwd(rows, kernel, sizes):
        return as_on_the_chip(rows, kernel, sizes), (rows, kernel, sizes)

    def bwd(kept, ct):
        rows, kernel, sizes = kept
        d_rows, d_kernel = jax.vjp(lambda r, k: real(r, k, sizes), rows, kernel)[1](ct)
        return jnp.where((jnp.arange(rows.shape[0]) < sizes.sum())[:, None], d_rows, jnp.nan), d_kernel, None

    as_on_the_chip.defvjp(fwd, bwd)
    return as_on_the_chip


# the grouped products run over the first rows of the sorted buffer when the held pairs fit in them (40 of 160
# here: 0.5 times the even share), over all of it when they do not, and over all of it always at a factor that
# covers every pair; with NaN in the rows of no group the layer's output and gradient stay what they are
@pytest.mark.parametrize("factor, planted, held_pairs", [(2.0, False, None), (0.5, True, 40), (0.5, False, None)],
                         ids=["whole_buffer", "first_rows_hold_every_held_pair", "held_pairs_do_not_fit"])  # fmt: skip
def test_rows_in_no_group_may_hold_anything(weights, monkeypatch, factor, planted, held_pairs):
    layer = dict(weights["layers"]["1"]["moe"])
    if planted:  # the correction bias decides the choice alone: expert 2 (held) and three absent ones
        layer["router"] = {**layer["router"], "bias": jnp.zeros((8,)).at[jnp.asarray([2, 5, 6, 7])].set(10.0)}
    cfg = core(dense_pairs_max=0)
    monkeypatch.setattr(seqpol, "GROUPED_ROWS_FACTOR", factor)
    assert seqpol.grouped_rows(cfg, 160) == (160 if factor == 2.0 else 40)
    x = jax.random.normal(jax.random.PRNGKey(5), (40, SIZES["hidden_size"]))
    counters = seqpol.counters_of(seqpol.moe(layer, cfg, x)[1])
    assert float(counters[1]) == held_pairs if planted else float(counters[1]) > 40  # which side of the 40 rows the pairs fall
    loss = lambda p, x, cfg: jnp.sum(jnp.square(seqpol.moe(p, cfg, x)[0]))  # noqa: E731
    want = jax.grad(loss, argnums=(0, 1))(layer, x, core(dense_pairs_max=10**6))
    assert gap(seqpol.moe(layer, cfg, x)[0], reference.expert_layer(layer, SIZES, x)) < TOL
    monkeypatch.setattr(seqpol.lax, "ragged_dot", _ragged_dot_as_on_the_chip())
    assert gap(seqpol.moe(layer, cfg, x)[0], reference.expert_layer(layer, SIZES, x)) < TOL
    got = jax.grad(loss, argnums=(0, 1))(layer, x, cfg)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.isfinite(a).all()) and gap(a, b) < TOL


def _batch(model, rng, agent, continuing, dtype=jnp.float32, lengths=(3, 7, 5, 4)):
    """One minibatch: an episode that begins in the rollout, one that
    continues from a snapshot (or begins too), and a padding sequence; the
    snapshot of two rows; and the reference's aligned form of the same.
    ``lengths``: the second episode's prompt and actions, and where it
    continues, how many of its inputs lie before the rollout and how many
    steps in it."""
    P, L = agent.prompt_max, 8
    pad = lambda a, n, dtype=np.float32: np.concatenate([np.asarray(a, dtype), np.zeros((n - len(a),), dtype)])  # noqa: E731
    noise = lambda n: rng.normal(size=n).astype(np.float32)  # noqa: E731
    prompt_a, acts_a = rng.integers(0, VOCAB, 4), rng.integers(0, VOCAB, 5)
    prompt_b, acts_b = rng.integers(0, VOCAB, lengths[0]), rng.integers(0, VOCAB, lengths[1])
    inputs_b = np.concatenate([prompt_b, acts_b[:-1]])
    before = lengths[2] if continuing else 0  # episode b's inputs that lie before the rollout: in the snapshot, not in the sequence
    # row 0 of the snapshot holds what an episode before left (a row that begins must not read it), row 1 episode b so far
    snap = jax.tree.map(lambda shape: jnp.full(shape, 3.0, dtype), seqpol.state_shapes(agent.core, 2), is_leaf=lambda s: isinstance(s[0], int))
    if continuing:
        own = whole(model.weights, inputs_b[:before], cfg=agent.core)[2]
        snap = jax.tree.map(lambda held, new: held.at[1, : new.shape[1]].set(new[0].astype(dtype)), snap, own)
    seq_a = dict(actions=acts_a, logprobs=-3 + 0.1 * noise(5), advantages=noise(5), returns=noise(5), values=noise(5))
    n_b = lengths[3] if continuing else lengths[1]
    seq_b = dict(actions=acts_b[-n_b:], logprobs=-3 + 0.1 * noise(n_b), advantages=noise(n_b), returns=noise(n_b), values=noise(n_b))
    first_b = len(inputs_b) - n_b
    batch = {
        "prompt": np.stack([pad(prompt_a, P, np.int32), pad(inputs_b[before : first_b + 1], P, np.int32), np.zeros(P, np.int32)]),
        "n0": np.asarray([4, first_b + 1 - before, 1], np.int32),
        "tok_in": np.stack([pad(np.concatenate([prompt_a[-1:], acts_a[:-1]]), L, np.int32), pad(inputs_b[first_b:], L, np.int32), np.zeros(L, np.int32)]),
        "len0": np.asarray([0, before, 0], np.int32),
        "env0": np.asarray([0, 1, 0], np.int32),
        "mask": np.stack([pad(np.ones(5), L), pad(np.ones(n_b), L), np.zeros(L, np.float32)]),
    }
    for k in seq_a:
        kind = np.int32 if k == "actions" else np.float32
        batch[k] = np.stack([pad(seq_a[k], L, kind), pad(seq_b[k], L, kind), np.zeros(L, kind)])

    def aligned(tokens, first, seq, size=max(12, len(inputs_b) + 1)):
        out = {"tokens": pad(tokens, size, np.int32), "steps": np.zeros(size, np.float32)}
        n = len(seq["actions"])
        out["steps"][first : first + n] = 1
        for k, v in seq.items():
            out[k] = np.zeros(size, np.int32 if k == "actions" else np.float32)
            out[k][first : first + n] = v
        return out

    return batch, snap, [aligned(np.concatenate([prompt_a, acts_a[:-1]]), 3, seq_a), aligned(inputs_b, first_b, seq_b)]


CONSTS = dict(clip_coef=0.2, vf_coef=0.2, ent_coef=0.001, lr=3e-4, eps=1e-4, weight_decay=0.01, max_grad_norm=0.5)
LOSS_NAMES = ("policy_loss", "value_loss", "entropy_loss", "mtp_loss")


def _program_loss(weights, agent, batch, snap, mtp_coef):
    def loss(p):
        return token_policy.token_loss(p, agent, batch, snap, 0.2, 0.001, vf_coef=0.2, mtp_coef=mtp_coef)

    (_, metrics), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(weights)
    return dict(zip(token_policy.METRICS, np.asarray(metrics))), grads


def _same_losses(ours, theirs):
    for name in LOSS_NAMES:
        want = theirs.get(name, 0.0)  # a model without the multi-token-prediction module has no such term: the program reports 0
        assert abs(ours[name] - want) < TOL * max(1.0, abs(want)), name


# (d) a sequence that starts from a snapshot, of both kinds of state, equals the same episode evaluated whole
def test_a_sequence_from_a_snapshot_equals_the_episode_evaluated_whole(model):
    agent = token_policy.TokenPolicy(model.core(), prompt_max=6, dtype=jnp.float32)
    batch, snap, aligned = _batch(model, np.random.default_rng(0), agent, continuing=True)
    ours, _ = _program_loss(model.weights, agent, batch, snap, model.mtp_coef)
    _same_losses(ours, model.losses(aligned, {**CONSTS, "mtp_loss_coef": model.mtp_coef}))
    assert ours["real_positions"] == 3 + 5 + 4 and ours["padded_positions"] == 3 * (6 + 8)
    if model.name == "lfm2":
        with conv_state_kept():  # the row that begins now reads what the episode before left in its row of the snapshot
            faulty, _ = _program_loss(model.weights, agent, batch, snap, model.mtp_coef)
        assert abs(faulty["value_loss"] - ours["value_loss"]) > 1000 * TOL


def test_a_sequence_from_a_wrapped_rings_snapshot_equals_the_episode_evaluated_whole(query_blocks):
    """The second episode has 13 of its 19 inputs before the rollout: the window layers' rings in the snapshot have
    wrapped (they hold positions 5 to 12, entry 0 position 8), and its 6 steps stop seeing those entries one by one."""
    model = Model("mellum2")
    agent = token_policy.TokenPolicy(model.core(), prompt_max=6, dtype=jnp.float32)
    batch, snap, aligned = _batch(model, np.random.default_rng(0), agent, continuing=True, lengths=(5, 15, 13, 6))
    assert batch["len0"].tolist() == [0, 13, 0] and snap[0][0].shape == (2, 8, 16) and snap[2][0].shape == (2, 32, 16)
    ours, _ = _program_loss(model.weights, agent, batch, snap, 0.0)
    _same_losses(ours, model.losses(aligned, {**CONSTS, "mtp_loss_coef": 0.0}))
    # the pairs the two window layers scored, 3 rows of 6 + 8 slots in one chunk: one block of 14 queries against the
    # row's 14 keys and the ring's 8; or blocks of 4, 4, 4 and 2 against slices of 12 keys, the ring beside them in
    # the two blocks that hold the second episode's steps (slots 6 to 11, positions 13 to 18: all inside the band of 12)
    assert ours["window_pairs_scored"] == 2 * 3 * {"blocks_as_they_are": 14 * (14 + 8), "blocks_of_4": 4 * 12 + 2 * 4 * (12 + 8) + 2 * 12}[query_blocks]
    # what the band needed: the first episode's 8 real queries at positions 0 to 7, the second's 6 past the window
    assert ours["window_pairs_scored"] >= token_policy.window_keys(batch, agent.core) == 2 * (sum(range(1, 9)) + 6 * 8)
    for fault in (window_ignored, yarn_left_out):  # the snapshot was made by the sound program: the update alone is faulty
        with fault():
            faulty, _ = _program_loss(model.weights, token_policy.TokenPolicy(model.core(), prompt_max=6, dtype=jnp.float32), batch, snap, 0.0)
        assert abs(faulty["value_loss"] - ours["value_loss"]) > 100 * TOL, fault.__name__


# (e) one update equals the reference's: losses, the gradient by leaf, the weights after; MTP term on and off
@pytest.mark.parametrize("name, mtp_coef, blocks_of_4", [("glm", 0.1, False), ("glm", 0.0, False), ("lfm2", 0.0, False), ("mellum2", 0.0, False),
                                                        ("mellum2", 0.0, True)], ids=["glm-mtp_on", "glm-mtp_off", "lfm2", "mellum2", "mellum2-blocks_of_4"])  # fmt: skip
def test_one_update_equals_the_reference(monkeypatch, name, mtp_coef, blocks_of_4):
    import optax

    if blocks_of_4:  # the gradient through a window layer's slices of its own keys (no row continues a ring here)
        _blocks_of_4(monkeypatch)
    model = Model(name)
    weights = model.weights
    agent = token_policy.TokenPolicy(model.core(), prompt_max=6, dtype=jnp.float32)
    batch, snap, aligned = _batch(model, np.random.default_rng(0), agent, continuing=False)
    a = {**CONSTS, "mtp_loss_coef": mtp_coef}
    ours, grads = _program_loss(weights, agent, batch, snap, mtp_coef)
    theirs, ref_grads = model.reference.loss_and_grad(weights, model.sizes, a, aligned)
    _same_losses(ours, theirs)
    gaps = jax.tree.map(gap, grads, ref_grads)
    assert max(jax.tree.leaves(gaps)) < 10 * TOL, gaps  # a leaf's gradient sums over every position: ten round-offs of room
    if name == "glm":
        moved = jax.tree.map(lambda g: float(jnp.abs(g).max()) > 0, ref_grads["mtp"])
        assert any(jax.tree.leaves(moved)) == (mtp_coef > 0)  # with its coefficient at 0 the module gets no gradient
    # the weights after: the main's optimizer (global-norm clip, then AdamW) against the reference's first step
    from sheeprl_tpu.ops.optim import adam

    tx = adam(lr=a["lr"], eps=a["eps"], weight_decay=a["weight_decay"], max_grad_norm=a["max_grad_norm"])
    updates, _ = tx.update(grads, tx.init(weights), weights)
    after = optax.apply_updates(weights, updates)
    ref_after = model.reference.adamw_first_step(weights, model.reference.clip_by_global_norm(ref_grads, a["max_grad_norm"]), a)
    change = jax.tree.map(lambda x, y, w: gap(x - w, y - w), after, ref_after, weights)
    assert max(jax.tree.leaves(change)) < 1e-3, change  # g / (|g| + eps) at eps 1e-4 magnifies the gradient's round-off


def test_bfloat16_fails_the_tolerances(model):
    """What (a), (b) and (e) hold in float32 does not survive bfloat16 operands."""
    tokens = np.random.default_rng(0).integers(0, VOCAB, (1, 12)).astype(np.int32)
    logits, values, _, _ = whole(model.weights, tokens, dtype=jnp.bfloat16, cfg=model.core())
    ref_logits, _ = model.forward(tokens[0])
    assert gap(logits[0].astype(jnp.float32), ref_logits) > 50 * TOL
    agent = token_policy.TokenPolicy(model.core(), prompt_max=6, dtype=jnp.bfloat16)
    batch, snap, aligned = _batch(model, np.random.default_rng(0), agent, continuing=False, dtype=jnp.bfloat16)
    _, grads = _program_loss(model.weights, agent, batch, snap, model.mtp_coef)
    _, ref_grads = model.reference.loss_and_grad(model.weights, model.sizes, {**CONSTS, "mtp_loss_coef": model.mtp_coef}, aligned)
    assert max(jax.tree.leaves(jax.tree.map(gap, grads, ref_grads))) > 50 * TOL


def test_build_sequences_emits_the_cores_carry():
    from sheeprl_tpu.algos.ppo_recurrent.ppo_recurrent import build_sequences

    T, E = 6, 2
    data = {"x": np.arange(T * E, dtype=np.float32).reshape(T, E, 1), "dones": np.zeros((T, E, 1), np.float32),
            "prev_len": np.arange(T * E, dtype=np.int32).reshape(T, E, 1) + 100, "prev_env": np.tile(np.arange(E, dtype=np.int32)[None, :, None], (T, 1, 1))}  # fmt: skip
    data["dones"][2, 0] = 1
    out = build_sequences(data, ["x"], 8, E, 4, carry_keys=("prev_len", "prev_env"))
    assert out["mask"].shape == (8, 4, 1) and out["mask"].sum(0)[:, 0].tolist() == [3, 3, 6, 0]
    assert out["len0"][:, 0].tolist() == [100, 106, 101, 0] and out["env0"][:, 0].tolist() == [0, 0, 1, 0]
    assert out["len0"].dtype == np.int32


# (g) the recipe through cli.run at the tiny size: it learns to copy, and leaves with 77 on SIGTERM
def tiny_args(tmp_path, exp="ppo_recurrent_glm47_flash", sizes=SIZES):
    as_word = lambda v: "[" + ",".join(str(x) for x in v) + "]" if isinstance(v, (list, tuple)) else v  # noqa: E731
    return [f"exp={exp}", "fabric=cpu", "fabric.precision=fp32", "fabric.devices=1", "env.num_envs=8", "algo.rollout_steps=16",
            "algo.per_rank_sequence_length=20", "algo.per_rank_batch_size=32", *[f"algo.core.{k}={as_word(v)}" for k, v in {
                **{k: v for k, v in sizes.items() if k not in ("vocab_rows", "context", "rope_theta", "rope_parameters", "rms_norm_eps")},
                "vocab_rows": 8, "context": 16, "prompt_max": 4, "prefill_rows": 2}.items()],
            "env.wrapper.prompt_min=1", "env.wrapper.prompt_max=2", "algo.optimizer.lr=3e-3", "metric.log_level=1", "algo.run_test=False",
            "checkpoint.save_last=False", "checkpoint.every=0", f"log_base_dir={tmp_path}/logs"]  # fmt: skip


def _sigterm_at_boundary(monkeypatch, nth):
    """A real SIGTERM to this process at the ``nth`` poll of the train loop's boundary."""
    import sheeprl_tpu.resilience.manager as manager

    real, count = manager.RunResilience.preempt_requested, [0]

    def polled(self):
        count[0] += 1
        if count[0] == nth:
            os.kill(os.getpid(), signal.SIGTERM)
        return real(self)

    monkeypatch.setattr(manager.RunResilience, "preempt_requested", polled)


def test_the_recipe_learns_to_copy_and_leaves_with_77_on_sigterm(tmp_path, monkeypatch, capsys):
    from sheeprl_tpu.resilience import PREEMPTED_EXIT_CODE

    monkeypatch.chdir(tmp_path)
    _sigterm_at_boundary(monkeypatch, 26)  # after 25 updates of 128 policy steps
    with pytest.raises(SystemExit) as left:
        run([*tiny_args(tmp_path), "algo.total_steps=1000000"])
    assert left.value.code == PREEMPTED_EXIT_CODE
    out = capsys.readouterr().out
    rewards = [float(line.rsplit("=", 1)[1]) for line in out.splitlines() if "reward_env_" in line]
    fifth = len(rewards) // 5
    assert fifth > 50 and np.mean(rewards[-fifth:]) > np.mean(rewards[:fifth]) + 0.1, (np.mean(rewards[:fifth]), np.mean(rewards[-fifth:]))


@pytest.mark.parametrize("exp, sizes", [("ppo_recurrent_lfm2_24b_a2b", LFM2_SIZES), ("ppo_recurrent_mellum2_12b", MELLUM2_SIZES)], ids=["lfm2", "mellum2"])
def test_the_other_recipes_train_through_the_same_main(tmp_path, monkeypatch, capsys, exp, sizes):
    """``exp=ppo_recurrent_lfm2_24b_a2b`` and ``exp=ppo_recurrent_mellum2_12b`` at the tiny size: rollouts whose
    episodes straddle them (every kind of snapshot is used: a cache, a convolution state, a ring of 8 that wraps
    inside the context of 16), the three programs under the names the first recipe gives them."""
    monkeypatch.chdir(tmp_path)
    seen = []
    real = token_policy.make_player_programs

    def programs(agent):
        made = real(agent)
        seen.extend(made[k].__wrapped__.__name__ for k in ("prefill", "decode"))
        return made

    monkeypatch.setattr(token_policy, "make_player_programs", programs)
    run([*tiny_args(tmp_path, exp, sizes), "algo.total_steps=768"])  # 6 updates of 128 policy steps
    assert seen == ["seqpol_prefill", "seqpol_decode"]
    out = capsys.readouterr().out
    assert sum("reward_env_" in line for line in out.splitlines()) > 50


def test_the_loops_spans_cover_each_update_and_count_the_prefill(tmp_path, monkeypatch):
    """``exp=ppo_recurrent_mellum2_12b`` at the tiny size with telemetry on, four updates: every new span under the
    parent it names, the self time of the spans but the two window spans covering each update to within a few percent
    (one of the three timed may read less: a pause of the process can fall between two spans), and the counters of
    ``seqpol/update`` as the prefill's shape bounds them (calls of 2 rows, then one of 1 for an odd one left, each row
    the observation's 2 slots; prompts of 1 or 2 tokens)."""
    import json

    from tests.test_algos.test_dv3_trace_names import loop_iterations

    monkeypatch.chdir(tmp_path)
    run([*tiny_args(tmp_path, "ppo_recurrent_mellum2_12b", MELLUM2_SIZES), "algo.total_steps=512", "metric.telemetry.enabled=True",
         "metric.telemetry.poll_interval=0.0"])  # fmt: skip
    (path,) = [os.path.join(root, f) for root, _, files in os.walk(tmp_path) for f in files if f == "telemetry.jsonl"]
    events = [json.loads(line) for line in open(path) if line.strip()]
    spans = [e for e in events if e["event"] == "span" and "t_mono_ns" in e]
    parents = {"loop/head": None, "update/assemble": None, "update/bootstrap": "train/dispatch", "update/sequences": "train/dispatch",
               "train/dispatch": "Time/train_time", "train/block": "Time/train_time", "loop/tail": None, "player/prefill": "Time/env_interaction_time"}  # fmt: skip
    for name, parent in parents.items():
        found = [e for e in spans if e["name"] == name]
        assert found and {e["parent"] for e in found} == {parent}, (name, {e["parent"] for e in found})
        assert name == "player/prefill" or len(found) == 4, (name, len(found))
    updates = loop_iterations(events)
    assert len(updates) == 4
    shares = [covered / wall for wall, covered in updates[1:]]  # past the first update's compiles
    assert sorted(shares)[1] > 0.9, shares
    counters = [e for e in events if e["event"] == "counters" and e["name"] == "seqpol/update"]
    assert len(counters) == 4 and not any("conv_state_resets" in e for e in counters)
    for e in counters:
        assert e["prefill_slots"] == e["rows_prefilled"] * 2  # with ``prefill_rows`` 2 every rung is full
        assert e["prefill_calls"] <= e["rows_prefilled"] <= 2 * e["prefill_calls"] and e["rows_prefilled"] > 0
        assert e["rows_prefilled"] == e["prefill_tokens"] <= e["prefill_slots"]  # a prompt of 2 tokens prefills 1
        assert 0 < e["window_keys"] <= e["window_pairs_scored"]


def test_the_lstm_recipe_leaves_with_77_on_sigterm(tmp_path, monkeypatch):
    from sheeprl_tpu.resilience import PREEMPTED_EXIT_CODE
    from tests.test_algos.test_ppo_recurrent import find_checkpoints, rppo_args

    monkeypatch.chdir(tmp_path)
    _sigterm_at_boundary(monkeypatch, 2)
    args = [a for a in rppo_args(tmp_path) if a != "dry_run=True"] + ["algo.total_steps=64", "algo.run_test=False"]
    with pytest.raises(SystemExit) as left:
        run(args)
    assert left.value.code == PREEMPTED_EXIT_CODE
    assert find_checkpoints(tmp_path)  # the emergency checkpoint of the update that had finished
