"""The grouped expert products' share of the chip's peak: the FLOPs of the
routed pairs a gradient step actually computed (``seqpol/update`` counters;
forward and backward, three times the forward) over the device time under
``seqpol/moe/experts`` times the bf16 peak."""

from perfbench import token_counters
from perfbench.algorithms import token_ppo


def read(run):
    ms = token_counters.scope_ms(run, "seqpol/moe/experts")
    pairs = token_counters.per_gradient_step(run, "held_pairs")
    if not ms or pairs is None or run.peak is None:
        return None
    return 100.0 * token_ppo.expert_pair_flops(run.cell.config, pairs) / (ms / 1e3 * run.peak["bf16_flops_per_s"] * run.cell.chips)
