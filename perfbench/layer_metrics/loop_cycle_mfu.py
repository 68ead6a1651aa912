"""The whole loop's share of the chip's peak, for any token policy: the model
FLOPs of every decode, prefill and gradient step of the window's whole update
cycles (from the ``seqpol/update`` counters, by the count of the cell's own
algorithm module, found through ``loader.algorithm``) over the time those
cycles took times the bf16 peak. Idle time counts against it."""

from perfbench import loader, token_counters


def read(run):
    found = token_counters.updates(run)
    counts = loader.algorithm(run.cell)
    if len(found) < 2 or run.peak is None or not all(hasattr(counts, f) for f in ("train_step_flops", "decode_flops", "prefill_flops")):
        return None
    config = run.cell.config
    flops = 0.0
    for e in found[1:]:  # each event closes the cycle that began at the one before it
        flops += e["gradient_steps"] * counts.train_step_flops(config, e["held_pairs"] / e["gradient_steps"])
        flops += e["tokens_decoded"] / config["algo"]["num_envs"] * counts.decode_flops(config)
        flops += e["rows_prefilled"] / config["algo"]["prefill_rows"] * counts.prefill_flops(config)
    seconds = (found[-1]["t_mono_ns"] - found[0]["t_mono_ns"]) / 1e9
    return 100.0 * flops / (seconds * run.peak["bf16_flops_per_s"] * run.cell.chips)
