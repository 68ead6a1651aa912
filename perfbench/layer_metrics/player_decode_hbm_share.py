"""The decode step's share of the chip's memory bandwidth: the bytes one step
cannot avoid (the weights it touches in the compute dtype, the cache entries
up to each row's length, the logits; ``token_ppo.decode_bytes`` with the mean
of the ``seqpol/update`` counters) over ``player.decode_device_ms`` times the
peak bytes per second."""

from perfbench import device_time, token_counters
from perfbench.algorithms import token_ppo


def read(run):
    ms = device_time.program_ms(device_time.of_run(run), "seqpol_decode")
    decoded, attended = token_counters.total(run, "tokens_decoded"), token_counters.total(run, "cache_positions")
    if ms is None or not decoded or run.peak is None:
        return None
    config = run.cell.config
    per_step = attended / (decoded / config["algo"]["num_envs"])
    return 100.0 * token_ppo.decode_bytes(config, per_step) / (ms / 1e3 * run.peak["hbm_bytes_per_s"])
