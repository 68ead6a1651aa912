"""The decode step's share of the chip's memory bandwidth, for any token
policy: the bytes one step cannot avoid (the cell's own algorithm module's
``decode_bytes``: the weights it touches in the compute dtype, the state of
either kind that the rows carry up to each row's length, the logits; with the
mean of the ``seqpol/update`` counters) over ``player.decode_device_ms`` times
the peak bytes per second."""

from perfbench import device_time, loader, token_counters


def read(run):
    count = getattr(loader.algorithm(run.cell), "decode_bytes", None)
    ms = device_time.program_ms(device_time.of_run(run), "seqpol_decode")
    decoded, attended = token_counters.total(run, "tokens_decoded"), token_counters.total(run, "cache_positions")
    if count is None or ms is None or not decoded or run.peak is None:
        return None
    config = run.cell.config
    per_step = attended / (decoded / config["algo"]["num_envs"])
    return 100.0 * count(config, per_step) / (ms / 1e3 * run.peak["hbm_bytes_per_s"])
