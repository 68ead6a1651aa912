"""The sliding-window attention's share of its roofline, which is the matrix
unit's: the FLOPs the band cannot avoid in a gradient step (the ``window_keys``
counter of ``seqpol/update``: over the real queries the keys inside each one's
window, summed over the window layers; forward and backward, by the cell's own
algorithm module's ``window_flops``) over the device time under
``seqpol/attn/window`` times the bf16 peak. It counts the keys a query may
see, not those the program scores, so it cannot pass 100%, and a program that
stops scoring masked keys raises it."""

from perfbench import loader, token_counters


def read(run):
    counts = loader.algorithm(run.cell)
    if not all(hasattr(counts, f) for f in ("window_scope_ms", "window_flops")) or run.peak is None:
        return None
    ms = counts.window_scope_ms(run)
    found = [e for e in token_counters.updates(run) if "window_keys" in e]
    steps = sum(e["gradient_steps"] for e in found)
    if not ms or not steps:
        return None
    keys = sum(e["window_keys"] for e in found) / steps
    return 100.0 * counts.window_flops(run.cell.config, keys) / (ms / 1e3 * run.peak["bf16_flops_per_s"] * run.cell.chips)
