"""Share of the positions the window's updates computed that were padding
(``seqpol/update`` counters): 100 x (1 - real over padded)."""

from perfbench import token_counters


def read(run):
    real, padded = token_counters.total(run, "real_positions"), token_counters.total(run, "padded_positions")
    return 100.0 * (1.0 - real / padded) if padded else None
