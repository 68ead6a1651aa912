"""Share of the routed pairs of the window's updates that landed on a held
expert (``seqpol/update`` counters): 100 x held over all; an eighth is expected
of 8 held of 64 under even routing."""

from perfbench import token_counters


def read(run):
    routed, held = token_counters.total(run, "routed_pairs"), token_counters.total(run, "held_pairs")
    return 100.0 * held / routed if routed else None
