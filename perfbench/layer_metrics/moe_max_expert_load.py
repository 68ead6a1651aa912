"""The busiest held expert's pairs over the mean of a held expert, the largest
of the window's updates (``seqpol/update`` counters): 1 is even routing."""

from perfbench import token_counters


def read(run):
    found = token_counters.updates(run)
    return float(max(e["max_expert_load"] for e in found)) if found else None
