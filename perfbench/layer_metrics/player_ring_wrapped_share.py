"""Share of the player's rows whose window layers' rings had wrapped as a
rollout ended (the ``ring_wrapped_rows`` counter of ``seqpol/update``: rows at
position ``sliding_window`` or beyond), mean over the window's rollouts: how
much of the traffic keeps the window at work. Nothing where the program counts
no such rows."""

from perfbench import token_counters


def read(run):
    found = [e for e in token_counters.updates(run) if "ring_wrapped_rows" in e]
    if not found:
        return None
    return 100.0 * sum(e["ring_wrapped_rows"] for e in found) / (len(found) * run.cell.config["algo"]["num_envs"])
