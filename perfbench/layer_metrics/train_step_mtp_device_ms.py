"""Device self time per ``seqpol_train_step`` under ``seqpol/mtp``, forward and backward."""

from perfbench import token_counters


def read(run):
    return token_counters.scope_ms(run, "seqpol/mtp")
