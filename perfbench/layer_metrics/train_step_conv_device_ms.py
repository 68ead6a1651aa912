"""Device self time per ``seqpol_train_step`` under ``seqpol/conv`` (the gated
short convolution: its two projections under ``seqpol/conv/proj``, the gates
and the depthwise convolution under ``seqpol/conv/mix``), forward and backward.
The scopes that make it up are the algorithm module's ``conv_scopes``."""

from perfbench import loader, token_counters


def read(run):
    scopes = getattr(loader.algorithm(run.cell), "conv_scopes", None)
    parts = [token_counters.scope_ms(run, scope) for scope in scopes or ()]
    return sum(parts) if parts and all(p is not None for p in parts) else None
