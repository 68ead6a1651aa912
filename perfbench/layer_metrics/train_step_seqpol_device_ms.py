"""Device time of one ``seqpol_train_step`` execution (a gradient step on one
minibatch of sequences), over its whole executions in the traced stretch: one
whole cycle of the token loop, so every gradient step of one update
(``perfbench/token_counters.py``)."""

from perfbench import token_counters


def read(run):
    return token_counters.train_step_ms(run)
