"""Device time of one ``seqpol_train_step`` execution (a gradient step on one
minibatch of sequences), over its whole executions in the trace to its end:
``perfbench/token_counters.py`` says why not over the stretch that
``train_step.device_ms`` reads."""

from perfbench import token_counters


def read(run):
    return token_counters.train_step_ms(run)
