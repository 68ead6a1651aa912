"""Device self time per ``seqpol_train_step`` under ``seqpol/conv/mix`` alone:
the gated short convolution's gates and depthwise taps, forward and backward,
without its two projections (``train_step.conv_device_ms`` holds all three
scopes). A time and no share of a roofline: the compiler keeps a chunk's gated
inputs in the chip's vector memory between the fusions of this scope where
they fit, so the bytes the scope would move as a kernel of its own bound
nothing (PERF.md section 6, PR 33)."""

from perfbench import token_counters


def read(run):
    return token_counters.scope_ms(run, "seqpol/conv/mix")
