"""Device self time per ``seqpol_train_step`` under ``seqpol/attn/window``: a
sliding-window layer's scores, softmax and weighted sum (not its projections),
forward and backward, summed over the window layers. The scope lies inside
``seqpol/attn``, which ``train_step.attn_device_ms`` reads whole, so the cell's
own algorithm module (found through ``loader.algorithm``) reads it in a
reduction of its own; a program or a module without it gives nothing."""

from perfbench import loader


def read(run):
    window_scope_ms = getattr(loader.algorithm(run.cell), "window_scope_ms", None)
    return window_scope_ms(run) if window_scope_ms else None
