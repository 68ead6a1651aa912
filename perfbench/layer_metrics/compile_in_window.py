"""Compile events (trace, lowering, backend compile) that the harness's own
``jax.monitoring`` listener saw end inside the window. Should read 0."""


def read(run):
    return float(len(run.compiles.between(run.window["open_ns"], run.window["close_ns"])))
