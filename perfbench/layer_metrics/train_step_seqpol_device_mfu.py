"""The token policy's train step's share of the chip's peak inside the step: the
configuration's model FLOPs per gradient step over
``train_step.seqpol_device_ms`` times the chip's bf16 peak."""

from perfbench import token_counters


def read(run):
    ms = token_counters.train_step_ms(run)
    if ms is None or run.peak is None:
        return None
    flops = run.cell.config["model_flops_per_grad_step"]
    return 100.0 * flops / (ms / 1e3 * run.peak["bf16_flops_per_s"] * run.cell.chips)
