"""The whole step's share of the chip's peak: the configuration's model FLOPs
per gradient step times the window's gradient steps, over the window's wall
time times the chip's bf16 peak. Idle time counts against it."""


def read(run):
    if run.peak is None:
        return None
    flops = run.cell.config["model_flops_per_grad_step"] * run.gradient_steps
    return 100.0 * flops / (run.window["seconds"] * run.peak["bf16_flops_per_s"] * run.cell.chips)
