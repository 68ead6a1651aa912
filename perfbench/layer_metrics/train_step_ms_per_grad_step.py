"""Summed ``Time/train_time`` spans of the window over its gradient steps
(policy steps times the recipe's replay ratio): replay gather included."""


def read(run):
    spans = run.spans("Time/train_time")
    if not spans or run.gradient_steps <= 0:
        return None
    return 1e3 * sum(s["dur"] for s in spans) / run.gradient_steps
