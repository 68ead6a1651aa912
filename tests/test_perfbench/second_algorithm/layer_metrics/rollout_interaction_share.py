"""Share of the window's wall time inside the program's
``Time/env_interaction_time`` spans: the rollout's side of an on-policy turn."""


def read(run):
    spans = run.spans("Time/env_interaction_time")
    if not spans:
        return None
    return 100.0 * sum(s["dur"] for s in spans) / run.window["seconds"]
