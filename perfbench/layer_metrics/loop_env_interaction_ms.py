"""Mean duration of the program's ``Time/env_interaction_time`` spans in the
window: player forward, ring write and the env step, once per vector step."""


def read(run):
    spans = run.spans("Time/env_interaction_time")
    if not spans:
        return None
    return 1e3 * sum(s["dur"] for s in spans) / len(spans)
