"""Share of the steps' wall time the loop spent waiting for data or
dispatching host-to-device copies (the step telemetry's `data_wait`
and `h2d` phases, read per step in the loop's own thread)."""

LAYER, UNIT, SOURCE = "train loop", "%", "program_span"


def reduce(run: dict):
    steps = run.get("steps")
    if not steps:
        return None
    wall = sum(s[1] for s in steps)
    return 100.0 * sum(s[2] + s[3] for s in steps) / wall
