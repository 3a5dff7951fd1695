"""Time collectives hold the core's instruction stream with no
compute running (leaf collective operations on the `XLA Ops` line),
as a share of the traced window."""

LAYER, UNIT, SOURCE = "device", "%", "device_trace"


def reduce(run: dict):
    trace = run.get("trace")
    if not trace or not trace.get("window_s"):
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
