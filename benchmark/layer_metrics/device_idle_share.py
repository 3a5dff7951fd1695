"""1 - (union of device operation intervals) / traced window, from
the profiler's trace, averaged over the chips."""

LAYER, UNIT, SOURCE = "device", "%", "device_trace"


def reduce(run: dict):
    trace = run.get("trace")
    if not trace or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
