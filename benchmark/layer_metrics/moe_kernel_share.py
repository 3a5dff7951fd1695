"""Device time of the experts' grouped matmuls as a share of the
traced window's busy time. The program runs them as `lax.ragged_dot`,
which XLA compiles on the TPU to a grouped-matmul kernel of its own
and names `ragged-dot-*` in the device's instruction stream (the
kernel proper and the small program that lays out its groups), which
is what the trace's operation families print. A program with no such
operation among its top ones, or a run with no trace, gives
nothing."""

LAYER, UNIT, SOURCE = "expert kernel", "%", "device_trace"

KERNEL_PREFIX = "ragged-dot"


def kernel_seconds(trace):
    """Seconds per chip in the grouped matmuls, or None if the trace's
    top operations list none."""
    if not trace or not trace.get("device_ops"):
        return None
    found = [
        s for name, s in trace["device_ops"] if name.startswith(KERNEL_PREFIX)
    ]
    return sum(found) if found else None


def reduce(run: dict):
    trace = run.get("trace")
    seconds = kernel_seconds(trace)
    if seconds is None or not trace.get("busy_s"):
        return None
    return 100.0 * seconds / trace["busy_s"]
