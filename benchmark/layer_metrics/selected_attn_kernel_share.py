"""Device time of the prefill chunks' selected attention as a share of
the traced window's busy time: the Pallas kernel
`ray_tpu/ops/selected_attention.py`, which the device's instruction
stream names `selected_attn*` (what the trace's operation families
print). A program with no such operation among its top ones, or a run
with no trace, gives nothing."""

LAYER, UNIT, SOURCE = "attention kernel", "%", "device_trace"

KERNEL_PREFIX = "selected_attn"


def kernel_seconds(trace):
    """Seconds per chip in the kernel, or None if the trace's top
    operations list none."""
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
