"""Device time of the flash-attention kernels, forward and backward,
as a share of the traced window's busy time. The kernels are found by
the names the program gives its Pallas calls (`flash_fwd`,
`flash_bwd`), which is what the trace's operation families print; a
program that names neither, or a run with no trace, gives nothing."""

LAYER, UNIT, SOURCE = "attention kernel", "%", "device_trace"

KERNELS = ("flash_fwd", "flash_bwd")


def kernel_seconds(trace):
    """Seconds per chip in the named kernels, or None if the trace's
    top operations list neither."""
    if not trace or not trace.get("device_ops"):
        return None
    found = [s for name, s in trace["device_ops"] if name in KERNELS]
    return sum(found) if found else None


def reduce(run: dict):
    trace = run.get("trace")
    seconds = kernel_seconds(trace)
    if seconds is None or not trace.get("busy_s"):
        return None
    return 100.0 * seconds / trace["busy_s"]
