"""Peak device memory on the fullest chip, as the backend reports it
in the process that holds the chip."""

LAYER, UNIT, SOURCE = "memory", "GB", "program_counter"


def reduce(run: dict):
    peak = run["device"].get("memory_peak_bytes")
    return peak / 1e9 if peak else None
