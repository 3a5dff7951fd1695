"""Mean time a request waited from `submit()` to its slot, over the
requests the engine admitted in the window: the deltas of two exact
counters in `engine.stats()`, `admit_wait_ms_total` over `admitted`.
A program whose engine does not count admissions gives nothing."""

LAYER, UNIT, SOURCE = "engine", "ms", "program_span"


def reduce(run: dict):
    engine = run.get("engine")
    if not engine:
        return None
    before, after = engine["before"], engine["after"]
    if "admitted" not in after or "admit_wait_ms_total" not in after:
        return None
    admitted = after["admitted"] - before.get("admitted", 0)
    if admitted <= 0:
        return None
    waited = after["admit_wait_ms_total"] - before.get(
        "admit_wait_ms_total", 0.0
    )
    return waited / admitted
