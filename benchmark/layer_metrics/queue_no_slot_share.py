"""Share of the window's admit wait that its requests spent behind
`no_slot`, in per cent: the engine's queue stood because the scheduler
had no free slot, so nothing but a finished row could have admitted
its head (llm/engine.py's module docstring: one cause at a time, the
FIFO head's, decided `no_slot` -> `behind_prefill` -> the refusing
pool -> `admissible`). From `engine.stats()`
`admit_wait_by_cause_ms_total`, exact per request, deltas over the
window, over their sum (which is `admit_wait_ms_total`'s delta, what
`engine_admit_wait_mean_ms` reads). What `queue_no_slot_share`,
`queue_behind_prefill_share` and `queue_no_memory_share` leave of 100
is `admissible`: the loop's own latency. A program whose engine keeps
no causes, or a window that admitted nobody who waited, gives
nothing."""

LAYER, UNIT, SOURCE = "engine", "%", "program_span"

CAUSES = ("no_slot",)


def waited_by_cause(run: dict):
    """{cause: ms} the window's admitted requests waited, or None."""
    engine = run.get("engine")
    if not engine:
        return None
    after = engine["after"].get("admit_wait_by_cause_ms_total")
    if not after:
        return None
    before = engine["before"].get("admit_wait_by_cause_ms_total") or {}
    return {c: ms - before.get(c, 0.0) for c, ms in after.items()}


def share(run: dict, causes) -> "float | None":
    waited = waited_by_cause(run)
    if not waited:
        return None
    total = sum(waited.values())
    if total <= 0:
        return None
    return 100.0 * sum(waited.get(c, 0.0) for c in causes) / total


def reduce(run: dict):
    return share(run, CAUSES)
