"""Share of the engine's programs (prefill chunks and decode steps)
that were dispatched while an earlier one had not been retired yet,
from `engine.stats()` deltas over the window: `programs_ahead` over
`programs`. Near 100 the device had its next program queued while the
host fetched, emitted and prepared; near 0 the loop ran host and
device in turn. A program whose engine counts neither (before PR 27)
gives nothing."""

LAYER, UNIT, SOURCE = "engine", "%", "program_counter"


def reduce(run: dict):
    engine = run.get("engine")
    if not engine:
        return None
    before, after = engine["before"], engine["after"]
    if "programs" not in after or "programs_ahead" not in after:
        return None
    programs = after["programs"] - before.get("programs", 0)
    if programs <= 0:
        return None
    ahead = after["programs_ahead"] - before.get("programs_ahead", 0)
    return 100.0 * ahead / programs
