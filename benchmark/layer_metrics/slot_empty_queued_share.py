"""Share of the window's slot-time in which a slot stood empty WHILE
the engine's queue stood, in per cent: `engine.stats()` `slot_ms`
(slots x milliseconds by state: `decoding`, `prefilling`,
`empty_queued`, `empty_idle`; the four sum to the slots times the time
elapsed), deltas over the window, `empty_queued` over their sum. It is
what any change to admission can win: a slot empty with no waiter
(`empty_idle`) is the traffic's. A program whose engine keeps no
slot-time gives nothing."""

LAYER, UNIT, SOURCE = "engine", "%", "program_span"


def reduce(run: dict):
    engine = run.get("engine")
    if not engine:
        return None
    after = engine["after"].get("slot_ms")
    if not after:
        return None
    before = engine["before"].get("slot_ms") or {}
    delta = {s: ms - before.get(s, 0.0) for s, ms in after.items()}
    total = sum(delta.values())
    if total <= 0:
        return None
    return 100.0 * delta.get("empty_queued", 0.0) / total
