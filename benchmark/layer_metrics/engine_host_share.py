"""Share of the engine loop's busy time its thread spent on host work,
from the loop's own phases (`engine.stats()["loop_ms"]`, deltas over
the window): every phase except `engine.idle`, the waits on the device
(`*.wait`) and the per-step sync (`engine.decode.sync`), over every
phase except `engine.idle`. The phases partition the loop's wall time,
so this is the part of an iteration the device is not what the loop
waits for. A program whose engine reports no phases gives nothing."""

LAYER, UNIT, SOURCE = "engine", "%", "program_span"

IDLE = "engine.idle"


def on_device(phase: str) -> bool:
    return phase.endswith(".wait") or phase == "engine.decode.sync"


def reduce(run: dict):
    engine = run.get("engine")
    if not engine:
        return None
    after = engine["after"].get("loop_ms")
    if not after:
        return None
    before = engine["before"].get("loop_ms") or {}
    delta = {
        phase: ms - before.get(phase, 0.0)
        for phase, ms in after.items() if phase != IDLE
    }
    busy = sum(delta.values())
    if busy <= 0:
        return None
    host = sum(ms for phase, ms in delta.items() if not on_device(phase))
    return 100.0 * host / busy
