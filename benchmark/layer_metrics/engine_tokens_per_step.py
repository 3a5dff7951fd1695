"""Tokens the engine emitted per engine step over the window, from
`engine.stats()` deltas: how full the decode batch ran."""

LAYER, UNIT, SOURCE = "engine", "tokens/step", "program_counter"


def reduce(run: dict):
    engine = run.get("engine")
    if not engine:
        return None
    before, after = engine["before"], engine["after"]
    steps = after["steps"] - before["steps"]
    if steps <= 0:
        return None
    return (after["tokens_emitted"] - before["tokens_emitted"]) / steps
