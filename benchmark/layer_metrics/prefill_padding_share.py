"""Share of the positions the window's prefill chunks computed that no
prompt needed: 100 x (1 - needed / computed) over the deltas of two
counters in `engine.stats()`, `prefill_tokens_computed` (the token
counts of the chunks dispatched, taken at dispatch in `llm/engine.py`
`_advance_prefill`) and `prefill_tokens_needed` (a prompt's tokens less
its prefix hit's skip, taken at admission). 0 is a program whose chunks
end where the prompts end; what is above it is padding of a prompt's
last chunk. (A request admitted in the window whose chunks run past its
edge, or the other way round, moves it by under a chunk in hundreds.)
An engine that does not count them (before PR 43) gives nothing."""

LAYER, UNIT, SOURCE = "engine", "%", "program_counter"


def reduce(run: dict):
    engine = run.get("engine")
    if not engine:
        return None
    before, after = engine["before"], engine["after"]
    names = ("prefill_tokens_computed", "prefill_tokens_needed")
    if any(name not in after for name in names):
        return None
    computed, needed = (
        after[name] - before.get(name, 0) for name in names
    )
    if computed <= 0:
        return None
    return 100.0 * (1.0 - needed / computed)
