"""Share of the window's prefill chunks that were dispatched in an
iteration of the engine's loop which had dispatched a chunk already,
in per cent: 100 x the deltas of two counters in `engine.stats()`,
`prefill_joined_chunks` over `prefill_chunks` (both cumulative and
exact, taken at a chunk's dispatch in `llm/engine.py`). A chunk joins
behind a prompt's SHORT last chunk, which leaves room in the
iteration's budget of `prefill_chunk` tokens: the next prompt is
admitted behind it and its row is in that iteration's decode step. 0
is one chunk an iteration. An engine that does not count them (before
PR 60) gives nothing."""

LAYER, UNIT, SOURCE = "engine", "%", "program_counter"


def reduce(run: dict):
    engine = run.get("engine")
    if not engine:
        return None
    before, after = engine["before"], engine["after"]
    names = ("prefill_joined_chunks", "prefill_chunks")
    if any(name not in after for name in names):
        return None
    joined, chunks = (
        after[name] - before.get(name, 0) for name in names
    )
    if chunks <= 0:
        return None
    return 100.0 * joined / chunks
