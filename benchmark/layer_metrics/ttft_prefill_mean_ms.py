"""Mean time from a request's admission to a slot to the first token
the engine's loop emitted for it (B4 -> B5), over the requests whose
first token came in the window: the chunks of its prompt AND the decode
steps of the other rows that ran between them. The deltas of two exact
counters in `engine.stats()`, `prefill_ms_total` over `first_tokens`,
taken in the branch of `llm/engine.py` `_emit` that sets
`first_token_ts` (once a request). An engine that does not count first
tokens (before PR 41) gives nothing."""

LAYER, UNIT, SOURCE = "engine", "ms", "program_span"


def reduce(run: dict):
    engine = run.get("engine")
    if not engine:
        return None
    before, after = engine["before"], engine["after"]
    if "first_tokens" not in after or "prefill_ms_total" not in after:
        return None
    first = after["first_tokens"] - before.get("first_tokens", 0)
    if first <= 0:
        return None
    spent = after["prefill_ms_total"] - before.get("prefill_ms_total", 0.0)
    return spent / first
