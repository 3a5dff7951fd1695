"""Of the prompt tokens the full-attention layers' pages alone would
have let prefix hits skip, the share that was skipped: 100 x
`prefix_tokens_saved` / `prefix_tokens_full_hit`, the deltas of two
counters in `engine.stats()` over the window's admissions. A hit also
needs the window layers' keys just before the skip (a boundary's tail,
`llm/kv_window.py`), which the window pool keeps only while no ring
needs the page: what is under 100 went with an evicted tail (the engine
fell back to a shorter boundary, or to a miss). A program whose engine
has no window pool, or a window with no admission that could hit, gives
nothing."""

LAYER, UNIT, SOURCE = "engine", "%", "program_counter"


def reduce(run: dict):
    engine = run.get("engine")
    if not engine:
        return None
    before, after = engine["before"], engine["after"]
    if "prefix_tokens_full_hit" not in after:
        return None
    could = after["prefix_tokens_full_hit"] - before.get(
        "prefix_tokens_full_hit", 0
    )
    if could <= 0:
        return None
    return 100.0 * (
        after["prefix_tokens_saved"] - before.get("prefix_tokens_saved", 0)
    ) / could
