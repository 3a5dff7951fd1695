"""Of the prompt tokens the attention layers' pages alone would have let
prefix hits skip, the share that was skipped: 100 x
`prefix_tokens_saved` / `prefix_tokens_full_hit`, the deltas of two
counters in `engine.stats()` over the window's admissions. Where most
layers are gated short convolutions a hit also needs their STATE at the
boundary it skips to, which no page holds: a snapshot the prefill left
there (`llm/kv_state.py`), kept only while no row and no newer snapshot
needs its slot. What is under 100 went with an evicted snapshot (the
engine fell back to a shorter boundary, or to a miss). A program whose
engine keeps no such state (no `conv_hits_restored` among its
counters), or a window with no admission that could hit, gives
nothing."""

LAYER, UNIT, SOURCE = "engine", "%", "program_counter"


def reduce(run: dict):
    engine = run.get("engine")
    if not engine:
        return None
    before, after = engine["before"], engine["after"]
    if "conv_hits_restored" not in after or "prefix_tokens_full_hit" not in after:
        return None
    could = after["prefix_tokens_full_hit"] - before.get(
        "prefix_tokens_full_hit", 0
    )
    if could <= 0:
        return None
    return 100.0 * (
        after["prefix_tokens_saved"] - before.get("prefix_tokens_saved", 0)
    ) / could
