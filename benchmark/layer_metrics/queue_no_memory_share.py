"""Share of the window's admit wait spent behind a POOL, in per cent:
a slot was free, no prompt was prefilling, and the admission gate
refused the queue's head for the full pages (`no_pages`), the window
ring (`no_window_pages`) or a state slot (`no_state_slots`), the three
together here (the split stays in `engine.stats()`). What a larger or
better-shared pool would win back. Same counter and sum as
`queue_no_slot_share`."""

from benchmark.harness import load_module

LAYER, UNIT, SOURCE = "engine", "%", "program_span"

CAUSES = ("no_pages", "no_window_pages", "no_state_slots")


def reduce(run: dict):
    return load_module("layer_metrics", "queue_no_slot_share").share(
        run, CAUSES
    )
