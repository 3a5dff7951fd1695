"""Mean time of one decode step over the slot batch, from the
program's fenced timer `serve_engine_decode_step_ms` (sum and count
deltas over the window, not bucket medians)."""

from benchmark.stats import timer_mean

LAYER, UNIT, SOURCE = "serve forwards", "ms", "program_span"


def reduce(run: dict):
    return timer_mean(run.get("engine_timers"), "serve_engine_decode_step_ms")
