"""Mean time of one prefill chunk forward, from the program's fenced
timer `serve_engine_prefill_chunk_ms` (sum and count deltas)."""

from benchmark.stats import timer_mean

LAYER, UNIT, SOURCE = "serve forwards", "ms", "program_span"


def reduce(run: dict):
    return timer_mean(run.get("engine_timers"), "serve_engine_prefill_chunk_ms")
