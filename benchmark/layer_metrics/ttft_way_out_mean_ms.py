"""Mean time of a stream's first token from the engine's loop to the
proxy's socket (B5 -> B7), the sum of two means: the loop thread's
`req.out.put` to the handler thread's first `yield`
(`serve_first_item_handoff_ms`, one interpreter, one clock, observed by
`llm/serving.py` at its first token) and the first item's way from the
replica's worker through `stream_append` in the head daemon and the
parked `stream_fetch` to the proxy's first `wfile.flush()`
(`serve_first_item_transit_ms`, from the epoch time the producer stamps
on a run's first item; two processes of one host). Each once per
streamed request; [sum, count] deltas of the head's metrics table, window
open to edge. A program without either series (before PR 41) gives
nothing."""

from benchmark.stats import timer_mean

LAYER, UNIT, SOURCE = "serve ingress", "ms", "program_span"


def reduce(run: dict):
    timers = run.get("engine_timers")
    handoff = timer_mean(timers, "serve_first_item_handoff_ms")
    transit = timer_mean(timers, "serve_first_item_transit_ms")
    if handoff is None or transit is None:
        return None
    return handoff + transit
