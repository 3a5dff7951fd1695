"""Mean time from a streamed request's handler running out (E0) to
its stream's end being handed to the transport (E1), both in the
replica's interpreter: `serve_stream_end_handoff_ms`, one observation a
request by the proxy from the two epochs the stream's end carries
(serve/observability.py), over the requests that ended in the window.
A program without the series gives nothing."""

from benchmark.stats import timer_mean

LAYER, UNIT, SOURCE = "serve ingress", "ms", "program_span"


def reduce(run: dict):
    return timer_mean(run.get("engine_timers"), "serve_stream_end_handoff_ms")
