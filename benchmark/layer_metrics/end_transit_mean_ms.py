"""Mean time from a stream's end being handed to the transport (E1,
replica) to the proxy's last body bytes written (E2): the head daemon,
the parked fetch's last answer, the proxy's write.
`serve_stream_end_transit_ms`, one observation a request, epoch clocks
of two processes of one host (as `serve_first_item_transit_ms`), over
the requests that ended in the window. A program without the series
gives nothing."""

from benchmark.stats import timer_mean

LAYER, UNIT, SOURCE = "serve ingress", "ms", "program_span"


def reduce(run: dict):
    return timer_mean(run.get("engine_timers"), "serve_stream_end_transit_ms")
