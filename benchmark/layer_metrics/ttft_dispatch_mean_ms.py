"""Mean time from the proxy's reading of an HTTP request to the replica
call sent (boundaries B0 -> B1 of `serve/observability.py`): route
refresh and match, body read, the handle, the router's choice of a
replica (`serve_router_routing_ms` lies inside it), the request
context. `serve_http_dispatch_ms`, observed once per streamed request in
the proxy; [sum, count] deltas of the head's metrics table, window open
to edge. A program without the series (before PR 41) gives nothing."""

from benchmark.stats import timer_mean

LAYER, UNIT, SOURCE = "serve ingress", "ms", "program_span"


def reduce(run: dict):
    return timer_mean(run.get("engine_timers"), "serve_http_dispatch_ms")
