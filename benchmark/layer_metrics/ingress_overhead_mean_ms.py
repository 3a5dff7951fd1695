"""Mean time a request spent above the replica over the window: the
proxy's whole-request timer (`serve_http_request_latency_ms`: HTTP in
to the last byte out, streaming included) less the replica's handler
timer (`serve_request_latency_ms`: first yield to exhaustion), each the
mean over the requests that ended in the window, from the head's
metrics table. What is between the two is the router, the actor
mailbox and the streaming-generator transport that carries each token
from the replica through the head daemon to the proxy. Near 0.1 s
under the knee; it grows by seconds once that path, not the engine, is
what a stream waits for (PERF.md section 6, PR 25). A program without
either timer gives nothing."""

from benchmark.stats import timer_mean

LAYER, UNIT, SOURCE = "serve ingress", "ms", "program_span"


def reduce(run: dict):
    timers = run.get("engine_timers")
    http = timer_mean(timers, "serve_http_request_latency_ms")
    handler = timer_mean(timers, "serve_request_latency_ms")
    if http is None or handler is None:
        return None
    return http - handler
