"""Mean time a streamed request spent above the replica, as an
identity per request: `serve_ingress_overhead_ms`, which the proxy
observes once a request in its own process as its time from the HTTP
request read to the last bytes written (its `perf_counter`) LESS the
handler's duration that the stream's end carried back (the replica's
`perf_counter`): the same request on both sides of the minus. Beside
`ingress_overhead_mean_ms`, which subtracts two means, each over its
own process's flushes: where the two disagree, that one was the two
sets of requests. A program without the series gives nothing."""

from benchmark.stats import timer_mean

LAYER, UNIT, SOURCE = "serve ingress", "ms", "program_span"


def reduce(run: dict):
    return timer_mean(run.get("engine_timers"), "serve_ingress_overhead_ms")
