"""What a streamed request spends above the replica that is neither
its way in nor its end, mean ms a request:
`ingress_overhead_req_mean_ms` less the means of
`serve_http_dispatch_ms` (B0 -> B1), `serve_queue_wait_ms` (B1 -> B2),
`end_handoff_mean_ms` (E0 -> E1) and `end_transit_mean_ms` (E1 -> E2).
What is left is the tokens' way out: every item's trip from the
handler's yield to the proxy's write that lies BEHIND the handler (the
handler runs on while its items travel, so only the last item's trip
and whatever holds the proxy back shows). Any of the five missing (a
program before PR 59) gives nothing."""

from benchmark.harness import load_module
from benchmark.stats import timer_mean

LAYER, UNIT, SOURCE = "serve ingress", "ms", "program_span"

NAMED = ("end_handoff_mean_ms", "end_transit_mean_ms")
WAY_IN = ("serve_http_dispatch_ms", "serve_queue_wait_ms")


def reduce(run: dict):
    def read(name):
        return load_module("layer_metrics", name).reduce(run)

    overhead = read("ingress_overhead_req_mean_ms")
    parts = [read(name) for name in NAMED] + [
        timer_mean(run.get("engine_timers"), name) for name in WAY_IN
    ]
    if overhead is None or None in parts:
        return None
    return overhead - sum(parts)
