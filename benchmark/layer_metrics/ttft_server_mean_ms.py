"""Mean time from the proxy's reading of an HTTP request to the first
body bytes it wrote, over the streamed requests whose first bytes went
out in the window: the program's own time to first token
(`serve_http_first_byte_ms`, observed once per request at the first
`wfile.flush()` of `serve/proxy.py` `_stream_response`; [sum, count]
deltas of the head's metrics table, window open to edge). Beside the
client's `ttft_p50_ms` it differs by the client's socket and loopback
and by what the client counts from (when the request was DUE, not when
the proxy read it). The stage readers `ttft_dispatch_mean_ms`,
`ttft_mailbox_mean_ms`, `ttft_prefill_mean_ms`, `ttft_way_out_mean_ms`
(with `engine_admit_wait_mean_ms` and the handler's
`serve_handler_submit_ms`) split it; `ttft_unaccounted_share` says what
they leave. A program without the series (before PR 41) gives
nothing."""

from benchmark.stats import timer_mean

LAYER, UNIT, SOURCE = "serve ingress", "ms", "program_span"


def reduce(run: dict):
    return timer_mean(run.get("engine_timers"), "serve_http_first_byte_ms")
