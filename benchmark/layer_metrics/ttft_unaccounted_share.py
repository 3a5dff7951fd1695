"""What the stage timers leave of the program's time to first token,
in per cent of it: 100 x (server - dispatch - mailbox - handler to
submit - admit wait - prefill - way out) / server, where server is
`ttft_server_mean_ms` (B0 -> B7) and the stages are
`ttft_dispatch_mean_ms` (B0 -> B1), `ttft_mailbox_mean_ms` (B1 -> B2),
the mean of `serve_handler_submit_ms` (B2 -> B3),
`engine_admit_wait_mean_ms` (B3 -> B4), `ttft_prefill_mean_ms` (B4 -> B5)
and `ttft_way_out_mean_ms` (B5 -> B7). The boundaries are adjacent clock
readings, so what is left is the seams between two clocks and the
requests that straddle the window's ends: each mean is over the
requests that crossed ITS boundary in the window, one or two of 65 in
`chat_loaded` differ. Near 0 where the split is whole; a stage without a
timer shows here as a positive share. Any stage missing (a program
before PR 41) gives nothing."""

from benchmark.harness import load_module
from benchmark.stats import timer_mean

LAYER, UNIT, SOURCE = "serve ingress", "%", "program_span"

STAGES = (
    "ttft_dispatch_mean_ms", "ttft_mailbox_mean_ms",
    "engine_admit_wait_mean_ms", "ttft_prefill_mean_ms",
    "ttft_way_out_mean_ms",
)


def reduce(run: dict):
    def read(name):
        return load_module("layer_metrics", name).reduce(run)

    server = read("ttft_server_mean_ms")
    stages = [read(name) for name in STAGES]
    stages.append(
        timer_mean(run.get("engine_timers"), "serve_handler_submit_ms")
    )
    if not server or None in stages:
        return None
    return 100.0 * (server - sum(stages)) / server
