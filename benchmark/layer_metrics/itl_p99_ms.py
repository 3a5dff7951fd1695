"""99th percentile of the gaps between streamed tokens that ended in
the window, pooled: the stragglers beyond the decode-step-plus-chunk
plateau that `itl_p95_ms` sits on. Recorded, not judged."""

from benchmark.stats import percentile, pooled_gaps_ms

LAYER, UNIT, SOURCE = "client", "ms", "host_clock"


def reduce(run: dict):
    if run.get("requests") is None:
        return None
    return percentile(pooled_gaps_ms(run["requests"], run["window_s"]), 99.0)
