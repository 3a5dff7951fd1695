"""95th percentile of the gaps between streamed tokens that ended in
the window, all requests pooled: the gap a reader sees when a prefill
chunk cuts into the decode batch."""

from benchmark.stats import percentile, pooled_gaps_ms


def reduce(run: dict):
    if run.get("requests") is None:
        return None
    return percentile(pooled_gaps_ms(run["requests"], run["window_s"]), 95.0)
