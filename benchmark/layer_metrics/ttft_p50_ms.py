"""Median of the time from when a request was due to its first
streamed token, over the requests due in the window. Recorded, not
judged: over the twenty requests a window holds at real lengths it
spread by 3 to 9 % between runs of one code (PERF.md section 6), more
than any bound the contract allows can admit. One the client cut
before its first token counts as the wait it had had by then."""

from benchmark.stats import percentile, ttfts_ms

LAYER, UNIT, SOURCE = "client", "ms", "host_clock"


def reduce(run: dict):
    if run.get("loop") != "open":
        return None
    return percentile(ttfts_ms(run["requests"]), 50.0)
