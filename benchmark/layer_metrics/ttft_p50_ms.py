"""Median of the time from when a request was due to its first
streamed token, over the requests due in the window (not the
lead-in's). Recorded, not judged: over the 58 requests a window of
`chat_loaded` holds it spread by 1.8 to 4.3 % between seeds (PERF.md
section 2), which only the contract's largest bound would admit, and
not safely. One the client cut before its first token counts as the
wait it had had by then."""

from benchmark.stats import percentile, ttfts_ms

LAYER, UNIT, SOURCE = "client", "ms", "host_clock"


def reduce(run: dict):
    if run.get("loop") != "open":
        return None
    return percentile(ttfts_ms(run["requests"]), 50.0)
