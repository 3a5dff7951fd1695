"""90th percentile of due-to-first-token over the window's requests.
With 58 requests in a window it lies between the sixth and seventh
largest sample and spread by 12 to 19 % between seeds (PERF.md section
2). Recorded, not judged, like the median beside it."""

from benchmark.stats import percentile, ttfts_ms

LAYER, UNIT, SOURCE = "client", "ms", "host_clock"


def reduce(run: dict):
    if run.get("loop") != "open":
        return None
    return percentile(ttfts_ms(run["requests"]), 90.0)
