"""How late the open-loop generator sent the window's requests (sent
minus due), 99th percentile. A late generator flatters time to first
token."""

from benchmark.stats import in_window, lateness_ms, percentile

LAYER, UNIT, SOURCE = "client", "ms", "host_clock"


def reduce(run: dict):
    if run.get("loop") != "open":
        return None
    sent = [r for r in in_window(run["requests"]) if "sent_s" in r]
    return percentile(
        lateness_ms([r["due_s"] for r in sent], [r["sent_s"] for r in sent]),
        99.0,
    )
