"""Requests the router's admission control refused (HTTP 503), as a
share of the requests attempted."""

LAYER, UNIT, SOURCE = "serve ingress", "%", "program_counter"


def reduce(run: dict):
    requests = run.get("requests")
    if not requests:
        return None
    shed = sum(1 for r in requests if r["status"] == 503)
    return 100.0 * shed / len(requests)
