"""Tokens the service delivered inside the window (after it opened at
0 and by its edge), over the window:
the prompt tokens of each request whose first token arrived in the
window (its prefill, or its prefix-cache hit, was done by then) plus
every generated token streamed in the window. Requests that completed
and requests the client cut after the window's edge count; failed
ones do not. Crediting work when it is delivered, and not whole
requests when they end, keeps a window of some tens of requests from
moving by one request's worth at its edge."""

from benchmark.stats import served


def reduce(run: dict):
    requests = run.get("requests")
    if requests is None:
        return None
    window = run["window_s"]
    tokens = 0
    for r in requests:
        if not served(r):
            continue
        if r["token_s"] and 0.0 < r["token_s"][0] <= window:
            tokens += r["n_prompt"]
        tokens += sum(1 for t in r["token_s"] if 0.0 < t <= window)
    return tokens / window
