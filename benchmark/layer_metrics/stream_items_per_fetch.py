"""Items a stream's consumer received per request it made for them,
over the streams that ended in the window: the sum of the router's
`serve_stream_items` over the sum of its `serve_stream_fetches` (one
observation of each per stream, at its end, in the process that
consumed it: the proxy), from the head's metrics table. The consumer
of a streaming generator keeps one request parked at the daemon and is
answered with every item appended since: 1.0 where it takes each token
before the next is made (a fetch that brings only the stream's end
pulls it a little under), more where tokens wait for their consumer,
which is then what the stream's way out costs. A program that keeps
neither series (one object, wait and get per token: before PR 34)
gives nothing."""

LAYER, UNIT, SOURCE = "serve ingress", "items/fetch", "program_counter"


def reduce(run: dict):
    timers = run.get("engine_timers")
    if not timers:
        return None
    sums = []
    for name in ("serve_stream_items", "serve_stream_fetches"):
        if name not in timers["after"]:
            return None
        sums.append(
            timers["after"][name][0]
            - timers["before"].get(name, (0.0, 0.0))[0]
        )
    items, fetches = sums
    return items / fetches if fetches > 0 else None
