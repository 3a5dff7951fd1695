"""Mean time from the router's send to the replica handler's start
(B1 -> B2): the call's way through the head daemon and the actor's
mailbox, until a thread of the replica, whose interpreter the engine's
loop shares, takes it. `serve_queue_wait_ms`, observed once per request
by `serve/replica.py` `_begin_request` from the epoch time the router put
in the request context (two processes of one host; approximate across
hosts); [sum, count] deltas of the head's metrics table, window open to
edge. The series is older than its reader (the run has kept it since
PR 25), so a parent of PR 41 reads it too."""

from benchmark.stats import timer_mean

LAYER, UNIT, SOURCE = "serve ingress", "ms", "program_span"


def reduce(run: dict):
    return timer_mean(run.get("engine_timers"), "serve_queue_wait_ms")
