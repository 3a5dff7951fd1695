"""Serve request-path observability.

Reference: python/ray/serve/_private/metrics_utils.py + the request
context module — every request carries an id, and the proxy, router,
replica and multiplex layers each record their segment of its life
into per-deployment histograms:

  serve_http_request_latency_ms   proxy: end-to-end HTTP time
  serve_router_routing_ms         handle: replica selection time
  serve_stream_items,             handle: per stream, the items its
  serve_stream_fetches            consumer received and its requests
  serve_queue_wait_ms             replica: send -> execution start
  serve_request_latency_ms        replica: handler execution time
                                  (a stream's: the generator's first
                                  advance to its exhaustion)
  serve_model_load_ms             multiplex: model swap (load) time
  serve_requests_total            replica: completions by outcome
  serve_http_requests_total       proxy: completions by status class
  serve_replica_executing         replica: currently-executing gauge

A streamed request's way to its first token is cut at eight
boundaries, each a clock reading where the work happens, one series
per adjacent pair, observed ONCE per request and never per token:

  B0 HTTP request read (proxy)          B4 admitted to a slot (engine)
  B1 replica chosen, call sent (proxy)  B5 first token emitted (engine)
  B2 handler starts (replica)           B6 first item handed to the
  B3 engine.submit() (replica)             transport (replica)
                                        B7 first body bytes written
                                           (proxy)

  serve_http_dispatch_ms          B0->B1 proxy: route, body, handle,
                                  replica choice (serve_router_routing_ms
                                  lies inside it), the context
  serve_queue_wait_ms             B1->B2 (above)
  serve_handler_submit_ms         B2->B3 replica: the handler before the
                                  engine has the request (a replica's
                                  first request: the model's load)
  engine.stats() admitted,        B3->B4 engine: the wait for a slot
  admit_wait_ms_total
  engine.stats() first_tokens,    B4->B5 engine: admission to first
  prefill_ms_total                token, the chunks and the decode steps
                                  between them (B3->B5 stays
                                  serve_engine_ttft_ms)
  serve_first_item_handoff_ms     B5->B6 replica: loop thread's put to
                                  the handler thread's first yield
  serve_first_item_transit_ms     B6->B7 the first item's way through the
                                  head daemon to the proxy's socket
                                  (epoch clocks of two processes:
                                  approximate across hosts, as
                                  serve_queue_wait_ms is)
  serve_http_first_byte_ms        B0->B7 proxy: the program's own time
                                  to first token

A streamed request's END continues them with three more, and what
they are for is one identity per request: its time above the replica.

  E0 the handler's generator is exhausted (replica; its perf_counter
     for the handler's own duration B2->E0, the epoch beside it)
  E1 the run's end is handed to the transport (the replica's worker,
     `_collect_returns`, where `stream_end` is notified)
  E2 the last body bytes are written and flushed (proxy)

  serve_ingress_overhead_ms       (B0->E2 on the proxy's clock) less
                                  (B2->E0 on the replica's): the SAME
                                  request on both sides of the minus,
                                  observed by the proxy, which the
                                  stream's end tells the handler's
                                  duration (`handler_ms`)
  serve_stream_end_handoff_ms     E0->E1 the replica's interpreter
  serve_stream_end_transit_ms     E1->E2 head daemon, parked fetch, the
                                  proxy's last write (epoch clocks of
                                  two processes, as
                                  serve_first_item_transit_ms)

All three are the proxy's, once per streamed request that ended
cleanly; E0 and E1 reach it as optional keys of the stream's end
(`_private/worker.py` note_stream_end, stream_runs.STREAM_END_NOTE),
which no other stream sets. What the overhead holds beyond the way in
(serve_http_dispatch_ms + serve_queue_wait_ms) and the end (handoff +
transit) is the tokens' way out: every item's trip behind the first.

The same readings ride the request's three spans as attributes
(`serve.http` first_byte_ms, ingress_overhead_ms; `serve.handle`
queue_wait_ms, submit_ms, first_item_ms; `engine.request`
first_token_ms, queue_cause_ms), each counted from its span's start.

All ride the existing metrics pipe (util/metrics) to the head, so
they show up in `metrics_summary()`, the Prometheus endpoint and the
time-series ring with {app, deployment, ...} labels; the flight
recorder additionally keeps the most recent requests per process
(kinds ``serve.http`` / ``serve.handle`` / ``serve.model_load``)
with the request id, so `ray_tpu doctor`'s ring digests show serve
traffic next to RPC traffic.

Request ids: the proxy honors an incoming ``x-request-id`` header or
mints one; bare handle calls mint one per request. The id propagates
proxy -> router -> replica -> multiplex via the request-context dict
the router ships with every replica call, and comes back to HTTP
callers as the ``x-request-id`` response header.

Kill switch: ``RT_serve_request_metrics_enabled=0`` disables every
histogram/counter observation on this process (the request-path
analog of ``RT_flight_recorder_enabled``); request ids still
propagate — they cost one uuid per request and make error logs
correlatable even with metrics off.
"""

from __future__ import annotations

import contextvars
import os
import threading
import time
import uuid
from typing import Dict, Optional

__all__ = [
    "REQUEST_ID_HEADER",
    "new_request_id",
    "new_request_context",
    "request_context",
    "current_request_context",
    "get_request_id",
    "reset_request_context",
    "observe_http",
    "observe_routing",
    "observe_http_dispatch",
    "observe_http_first_byte",
    "observe_http_stream_end",
    "observe_queue_wait",
    "observe_handler_submit",
    "observe_first_item",
    "observe_handler",
    "observe_model_load",
    "replica_executing",
    "observe_engine_step",
    "observe_engine_prefill",
    "observe_engine_prefix",
    "observe_engine_ttft",
    "deployment_snapshot",
]

REQUEST_ID_HEADER = "x-request-id"

#: Latency bucket boundaries (ms) shared by every serve histogram:
#: sub-ms RPC floors through multi-second model loads.
LATENCY_BUCKETS_MS = (
    1.0, 2.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
    1000.0, 2500.0, 5000.0, 10000.0, 30000.0,
)


def _enabled() -> bool:
    raw = os.environ.get("RT_serve_request_metrics_enabled", "1")
    return raw.lower() in ("1", "true", "yes")


_ENABLED = _enabled()

#: Replica-side context of the request being handled; multiplex reads
#: it for model-load attribution, user code may read the request id.
_request_ctx: contextvars.ContextVar = contextvars.ContextVar(
    "serve_request_context", default=None
)


def new_request_id() -> str:
    return uuid.uuid4().hex[:16]


def new_request_context(
    app: str,
    deployment: str,
    request_id: Optional[str] = None,
    trace: Optional[dict] = None,
) -> dict:
    """The dict the router ships with every replica call: identity +
    the send timestamp the replica turns into queue wait."""
    ctx = {
        "request_id": request_id or new_request_id(),
        "app": app,
        "deployment": deployment,
        "sent_ts": time.time(),
    }
    if trace:
        ctx["trace"] = trace
    return ctx


def request_context(ctx: Optional[dict]):
    """Set the replica-side request context; returns the reset
    token."""
    return _request_ctx.set(ctx)


def reset_request_context(token) -> None:
    _request_ctx.reset(token)


def current_request_context() -> Optional[dict]:
    """Context of the request being handled (replica-side); None
    outside a serve request."""
    return _request_ctx.get()


def get_request_id() -> str:
    """Id of the serve request being handled (usable in user handler
    code for log correlation); "" outside a serve request."""
    ctx = _request_ctx.get()
    return str(ctx.get("request_id", "")) if ctx else ""


# ---------------------------------------------------------------------
# lazy metric singletons (one instance per (name) per process; the
# metrics pipe batches records, so per-request cost is a tuple append)
# ---------------------------------------------------------------------

_metrics_lock = threading.Lock()
_metrics: Dict[str, object] = {}


def _histogram(
    name: str, description: str, tag_keys: tuple, boundaries=None
):
    from ..util.metrics import Histogram

    with _metrics_lock:
        metric = _metrics.get(name)
        if metric is None:
            metric = _metrics[name] = Histogram(
                name,
                description=description,
                boundaries=boundaries or LATENCY_BUCKETS_MS,
                tag_keys=tag_keys,
            )
    return metric


def _counter(name: str, description: str, tag_keys: tuple):
    from ..util.metrics import Counter

    with _metrics_lock:
        metric = _metrics.get(name)
        if metric is None:
            metric = _metrics[name] = Counter(
                name, description=description, tag_keys=tag_keys
            )
    return metric


def _gauge(name: str, description: str, tag_keys: tuple):
    from ..util.metrics import Gauge

    with _metrics_lock:
        metric = _metrics.get(name)
        if metric is None:
            metric = _metrics[name] = Gauge(
                name, description=description, tag_keys=tag_keys
            )
    return metric


def _fr(kind: str, name: str, dur_ms: float, extra: dict) -> None:
    from .._private.flight_recorder import record

    record(kind, name, dur_ms, extra)


# ---------------------------------------------------------------------
# observation hooks (each guarded: observability must never fail a
# request)
# ---------------------------------------------------------------------

def observe_http(
    app: str,
    deployment: str,
    route: str,
    status: int,
    dur_ms: float,
    request_id: str,
) -> None:
    """Proxy: one completed HTTP request (end-to-end, queueing and
    streaming included)."""
    if not _ENABLED:
        return
    try:
        tags = {"app": app, "deployment": deployment}
        _histogram(
            "serve_http_request_latency_ms",
            "End-to-end HTTP request latency at the ingress proxy",
            ("app", "deployment"),
        ).observe(dur_ms, tags=tags)
        _counter(
            "serve_http_requests_total",
            "HTTP requests completed at the ingress proxy",
            ("app", "deployment", "status"),
        ).inc(1.0, tags={**tags, "status": f"{int(status) // 100}xx"})
        _fr(
            "serve.http",
            f"{app}/{deployment}{route}",
            dur_ms,
            {
                "request_id": request_id,
                "status": int(status),
                "error": int(status) >= 500,
            },
        )
    except Exception:
        pass


def observe_routing(app: str, deployment: str, dur_ms: float) -> None:
    """Handle: time spent choosing a replica (includes any wait for
    replica membership to appear)."""
    if not _ENABLED:
        return
    try:
        _histogram(
            "serve_router_routing_ms",
            "Replica selection time in the router",
            ("app", "deployment"),
        ).observe(dur_ms, tags={"app": app, "deployment": deployment})
    except Exception:
        pass


def _observe_stage(
    name: str, description: str, app: str, deployment: str, dur_ms: float
) -> None:
    """One reading of a request's stage, clamped at 0 where two
    processes' clocks meet; the callers see to `_ENABLED` and, for the
    first-token stages, to once per streamed request."""
    try:
        _histogram(name, description, ("app", "deployment")).observe(
            max(0.0, dur_ms),
            tags={"app": app, "deployment": deployment},
        )
    except Exception:
        pass


def observe_http_dispatch(
    app: str, deployment: str, dur_ms: float
) -> None:
    """Proxy, B0->B1: HTTP request read to the replica call sent
    (route refresh and match, body read, the handle, replica choice,
    the context). Streamed requests only."""
    if not _ENABLED:
        return
    _observe_stage(
        "serve_http_dispatch_ms",
        "HTTP request read to replica call sent, per streamed request",
        app, deployment, dur_ms,
    )


def observe_http_first_byte(
    app: str,
    deployment: str,
    first_byte_ms: float,
    first_item_ts: Optional[float],
) -> None:
    """Proxy, at the first body bytes of a streamed response (B7):
    B0->B7, the program's own time to first token, onto the series
    and the `serve.http` span; and B6->B7 from the epoch stamp the
    producer put on the stream's first item (None where the transport
    carried none: nothing observed)."""
    if not _ENABLED:
        return
    from ..util.tracing import add_span_attributes

    now = time.time()  # B7 on the epoch clock, beside the caller's reading
    _observe_stage(
        "serve_http_first_byte_ms",
        "HTTP request read to first body bytes written, per streamed "
        "request",
        app, deployment, first_byte_ms,
    )
    if first_item_ts is not None:
        _observe_stage(
            "serve_first_item_transit_ms",
            "First stream item handed to the transport to its bytes "
            "written by the proxy, per streamed request",
            app, deployment, (now - first_item_ts) * 1e3,
        )
    add_span_attributes(first_byte_ms=round(first_byte_ms, 3))


def observe_http_stream_end(
    app: str, deployment: str, total_ms: float, note: Optional[dict]
) -> None:
    """Proxy, at the last body bytes of a streamed response (E2):
    the request's time above the replica, `total_ms` (B0->E2, the
    caller's reading) less the handler's own duration that the
    stream's end carried back, onto the series and the `serve.http`
    span; and the end's two stages from the epochs beside it. A
    stream whose end carried no note (its producer stamped none)
    observes nothing."""
    if not _ENABLED or not note or "handler_ms" not in note:
        return
    from ..util.tracing import add_span_attributes

    now = time.time()  # E2 on the epoch clock, beside the caller's reading
    overhead_ms = total_ms - float(note["handler_ms"])
    _observe_stage(
        "serve_ingress_overhead_ms",
        "A streamed request's time in the proxy less its handler's "
        "time in the replica, per request",
        app, deployment, overhead_ms,
    )
    exhausted_ts, end_ts = note.get("exhausted_ts"), note.get("end_ts")
    if exhausted_ts is not None and end_ts is not None:
        _observe_stage(
            "serve_stream_end_handoff_ms",
            "Handler generator exhausted to the stream's end handed to "
            "the transport, per streamed request",
            app, deployment, (end_ts - exhausted_ts) * 1e3,
        )
        _observe_stage(
            "serve_stream_end_transit_ms",
            "Stream's end handed to the transport to the proxy's last "
            "bytes written, per streamed request",
            app, deployment, (now - end_ts) * 1e3,
        )
    add_span_attributes(ingress_overhead_ms=round(overhead_ms, 3))


def observe_stream(
    app: str, deployment: str, items: int, fetches: int
) -> None:
    """Handle: one stream is over; what its consumer counted of the
    transport. The sums of the two series are cumulative items and
    fetches: their ratio is 1.0 where consumers keep up with their
    producers and more where tokens wait for them."""
    if not _ENABLED:
        return
    try:
        tags = {"app": app, "deployment": deployment}
        _histogram(
            "serve_stream_items",
            "Items a stream's consumer received, per stream",
            ("app", "deployment"),
        ).observe(float(items), tags=tags)
        _histogram(
            "serve_stream_fetches",
            "Requests a stream's consumer made for its items, per stream",
            ("app", "deployment"),
        ).observe(float(fetches), tags=tags)
    except Exception:
        pass


def observe_queue_wait(
    app: str, deployment: str, dur_ms: float
) -> None:
    """Replica: router send -> handler start (actor mailbox + wire
    time; cross-host clock skew makes this approximate off-box)."""
    if not _ENABLED:
        return
    _observe_stage(
        "serve_queue_wait_ms",
        "Router-send to handler-start wait per request",
        app, deployment, dur_ms,
    )


def observe_handler_submit(submitted_ts: float) -> None:
    """Replica, B2->B3: the streaming handler's start (the reading the
    replica left in the request context, its `perf_counter`) to
    `engine.submit()`. The context keeps the result, so a handler that
    submits twice observes once; outside a streamed serve request
    there is no reading and nothing is observed."""
    ctx = _request_ctx.get()
    if not _ENABLED or not ctx or "submit_ms" in ctx:
        return
    started = ctx.get("handler_started_ts")
    if started is None:
        return
    from ..util.tracing import add_span_attributes

    dur_ms = ctx["submit_ms"] = (submitted_ts - started) * 1e3
    _observe_stage(
        "serve_handler_submit_ms",
        "Handler start to engine.submit(), per streamed request",
        str(ctx.get("app", "")), str(ctx.get("deployment", "")), dur_ms,
    )
    add_span_attributes(submit_ms=round(dur_ms, 3))


def observe_first_item(first_token_ts: Optional[float]) -> None:
    """Replica handler, at its first token (B6), before the yield
    that hands it to the transport: B5->B6 from the engine loop's
    reading of the first token (same interpreter, same clock), and
    the `serve.handle` span's `first_item_ms`, counted from the
    handler's start."""
    ctx = _request_ctx.get()
    if not _ENABLED or not ctx or first_token_ts is None:
        return
    from ..util.tracing import add_span_attributes

    now = time.perf_counter()
    _observe_stage(
        "serve_first_item_handoff_ms",
        "Engine loop's first token to the handler's first yield, per "
        "streamed request",
        str(ctx.get("app", "")), str(ctx.get("deployment", "")),
        (now - first_token_ts) * 1e3,
    )
    started = ctx.get("handler_started_ts")
    if started is not None:
        add_span_attributes(first_item_ms=round((now - started) * 1e3, 3))


def observe_handler(
    app: str,
    deployment: str,
    method: str,
    dur_ms: float,
    error: bool,
    request_id: str = "",
) -> None:
    """Replica: handler execution time + outcome counter."""
    if not _ENABLED:
        return
    try:
        tags = {"app": app, "deployment": deployment}
        _histogram(
            "serve_request_latency_ms",
            "Handler execution latency per deployment",
            ("app", "deployment"),
        ).observe(dur_ms, tags=tags)
        _counter(
            "serve_requests_total",
            "Requests completed by replicas, by outcome",
            ("app", "deployment", "method", "outcome"),
        ).inc(
            1.0,
            tags={
                **tags,
                "method": method,
                "outcome": "error" if error else "ok",
            },
        )
        _fr(
            "serve.handle",
            f"{app}/{deployment}.{method}",
            dur_ms,
            {"request_id": request_id, "error": bool(error)},
        )
    except Exception:
        pass


def observe_model_load(model_id: str, dur_ms: float) -> None:
    """Multiplex: one model load (LRU miss). Deployment attribution
    comes from the request context the replica set around the call;
    loads outside any request (warmup) land under app=""/deployment=""
    rather than being dropped."""
    if not _ENABLED:
        return
    try:
        ctx = current_request_context() or {}
        app = str(ctx.get("app", ""))
        deployment = str(ctx.get("deployment", ""))
        _histogram(
            "serve_model_load_ms",
            "Multiplexed model load (swap) time per deployment",
            ("app", "deployment"),
        ).observe(dur_ms, tags={"app": app, "deployment": deployment})
        _fr(
            "serve.model_load",
            model_id,
            dur_ms,
            {
                "request_id": str(ctx.get("request_id", "")),
                "deployment": f"{app}/{deployment}",
            },
        )
    except Exception:
        pass


#: Throttle for the executing gauge: one gauge record per replica per
#: change is fine at test scale but pure overhead at bench rates —
#: zero-crossing edges (0 <-> nonzero, both directions) ALWAYS push;
#: same-sign updates are limited to one per period.
_GAUGE_MIN_INTERVAL_S = 0.1
_gauge_last: Dict[tuple, tuple] = {}  # key -> (ts, value)


def replica_executing(
    app: str, deployment: str, replica_id: str, executing: int
) -> None:
    """Replica: currently-executing request count. Tagged by replica
    so concurrent replicas of one deployment don't overwrite each
    other's gauge — consumers sum across the replica label."""
    if not _ENABLED:
        return
    try:
        key = (app, deployment, replica_id)
        now = time.monotonic()
        last_ts, last_value = _gauge_last.get(key, (0.0, -1))
        edge = (executing == 0) != (last_value == 0)
        if not edge and now - last_ts < _GAUGE_MIN_INTERVAL_S:
            return
        _gauge_last[key] = (now, executing)
        _gauge(
            "serve_replica_executing",
            "Requests currently executing on a replica",
            ("app", "deployment", "replica"),
        ).set(
            float(executing),
            tags={
                "app": app,
                "deployment": deployment,
                "replica": replica_id,
            },
        )
    except Exception:
        pass


# ---------------------------------------------------------------------
# continuous-batching engine (ray_tpu/llm): per-iteration decode and
# prefill timing, slot occupancy, token throughput. Tagged by model
# FAMILY on top of app/deployment — one engine per multiplexed family,
# so family series are the per-family slot accounting. Names ride the
# normal metrics pipe: labeled series on /metrics, folded per
# deployment into /api/serve by deployment_snapshot below.
# ---------------------------------------------------------------------

ENGINE_TAGS = ("app", "deployment", "family")

#: Why an engine's waiting queue stands, in the order the cause of its
#: FIFO head is decided (llm/engine.py's module docstring; here
#: because the gauge that carries it is folded in processes that
#: import no engine).
QUEUE_CAUSES = (
    "no_slot", "behind_prefill", "no_pages", "no_window_pages",
    "no_state_slots", "admissible",
)

#: Decode-batch-size bucket boundaries (requests per step).
BATCH_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0)


def _engine_histogram(name: str, description: str, boundaries=None):
    return _histogram(
        name, description, ENGINE_TAGS, boundaries=boundaries
    )


def observe_engine_step(
    tags: Dict[str, str],
    step_ms: float,
    batch: int,
    tokens: int,
    slots_used: int,
    slots_total: int,
    waiting: int,
    kv_used: Optional[int] = None,
    kv_total: Optional[int] = None,
    queue_cause: Optional[str] = None,
) -> None:
    """Engine: one decode iteration over the slot batch."""
    if not _ENABLED:
        return
    try:
        _engine_histogram(
            "serve_engine_decode_step_ms",
            "One decode step over the engine's slot batch",
        ).observe(step_ms, tags=tags)
        _engine_histogram(
            "serve_engine_step_batch",
            "Sequences decoded per engine step (batch size)",
            boundaries=BATCH_BUCKETS,
        ).observe(float(batch), tags=tags)
        if tokens:
            _counter(
                "serve_engine_tokens_total",
                "Tokens sampled by the engine's decode loop",
                ENGINE_TAGS,
            ).inc(float(tokens), tags=tags)
        _engine_gauges(
            tags, slots_used, slots_total, waiting,
            kv_used, kv_total, queue_cause,
        )
    except Exception:
        pass


def observe_engine_prefill(tags: Dict[str, str], chunk_ms: float) -> None:
    """Engine: one prefill chunk (interleaved with decode steps)."""
    if not _ENABLED:
        return
    try:
        _engine_histogram(
            "serve_engine_prefill_chunk_ms",
            "One prefill chunk forward in the engine",
        ).observe(chunk_ms, tags=tags)
    except Exception:
        pass


def observe_engine_prefix(
    tags: Dict[str, str], skip_tokens: int
) -> None:
    """Engine: one admission's prefix-cache outcome. A HIT means the
    request skipped `skip_tokens` of prefill by pinning pooled blocks
    (hit-rate = hits / (hits + misses) over the counters)."""
    if not _ENABLED:
        return
    try:
        name = (
            "serve_engine_prefix_hits_total"
            if skip_tokens
            else "serve_engine_prefix_misses_total"
        )
        _counter(
            name,
            "Engine admissions whose prompt prefix "
            + ("hit" if skip_tokens else "missed")
            + " the paged KV prefix cache",
            ENGINE_TAGS,
        ).inc(1.0, tags=tags)
    except Exception:
        pass


def observe_engine_ttft(tags: Dict[str, str], ttft_ms: float) -> None:
    """Engine: submit -> first sampled token for one request."""
    if not _ENABLED:
        return
    try:
        _engine_histogram(
            "serve_engine_ttft_ms",
            "Engine-side time to first token per request",
        ).observe(ttft_ms, tags=tags)
    except Exception:
        pass


def observe_engine_device(
    tags: Dict[str, str], platform: str, device_kind: str, devices: int
) -> None:
    """Engine: the device its programs run on, as JAX reported it
    inside the replica process — an info-style gauge (value = device
    count; platform and kind ride as labels) so `/api/serve` can name
    what every engine number was taken on."""
    if not _ENABLED:
        return
    try:
        _gauge(
            "serve_engine_devices",
            "Devices visible to the engine process, labeled with the "
            "platform and device kind JAX reports",
            ENGINE_TAGS + ("platform", "device_kind"),
        ).set(
            float(devices),
            tags={
                **tags, "platform": platform, "device_kind": device_kind,
            },
        )
    except Exception:
        pass


def observe_engine_occupancy(
    tags: Dict[str, str],
    slots_used: int,
    slots_total: int,
    waiting: int,
    kv_used: Optional[int] = None,
    kv_total: Optional[int] = None,
    queue_cause: Optional[str] = None,
) -> None:
    """Engine: occupancy push OUTSIDE the decode step — cancellation,
    request retirement, and engine unload all free slots (and unpin
    KV blocks) without a following step, and the gauges must not
    report phantom occupancy until the next request arrives."""
    if not _ENABLED:
        return
    try:
        _engine_gauges(
            tags, slots_used, slots_total, waiting,
            kv_used, kv_total, queue_cause,
        )
    except Exception:
        pass


def _queue_cause_code(cause: Optional[str]) -> int:
    return QUEUE_CAUSES.index(cause) + 1 if cause in QUEUE_CAUSES else 0


def _engine_gauges(
    tags: Dict[str, str],
    slots_used: int,
    slots_total: int,
    waiting: int,
    kv_used: Optional[int] = None,
    kv_total: Optional[int] = None,
    queue_cause: Optional[str] = None,
) -> None:
    """Slot-occupancy + KV-block gauges, throttled like
    replica_executing: zero-crossing edges always push, same-sign
    updates at most one per period per engine."""
    key = ("engine", tags.get("app", ""), tags.get("deployment", ""),
           tags.get("family", ""))
    now = time.monotonic()
    last_ts, last_value = _gauge_last.get(key, (0.0, -1))
    edge = (slots_used == 0) != (last_value == 0)
    if not edge and now - last_ts < _GAUGE_MIN_INTERVAL_S:
        return
    _gauge_last[key] = (now, slots_used)
    series = [
        (
            "serve_engine_slots_used",
            "KV slots occupied by decoding sequences",
            slots_used,
        ),
        (
            "serve_engine_slots_total",
            "KV slots provisioned in the engine",
            slots_total,
        ),
        (
            "serve_engine_waiting",
            "Requests queued for a free engine slot",
            waiting,
        ),
        (
            "serve_engine_queue_cause",
            "Why the engine's queue stands: 0 while nobody waits, else "
            "1 + the cause's place in QUEUE_CAUSES",
            _queue_cause_code(queue_cause),
        ),
    ]
    if kv_used is not None:
        series.append((
            "serve_engine_kv_blocks_used",
            "Paged-KV blocks pinned by live requests",
            kv_used,
        ))
    if kv_total is not None:
        series.append((
            "serve_engine_kv_blocks_total",
            "Paged-KV blocks provisioned in the engine's pool",
            kv_total,
        ))
    for name, desc, value in series:
        _gauge(name, desc, ENGINE_TAGS).set(
            float(value), tags=tags
        )


# ---------------------------------------------------------------------
# read side: fold the head's metric table into per-deployment rows
# ---------------------------------------------------------------------

def _tag_dict(flat: str) -> Dict[str, str]:
    out: Dict[str, str] = {}
    if not flat:
        return out
    for part in flat.split("|"):
        key, _, value = part.partition("=")
        out[key] = value
    return out


def deployment_snapshot(summary: Dict[str, dict]) -> Dict[tuple, dict]:
    """Fold a `metrics_summary()` mapping into {(app, deployment):
    {p50_ms, p99_ms, requests_total, errors_total, executing, ...}} —
    the request-path half of `serve.status()` / `/api/serve`."""
    out: Dict[tuple, dict] = {}

    def row(tags: Dict[str, str]) -> Optional[dict]:
        app = tags.get("app")
        deployment = tags.get("deployment")
        if app is None or deployment is None or not deployment:
            return None
        return out.setdefault(
            (app, deployment),
            {
                "requests_total": 0.0,
                "errors_total": 0.0,
                "executing": 0.0,
            },
        )

    latency = summary.get("serve_request_latency_ms", {})
    for flat, series in (latency.get("by_tags") or {}).items():
        target = row(_tag_dict(flat))
        if target is None:
            continue
        for stat in ("p50", "p99"):
            if stat in series:
                target[f"{stat}_ms"] = series[stat]
        target["mean_ms"] = round(
            series.get("sum", 0.0) / series["count"], 3
        ) if series.get("count") else 0.0

    counts = summary.get("serve_requests_total", {})
    for flat, series in (counts.get("by_tags") or {}).items():
        tags = _tag_dict(flat)
        target = row(tags)
        if target is None:
            continue
        total = float(series.get("total", 0.0) or 0.0)
        target["requests_total"] += total
        if tags.get("outcome") == "error":
            target["errors_total"] += total

    executing = summary.get("serve_replica_executing", {})
    for flat, series in (executing.get("by_tags") or {}).items():
        target = row(_tag_dict(flat))
        if target is None:
            continue
        target["executing"] += float(series.get("value", 0.0) or 0.0)

    queue_wait = summary.get("serve_queue_wait_ms", {})
    for flat, series in (queue_wait.get("by_tags") or {}).items():
        target = row(_tag_dict(flat))
        if target is None or not series.get("count"):
            continue
        target["queue_wait_p50_ms"] = series.get("p50", 0.0)

    model_load = summary.get("serve_model_load_ms", {})
    for flat, series in (model_load.get("by_tags") or {}).items():
        target = row(_tag_dict(flat))
        if target is None or not series.get("count"):
            continue
        target["model_loads"] = series.get("count", 0)
        target["model_load_p50_ms"] = series.get("p50", 0.0)

    _fold_engine(summary, row, out)
    return out


def _fold_engine(summary: Dict[str, dict], row, out) -> None:
    """Continuous-batching engine series -> per-deployment rows: a
    nested per-family breakdown plus summed top-level occupancy (the
    at-a-glance numbers `/api/serve` and `serve.status()` show)."""

    def family_row(tags: Dict[str, str]) -> Optional[dict]:
        target = row(tags)
        if target is None:
            return None
        families = target.setdefault("engine", {})
        return families.setdefault(tags.get("family", "default"), {})

    def fold(metric: str, fn) -> None:
        for flat, series in (
            summary.get(metric, {}).get("by_tags") or {}
        ).items():
            tags = _tag_dict(flat)
            target = family_row(tags)
            if target is not None:
                fn(target, series)

    fold(
        "serve_engine_slots_used",
        lambda t, s: t.__setitem__(
            "slots_used", float(s.get("value", 0.0) or 0.0)
        ),
    )
    fold(
        "serve_engine_slots_total",
        lambda t, s: t.__setitem__(
            "slots_total", float(s.get("value", 0.0) or 0.0)
        ),
    )
    fold(
        "serve_engine_waiting",
        lambda t, s: t.__setitem__(
            "waiting", float(s.get("value", 0.0) or 0.0)
        ),
    )

    def queue_cause(target: dict, series: dict) -> None:
        code = int(series.get("value", 0.0) or 0.0)
        if 0 < code <= len(QUEUE_CAUSES):
            target["queue_cause"] = QUEUE_CAUSES[code - 1]

    fold("serve_engine_queue_cause", queue_cause)
    fold(
        "serve_engine_tokens_total",
        lambda t, s: t.__setitem__(
            "tokens_total", float(s.get("total", 0.0) or 0.0)
        ),
    )
    fold(
        "serve_engine_kv_blocks_used",
        lambda t, s: t.__setitem__(
            "kv_blocks_used", float(s.get("value", 0.0) or 0.0)
        ),
    )
    fold(
        "serve_engine_kv_blocks_total",
        lambda t, s: t.__setitem__(
            "kv_blocks_total", float(s.get("value", 0.0) or 0.0)
        ),
    )
    fold(
        "serve_engine_prefix_hits_total",
        lambda t, s: t.__setitem__(
            "prefix_hits", float(s.get("total", 0.0) or 0.0)
        ),
    )
    fold(
        "serve_engine_prefix_misses_total",
        lambda t, s: t.__setitem__(
            "prefix_misses", float(s.get("total", 0.0) or 0.0)
        ),
    )

    for flat, series in (
        summary.get("serve_engine_devices", {}).get("by_tags") or {}
    ).items():
        tags = _tag_dict(flat)
        target = family_row(tags)
        if target is not None:
            target["platform"] = tags.get("platform", "")
            target["device_kind"] = tags.get("device_kind", "")
            target["devices"] = float(series.get("value", 0.0) or 0.0)

    def histo(target: dict, series: dict, prefix: str) -> None:
        if not series.get("count"):
            return
        target[f"{prefix}_p50"] = series.get("p50", 0.0)
        if "p99" in series:
            target[f"{prefix}_p99"] = series["p99"]

    fold(
        "serve_engine_step_batch",
        lambda t, s: histo(t, s, "batch"),
    )
    fold(
        "serve_engine_decode_step_ms",
        lambda t, s: histo(t, s, "decode_ms"),
    )
    fold(
        "serve_engine_ttft_ms",
        lambda t, s: histo(t, s, "ttft_ms"),
    )

    # Summed top-level occupancy per deployment (families collapse
    # into the at-a-glance columns).
    for target in out.values():
        families = target.get("engine")
        if not families:
            continue
        for key in (
            "slots_used", "slots_total", "waiting", "tokens_total",
            "kv_blocks_used", "kv_blocks_total",
            "prefix_hits", "prefix_misses",
        ):
            target[f"engine_{key}"] = sum(
                f.get(key, 0.0) for f in families.values()
            )
