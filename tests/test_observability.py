"""Observability tests (reference test models: metric export tests,
ray.timeline chrome trace, dashboard HTTP API)."""

import json
import time
import urllib.request

import pytest


def test_metrics_counter_gauge_histogram(rt_session):
    rt = rt_session
    from ray_tpu.util.metrics import (
        Counter,
        Gauge,
        Histogram,
        metrics_summary,
    )

    requests = Counter("app_requests", tag_keys=("route",))
    temperature = Gauge("app_temperature")
    latency = Histogram("app_latency_ms")

    requests.inc(1, tags={"route": "a"})
    requests.inc(2, tags={"route": "b"})
    temperature.set(21.5)
    for v in (5.0, 10.0, 15.0):
        latency.observe(v)

    deadline = time.time() + 10
    while time.time() < deadline:
        metrics = metrics_summary()
        if "app_requests" in metrics and metrics["app_requests"].get(
            "total"
        ) == 3.0 and metrics.get("app_latency_ms", {}).get("count") == 3:
            break
        time.sleep(0.2)
    metrics = metrics_summary()
    assert metrics["app_requests"]["total"] == 3.0
    assert metrics["app_requests"]["by_tags"]["route=b"]["total"] == 2.0
    assert metrics["app_temperature"]["value"] == 21.5
    hist = metrics["app_latency_ms"]
    assert hist["count"] == 3 and hist["sum"] == 30.0
    assert hist["min"] == 5.0 and hist["max"] == 15.0


def test_metrics_from_tasks(rt_session):
    rt = rt_session
    from ray_tpu.util.metrics import Counter, metrics_summary

    @rt.remote
    def work(i):
        from ray_tpu.util.metrics import Counter, flush

        Counter("task_side_counter").inc(1)
        flush()
        return i

    rt.get([work.remote(i) for i in range(5)], timeout=60)
    deadline = time.time() + 10
    while time.time() < deadline:
        metrics = metrics_summary()
        if metrics.get("task_side_counter", {}).get("total") == 5.0:
            break
        time.sleep(0.2)
    assert metrics_summary()["task_side_counter"]["total"] == 5.0


def test_chrome_trace_export(rt_session, tmp_path):
    rt = rt_session
    from ray_tpu.util.tracing import export_timeline

    @rt.remote
    def traced(x):
        return x + 1

    rt.get([traced.remote(i) for i in range(3)], timeout=30)
    path = str(tmp_path / "trace.json")
    trace = export_timeline(path)
    assert len(trace) >= 3
    with open(path) as f:
        loaded = json.load(f)
    slices = [e for e in loaded if e["name"] == "traced"]
    assert len(slices) == 3
    for event in slices:
        assert event["ph"] == "X" and event["dur"] >= 1


def test_timeline_slice_excludes_queue_time():
    """The chrome slice runs from the first RUNNING-adjacent state to
    the terminal state; queue time (PENDING_*/FORWARDED) is reported
    as args.queued_us, not billed as runtime (satellite fix: the dead
    _BEGIN_STATES/_END_STATES are now load-bearing)."""
    from ray_tpu.util.tracing import timeline_to_chrome_trace

    t0 = 1000.0
    events = [
        {
            "task_id": "t1",
            "name": "queued_task",
            "kind": "normal",
            "state": state,
            "time": t0 + dt,
        }
        for state, dt in (
            ("PENDING_NODE_ASSIGNMENT", 0.0),
            ("FORWARDED", 2.0),
            ("RUNNING", 5.0),
            ("FINISHED", 6.0),
        )
    ]
    (slice_,) = timeline_to_chrome_trace(events)
    assert slice_["ts"] == pytest.approx((t0 + 5.0) * 1e6)
    assert slice_["dur"] == pytest.approx(1e6)
    assert slice_["args"]["queued_us"] == pytest.approx(5e6)
    assert slice_["args"]["final_state"] == "FINISHED"

    # A task with only queued states (never ran) still gets a slice —
    # a 1 us marker at submission with the whole span reported as
    # queue time, so none of it reads as execution.
    (queued_only,) = timeline_to_chrome_trace(events[:2])
    assert queued_only["ts"] == pytest.approx(t0 * 1e6)
    assert queued_only["dur"] == pytest.approx(1.0)
    assert queued_only["args"]["queued_us"] == pytest.approx(2e6)
    assert queued_only["args"]["final_state"] == "FORWARDED"


def test_timeline_retry_splits_into_attempts():
    """A re-queue transition (RETRY/RECONSTRUCTING) splits the task
    into per-attempt slices: the reschedule wait must be billed as
    that attempt's queue time, never as runtime."""
    from ray_tpu.util.tracing import timeline_to_chrome_trace

    t0 = 1000.0
    events = [
        {
            "task_id": "t1",
            "name": "retried",
            "kind": "normal",
            "state": state,
            "time": t0 + dt,
        }
        for state, dt in (
            ("PENDING_NODE_ASSIGNMENT", 0.0),
            ("RUNNING", 1.0),
            ("RETRY", 2.0),
            ("FORWARDED", 3.0),
            ("RUNNING", 62.0),
            ("FINISHED", 63.0),
        )
    ]
    first, second = timeline_to_chrome_trace(events)
    # Attempt 1: ran 1s (RUNNING@1 -> RETRY@2 closes the attempt).
    assert first["ts"] == pytest.approx((t0 + 1.0) * 1e6)
    assert first["dur"] == pytest.approx(1e6)
    assert first["args"]["attempt"] == 1
    # Attempt 2: the 60s reschedule wait is queue time, runtime is
    # the 1s second execution.
    assert second["ts"] == pytest.approx((t0 + 62.0) * 1e6)
    assert second["dur"] == pytest.approx(1e6)
    assert second["args"]["queued_us"] == pytest.approx(60e6)
    assert second["args"]["final_state"] == "FINISHED"
    assert second["args"]["attempts"] == 2


def test_requeue_truncation_keeps_boundary_declares():
    """A head outage long enough to overflow the requeue cap must not
    age out the one record carrying a histogram's boundaries — the
    head could never bucket that histogram again."""
    from ray_tpu.util import metrics

    buf = metrics._Buffer()
    try:
        declare = ("histogram", "h", 1.0, (), (10.0, 100.0))
        buf.push(declare)
        for _ in range(metrics._MAX_BUFFERED + 5):
            buf.push(("counter", "c", 1.0, ()))
        # No session: delivery fails, the sealed batch stays trimmed.
        buf.flush(raise_on_error=False)
        with buf.records_lock:
            buffered = [
                r for _, batch in buf._sealed for r in batch
            ]
        assert declare in buffered
        assert len(buffered) <= metrics._MAX_BUFFERED + 1
    finally:
        buf._stop.set()


def test_metrics_redelivery_does_not_double_count(rt_session):
    """Sealed batches retry until acknowledged; a batch whose reply
    was lost arrives twice and must be folded in exactly once. Uses a
    synthetic sender so the live driver's dedup state is untouched."""
    from ray_tpu._private.worker import global_worker
    from ray_tpu.util.metrics import metrics_summary

    worker = global_worker()
    batch = [("counter", "dedup_total", 5.0, ())]
    worker.call(
        "metrics_record", records=batch, sender="t-sender", seq=7
    )
    assert metrics_summary()["dedup_total"]["total"] == 5.0
    # The lost-reply retry: same (sender, seq) redelivered — dropped.
    worker.call(
        "metrics_record", records=batch, sender="t-sender", seq=7
    )
    assert metrics_summary()["dedup_total"]["total"] == 5.0
    # A NEW seq from the same sender still lands.
    worker.call(
        "metrics_record",
        records=[("counter", "dedup_total", 2.0, ())],
        sender="t-sender",
        seq=8,
    )
    assert metrics_summary()["dedup_total"]["total"] == 7.0


def test_merged_chrome_trace_has_all_three_streams(tmp_path):
    """doctor --trace artifact: task slices + spans + per-rank step
    phases in one chrome trace, phases laid sequentially inside the
    step's wall window."""
    from ray_tpu.util.tracing import merge_chrome_trace

    t0 = 2000.0
    task_events = [
        {
            "task_id": "t1",
            "name": "task_a",
            "kind": "normal",
            "state": "RUNNING",
            "time": t0,
        },
        {
            "task_id": "t1",
            "name": "task_a",
            "kind": "normal",
            "state": "FINISHED",
            "time": t0 + 1.0,
        },
    ]
    spans = [
        {
            "name": "span_a",
            "trace_id": "ab" * 16,
            "span_id": "cd" * 8,
            "parent_span_id": "",
            "start_ns": int(t0 * 1e9),
            "end_ns": int((t0 + 0.5) * 1e9),
            "attributes": {"flavor": "x"},
        }
    ]
    steps = [
        {
            "step": 7,
            "rank": 0,
            "time": t0 + 1.0,
            "wall_ms": 1000.0,
            "data_wait_ms": 200.0,
            "step_ms": 800.0,
        }
    ]
    path = tmp_path / "merged.json"
    trace = merge_chrome_trace(task_events, spans, steps, str(path))
    assert json.load(open(path)) == trace
    by_cat = {}
    for event in trace:
        by_cat.setdefault(event["cat"], []).append(event)
    assert {"normal", "span", "step"} <= set(by_cat)
    # Step phases: sequential layout filling the wall window.
    wait, step = sorted(by_cat["step"], key=lambda e: e["ts"])
    assert wait["name"] == "step 7 data_wait"
    assert step["name"] == "step 7 step"
    assert wait["ts"] == pytest.approx((t0 + 1.0 - 1.0) * 1e6)
    assert step["ts"] == pytest.approx(wait["ts"] + wait["dur"])
    assert step["dur"] == pytest.approx(800e3)
    assert wait["tid"] == "rank 0" and wait["pid"] == "steps"


def test_dashboard_endpoints(rt_session):
    rt = rt_session
    import socket

    from ray_tpu.dashboard import start_dashboard

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    dash = start_dashboard(port)
    try:

        @rt.remote
        class Marker:
            def ping(self):
                return 1

        marker = Marker.remote()
        rt.get(marker.ping.remote(), timeout=30)

        def fetch(path):
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=30
            ) as resp:
                return resp.read()

        nodes = json.loads(fetch("/api/nodes"))
        assert len(nodes) == 1
        actors = json.loads(fetch("/api/actors"))
        assert any(a["class_name"] == "Marker" for a in actors)
        resources = json.loads(fetch("/api/resources"))
        assert "CPU" in resources["total"]
        html = fetch("/").decode()
        # SPA shell: data is client-rendered from /api/* (asserted
        # above); the page just needs to serve with its poller.
        assert "ray_tpu" in html and "/api/" in html

        from ray_tpu.util.metrics import Counter, flush

        Counter("dash_metric").inc(2)
        flush()
        time.sleep(0.3)
        prom = fetch("/metrics").decode()
        assert "dash_metric 2.0" in prom
    finally:
        dash.stop()


def test_histogram_boundaries_buckets_and_percentiles(rt_session):
    """Satellite: declared boundaries are real — the head buckets
    observations (cumulative le_* counts) and reports p50/p95/p99
    from its sample reservoir."""
    rt = rt_session
    from ray_tpu.util.metrics import Histogram, metrics_summary

    lat = Histogram(
        "bucketed_ms", boundaries=[10, 100, 1000], tag_keys=("op",)
    )
    for v in (5.0, 50.0, 50.0, 500.0, 2000.0):
        lat.observe(v, tags={"op": "rpc"})

    deadline = time.time() + 10
    while time.time() < deadline:
        hist = metrics_summary().get("bucketed_ms", {})
        if hist.get("count") == 5:
            break
        time.sleep(0.2)
    assert hist["count"] == 5
    assert hist["buckets"] == {
        "le_10": 1,
        "le_100": 3,
        "le_1000": 4,
        "inf": 5,
    }
    assert hist["p50"] == 50.0
    assert hist["p95"] == 2000.0
    assert hist["p99"] == 2000.0
    # Per-tag buckets too, and no internal reservoir keys on the wire.
    tagged = hist["by_tags"]["op=rpc"]
    assert tagged["buckets"]["inf"] == 5
    assert not any(k.startswith("_") for k in hist)
    assert not any(k.startswith("_") for k in tagged)


def test_metrics_buffer_resets_on_shutdown():
    """Satellite: the _Buffer singleton + flusher thread die with
    ray_tpu.shutdown(); re-init binds a fresh buffer to the new
    session instead of leaking records at the dead one."""
    import ray_tpu as rt
    from ray_tpu.util.metrics import Counter, _Buffer, metrics_summary

    rt.init(num_cpus=2)
    try:
        Counter("lifecycle_counter").inc(1.0)
        first = _Buffer.get()
        assert first.thread.is_alive()
    finally:
        rt.shutdown()
    assert _Buffer._instance is None
    first.thread.join(timeout=5)
    assert not first.thread.is_alive()

    rt.init(num_cpus=2)
    try:
        second = _Buffer.get()
        assert second is not first
        Counter("lifecycle_counter").inc(41.0)
        deadline = time.time() + 10
        total = None
        while time.time() < deadline:
            total = (
                metrics_summary()
                .get("lifecycle_counter", {})
                .get("total")
            )
            if total == 41.0:
                break
            time.sleep(0.2)
        # Fresh cluster: only the post-re-init increment exists.
        assert total == 41.0
    finally:
        rt.shutdown()


def test_metrics_flush_raises_without_session():
    """Satellite: an explicit flush() surfaces delivery failure
    (RayTpuError) instead of silently swallowing it; the records
    stay buffered for a later retry."""
    import ray_tpu.exceptions as exc
    from ray_tpu.util.metrics import _Buffer, flush

    _Buffer.reset()  # known-clean start regardless of test order
    buf = _Buffer.get()
    try:
        buf.push(("counter", "orphan_metric", 1.0, ()))
        with pytest.raises(exc.RayTpuError):
            flush()
        with buf.records_lock:
            buffered = [
                r for _, batch in buf._sealed for r in batch
            ]
        assert buffered, "failed flush must keep the batch, not drop"
    finally:
        _Buffer.reset()


def test_event_stats_per_handler_timing(rt_session):
    """Per-handler RPC timing stats accumulate on the daemon
    (reference: event_stats.cc — count + execution + queueing delay
    per asio handler). After real traffic, the handlers that ran must
    show up with sane numbers."""
    rt = rt_session
    from ray_tpu.util import state

    @rt.remote
    def f(x):
        return x + 1

    assert rt.get([f.remote(i) for i in range(20)], timeout=60) == list(
        range(1, 21)
    )
    stats = state.event_stats()
    # direct transport routes tasks via leases; registration always
    # hits the daemon regardless of transport
    assert "register_client" in stats, sorted(stats)
    assert stats["register_client"]["count"] >= 1
    busiest = max(stats.values(), key=lambda r: r["count"])
    assert busiest["count"] >= 5
    for row in stats.values():
        assert row["max_exec_ms"] >= row["mean_exec_ms"] >= 0
        assert row["max_queue_ms"] >= row["mean_queue_ms"] >= 0
        assert row["errors"] >= 0
    # errors asserted only on a handler THIS test exercised — other
    # handlers may legitimately carry errors from session traffic.
    assert stats["register_client"]["errors"] == 0


@pytest.mark.parametrize(
    "code,cause", [(0, None), (2, "behind_prefill"), (6, "admissible")]
)
def test_the_waiting_gauges_row_says_why_the_queue_stands(code, cause):
    """`/api/serve` and `serve.status()` fold the head's table with
    `deployment_snapshot`: the family's row that shows `waiting` gains
    the standing cause from the gauge beside it (ISSUE 59)."""
    from ray_tpu.serve.observability import (
        QUEUE_CAUSES,
        _queue_cause_code,
        deployment_snapshot,
    )

    assert _queue_cause_code(cause) == code and len(QUEUE_CAUSES) == 6
    tags = "app=llm|deployment=llm|family=tiny"
    row = deployment_snapshot({
        "serve_engine_waiting": {"by_tags": {tags: {"value": 3.0}}},
        "serve_engine_queue_cause": {"by_tags": {tags: {"value": code}}},
    })[("llm", "llm")]["engine"]["tiny"]
    assert row["waiting"] == 3.0 and row.get("queue_cause") == cause


# -- a streamed request's first token, stage by stage (ISSUE 41) -------
# Keep these LAST in the file: the cluster below is module-scoped, and
# the tests above each make and shut down a session of their own.

#: The series a streamed request observes once each on its way to its
#: first token (serve/observability.py, boundaries B0 to B7).
STAGE_SERIES = (
    "serve_http_dispatch_ms", "serve_handler_submit_ms",
    "serve_first_item_handoff_ms", "serve_first_item_transit_ms",
    "serve_http_first_byte_ms",
)
#: ... and once each at its end (boundaries E0 to E2, ISSUE 59).
END_SERIES = (
    "serve_ingress_overhead_ms", "serve_stream_end_handoff_ms",
    "serve_stream_end_transit_ms",
)
OLD_SERIES = (
    "serve_queue_wait_ms", "serve_engine_ttft_ms",
    "serve_http_request_latency_ms",
)
ENGINE_COUNTERS = (
    "admitted", "admit_wait_ms_total", "first_tokens", "prefill_ms_total",
    "tokens_emitted",
)
TINY_LLM = {
    "vocab_size": 128, "dim": 64, "n_layers": 2, "n_heads": 4,
    "n_kv_heads": 2, "intermediate": 128, "max_seq_len": 128,
    "dtype": "float32",
}


class FirstTokenCluster:
    """proxy -> router -> replica -> a tiny engine at `/llm`, and a
    handler that streams nothing at `/echo`."""

    def __init__(self, rt, serve, port, actor):
        self.rt, self.serve, self.port, self._actor = rt, serve, port, actor

    def post(self, path, payload, request_id="", on_first=None):
        import http.client

        conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=120)
        headers = {"Content-Type": "application/json"}
        if request_id:
            headers["x-request-id"] = request_id
        try:
            conn.request("POST", path, body=json.dumps(payload), headers=headers)
            resp = conn.getresponse()
            first = resp.read(2)
            if on_first is not None:
                on_first()
            return resp.status, first + resp.read(), dict(resp.getheaders())
        finally:
            conn.close()

    def readings(self):
        """[sum, count] of every serve series on the head and the
        engine's counters, once they have settled."""
        from ray_tpu.util.metrics import metrics_summary

        def read():
            table = {
                name: (float(row.get("sum", 0.0)), float(row.get("count", 0.0)))
                for name, row in metrics_summary().items()
                # (the replica's handler timer counts this read's own
                # call for the engine's counters)
                if name.startswith("serve_") and "count" in row
                and name != "serve_request_latency_ms"
            }
            engine = self.rt.get(
                self._actor.handle_request.remote("engine_stats", (), {}),
                timeout=60,
            ).get("tiny") or {}
            return table, {k: engine.get(k, 0) for k in ENGINE_COUNTERS}

        # Every process flushes each half second: three reads in a
        # row that agree have seen the flush of each.
        deadline = time.monotonic() + 20.0
        reads = [read()]
        while True:
            time.sleep(0.7)
            reads.append(read())
            if reads[-3:].count(reads[-1]) == 3 or time.monotonic() > deadline:
                return reads[-1]

    def spans(self, request_id):
        by_name = {}
        deadline = time.monotonic() + 10.0
        while len(by_name) < 3 and time.monotonic() < deadline:
            for s in self.rt.api._session.worker.call(
                "list_spans", limit=10000
            )["spans"]:
                if s["attributes"].get("request_id") == request_id:
                    by_name[s["name"]] = s
            time.sleep(0.05)
        return by_name


@pytest.fixture(scope="module")
def first_token_cluster():
    import os

    import ray_tpu as rt
    import ray_tpu.serve as serve
    from ray_tpu.llm import build_llm_app
    from ray_tpu.serve.controller import CONTROLLER_NAME

    # The proxy's process reads the SLO threshold from its environment:
    # with 8 tokens a request is shed while another still streams.
    key = "RT_serve_slo_queue_threshold_tokens"
    old = os.environ.get(key)
    os.environ[key] = "8"
    rt.init(num_cpus=4, ignore_reinit_error=False)
    try:
        serve.run(
            build_llm_app(
                {"tiny": {"kind": "init", "seed": 0, "config": TINY_LLM}},
                engine={"slots": 2, "max_len": 128, "prefill_chunk": 8},
            ),
            name="llm", route_prefix="/llm",
        )

        @serve.deployment
        class Echo:
            def __call__(self, request):
                return {"echo": request.json()}

        serve.run(Echo.bind(), name="echo", route_prefix="/echo")
        port = serve.start(http_port=0)
        controller = rt.get_actor(CONTROLLER_NAME, namespace="serve")
        rows = rt.get(controller.get_replicas.remote("llm", "llm"), timeout=60)
        cluster = FirstTokenCluster(rt, serve, port, rows[0]["actor"])
        # The replica's first request loads the model and compiles.
        status, body, _ = cluster.post(
            "/llm", {"prompt": [3, 1, 4, 1, 5], "max_new_tokens": 4}
        )
        assert status == 200 and len(body.split()) == 4
        yield cluster
    finally:
        serve.shutdown()
        rt.shutdown()
        if old is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = old


@pytest.mark.timeout(300)
@pytest.mark.parametrize("case", ["stream", "unary", "shed"])
def test_first_token_stages_are_observed_once_a_request(
    first_token_cluster, case
):
    cluster = first_token_cluster
    before, engine_before = cluster.readings()
    shed = []
    if case == "stream":
        status, body, headers = cluster.post(
            "/llm", {"prompt": [2, 7, 1, 8, 2, 8], "max_new_tokens": 32},
            request_id=f"stages-{case}",
        )
        assert status == 200 and len(body.split()) == 32
    elif case == "unary":
        status, body, _ = cluster.post("/echo", {"x": 1})
        assert status == 200 and json.loads(body) == {"echo": {"x": 1}}
    else:
        # A second request while the first still holds most of its 96
        # tokens against a threshold of 8: the proxy answers 503.
        status, body, _ = cluster.post(
            "/llm", {"prompt": [1, 2, 3], "max_new_tokens": 96},
            on_first=lambda: shed.append(cluster.post(
                "/llm", {"prompt": [4, 5, 6], "max_new_tokens": 96}
            )),
        )
        assert status == 200 and len(body.split()) == 96
        assert shed[0][0] == 503 and "Retry-After" in shed[0][2]
    after, engine_after = cluster.readings()

    def delta(name):
        s0, n0 = before.get(name, (0.0, 0.0))
        s1, n1 = after.get(name, (0.0, 0.0))
        return s1 - s0, n1 - n0

    streamed = 0 if case == "unary" else 1
    # Exactly one observation a streamed request (of 32 or 96 tokens),
    # none a token; a unary call and a 503 observe nothing new.
    for name in STAGE_SERIES + END_SERIES:
        assert delta(name)[1] == streamed, (name, delta(name))
    engine = {
        k: engine_after[k] - engine_before[k] for k in ENGINE_COUNTERS
    }
    assert engine["first_tokens"] == engine["admitted"] == streamed
    assert delta("serve_http_request_latency_ms")[1] == 1 + len(shed)
    if case != "stream":
        return
    assert engine["tokens_emitted"] == 32
    stage = {
        name: delta(name)[0]
        for name in STAGE_SERIES + END_SERIES + OLD_SERIES
    }
    assert [delta(name)[1] for name in OLD_SERIES] == [1, 1, 1]
    assert all(v >= 0.0 for v in stage.values()), stage
    admit, prefill = engine["admit_wait_ms_total"], engine["prefill_ms_total"]
    assert admit >= 0.0 and prefill > 0.0
    # The engine's own time to first token is its two stages.
    assert stage["serve_engine_ttft_ms"] == pytest.approx(
        admit + prefill, abs=0.01
    )
    # The stages sum to the program's own time to first token.
    first_byte = stage["serve_http_first_byte_ms"]
    parts = (
        stage["serve_http_dispatch_ms"] + stage["serve_queue_wait_ms"]
        + stage["serve_handler_submit_ms"] + admit + prefill
        + stage["serve_first_item_handoff_ms"]
        + stage["serve_first_item_transit_ms"]
    )
    assert abs(first_byte - parts) <= max(5.0, 0.2 * first_byte), stage
    assert first_byte <= stage["serve_http_request_latency_ms"]
    # One trace, three spans, the same readings as attributes.
    spans = cluster.spans(f"stages-{case}")
    assert set(spans) == {"serve.http", "serve.handle", "engine.request"}
    assert len({s["trace_id"] for s in spans.values()}) == 1
    http_attrs = spans["serve.http"]["attributes"]
    handle = spans["serve.handle"]["attributes"]
    request = spans["engine.request"]["attributes"]
    assert float(http_attrs["first_byte_ms"]) == pytest.approx(
        first_byte, abs=0.01
    )
    assert float(handle["queue_wait_ms"]) == pytest.approx(
        stage["serve_queue_wait_ms"], abs=0.01
    )
    assert float(handle["submit_ms"]) == pytest.approx(
        stage["serve_handler_submit_ms"], abs=0.01
    )
    assert float(request["first_token_ms"]) == pytest.approx(
        admit + prefill, abs=0.01
    )
    assert float(handle["first_item_ms"]) == pytest.approx(
        stage["serve_handler_submit_ms"] + admit + prefill
        + stage["serve_first_item_handoff_ms"], abs=0.05
    )
    assert {"queue_ms", "prefill_ms", "decode_ms"} <= set(request)
    # Its wait by cause, summing to its wait.
    by_cause = dict(
        part.split("=") for part in request["queue_cause_ms"].split(",")
    )
    assert sum(map(float, by_cause.values())) == pytest.approx(
        float(request["queue_ms"]), abs=0.01
    ) and set(by_cause) <= {"admissible", "behind_prefill", "no_slot"}
    # Above the replica: the proxy's time for THIS request less its
    # handler's (the two spans time the same two stretches, each to
    # its own end), on the series and the `serve.http` span, and no
    # less than the way in and the end together.
    overhead = stage["serve_ingress_overhead_ms"]
    span_ms = {
        name: (s["end_ns"] - s["start_ns"]) / 1e6 for name, s in spans.items()
    }
    assert overhead == pytest.approx(
        span_ms["serve.http"] - span_ms["serve.handle"], abs=5.0
    )
    assert float(http_attrs["ingress_overhead_ms"]) == pytest.approx(
        overhead, abs=0.01
    )
    assert 0.0 < overhead <= stage["serve_http_request_latency_ms"]
    named = (
        stage["serve_http_dispatch_ms"] + stage["serve_queue_wait_ms"]
        + stage["serve_stream_end_handoff_ms"]
        + stage["serve_stream_end_transit_ms"]
    )
    assert named <= overhead + 1.0, stage


def test_a_stream_that_notes_nothing_ends_as_it_always_did(
    first_token_cluster,
):
    """`data/` and `train/` stream through the transport serve does:
    the end of a stream whose producer noted nothing (every stream but
    a serve replica's) reaches its consumer in the parent's form, key
    for key, in the run's answer and on the wire."""
    from ray_tpu._private import wire
    from ray_tpu._private.stream_runs import StreamRuns

    rt = first_token_cluster.rt

    @rt.remote(num_returns="streaming")
    def count(n):
        yield from range(n)

    gen = count.remote(3)
    assert [gen.next_value() for _ in range(3)] == [0, 1, 2]
    with pytest.raises(StopIteration):
        gen.next_value()
    assert gen._end == {"count": 3, "error": None} and gen.end_note == {}

    class Conn:
        conn_id, sent = 1, []

        def reply(self, mid, payload):
            self.sent.append(payload)

    runs, conn = StreamRuns(), Conn()
    for task, note in ((b"plain", {}), (b"serve", {"handler_ms": 7.5})):
        runs.put(task, 0, b"x", first_ts=1.0)
        runs.end(task, 1, None, note=note)
        runs.fetch(conn, 0, task, 0)
    assert conn.sent == [
        {"items": [b"x"], "end": {"count": 1, "error": None},
         "first_ts": 1.0},
        {"items": [b"x"], "end": {"count": 1, "error": None,
                                  "handler_ms": 7.5}, "first_ts": 1.0},
    ]
    assert wire.validate("stream_end", {"task": b"t", "count": 1}) is None
    assert wire.validate(
        "stream_end", {"task": b"t", "count": 1, "handler_ms": 1.0,
                       "exhausted_ts": 2.0, "end_ts": 3.0},
    ) is None
