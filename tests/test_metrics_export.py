"""Metrics-export + request-path observability tests (ISSUE 7):
Prometheus text-format rendering, the head time-series ring, goodput
classification, and the 2-node serve e2e that ties /metrics,
/api/serve and /api/timeseries together.
"""

import json
import time
import urllib.request

import pytest

from ray_tpu._private.step_telemetry import goodput_from_records
from ray_tpu._private.timeseries import TimeSeriesStore, compact_summary
from ray_tpu.util.prometheus import render_prometheus


# ---------------------------------------------------------------------------
# Prometheus rendering (pure-function unit tests)
# ---------------------------------------------------------------------------


def test_prometheus_escaping_and_sanitization():
    text = render_prometheus(
        {
            "legacy.dotted-name": {
                "kind": "counter",
                "description": 'has "quotes" and\nnewline \\ slash',
                "total": 3.0,
                "by_tags": {
                    'path=/a"b\\c\nd': {"total": 3.0},
                },
            },
        }
    )
    # Name sanitized, HELP escaped (newline survives as literal \n).
    assert "# HELP legacy_dotted_name" in text
    assert r"newline \\ slash" in text
    assert "\nnewline" not in text.split("# HELP", 1)[1].split("\n")[0]
    # Label values escape quote, backslash, newline.
    assert r'path="/a\"b\\c\nd"' in text
    assert text.endswith("\n")


def test_prometheus_counter_gauge_series_rules():
    text = render_prometheus(
        {
            "rt_workers_alive": {
                "kind": "gauge",
                "description": "workers",
                "value": 5.0,
                "by_node": {"aa": 2.0, "bb": 3.0},
            },
            "plain_total": {"kind": "counter", "total": 2.0},
            "tagged_total": {
                "kind": "counter",
                "total": 7.0,
                "by_tags": {
                    "app=x|deployment=y": {"total": 4.0},
                    "app=x|deployment=z": {"total": 3.0},
                },
            },
        }
    )
    lines = text.splitlines()
    # by_node: ONLY per-node series (no unlabeled double-count line).
    assert 'rt_workers_alive{node="aa"} 2.0' in lines
    assert 'rt_workers_alive{node="bb"} 3.0' in lines
    assert "rt_workers_alive 5.0" not in lines
    # bare counter renders unlabeled; tagged one per tag set, no
    # aggregate line.
    assert "plain_total 2.0" in lines
    assert 'tagged_total{app="x",deployment="y"} 4.0' in lines
    assert 'tagged_total{app="x",deployment="z"} 3.0' in lines
    assert "tagged_total 7.0" not in lines
    assert "# TYPE tagged_total counter" in lines


def _parse_bucket_lines(text, name):
    """[(labels-dict, value)] for every `<name>_bucket` line."""
    out = []
    for line in text.splitlines():
        if not line.startswith(name + "_bucket"):
            continue
        labels_part = line[line.index("{") + 1 : line.rindex("}")]
        labels = {}
        for item in labels_part.split('",'):
            key, _, value = item.partition("=")
            labels[key.strip()] = value.strip('"')
        out.append((labels, float(line.rsplit(" ", 1)[1])))
    return out


def test_prometheus_histogram_le_monotonic_inf_sum_count():
    entry = {
        "kind": "histogram",
        "description": "latency",
        "count": 9,
        "sum": 123.5,
        "buckets": {"le_1": 2, "le_5": 5, "le_25": 8, "inf": 9},
        "by_tags": {
            "app=a|deployment=d": {
                "count": 9,
                "sum": 123.5,
                "buckets": {
                    "le_1": 2,
                    "le_5": 5,
                    "le_25": 8,
                    "inf": 9,
                },
            }
        },
    }
    text = render_prometheus({"serve_request_latency_ms": entry})
    assert "# TYPE serve_request_latency_ms histogram" in text
    buckets = _parse_bucket_lines(text, "serve_request_latency_ms")
    assert buckets, text
    # Cumulative counts nondecreasing in le order; +Inf == _count.
    values = [v for _labels, v in buckets]
    assert values == sorted(values)
    assert buckets[-1][0]["le"] == "+Inf"
    assert buckets[-1][1] == 9.0
    assert (
        'serve_request_latency_ms_sum{app="a",deployment="d"} 123.5'
        in text
    )
    assert (
        'serve_request_latency_ms_count{app="a",deployment="d"} 9.0'
        in text
    )
    # Deployment label rides every bucket line.
    assert all(
        labels.get("deployment") == "d" for labels, _v in buckets
    )


def test_prometheus_histogram_without_boundaries_gets_inf_bucket():
    text = render_prometheus(
        {"h": {"kind": "histogram", "count": 4, "sum": 8.0}}
    )
    assert 'h_bucket{le="+Inf"} 4.0' in text
    assert "h_sum 8.0" in text
    assert "h_count 4.0" in text


# ---------------------------------------------------------------------------
# time-series ring (store unit tests)
# ---------------------------------------------------------------------------


def test_timeseries_ring_bounds_and_eviction():
    store = TimeSeriesStore(max_snapshots=5)
    for i in range(12):
        store.append({"m": {"kind": "counter", "total": float(i)}},
                     now=1000.0 + i)
    assert len(store) == 5
    snaps = store.query()
    # Oldest evicted: only the newest 5 survive, oldest first.
    assert [s["time"] for s in snaps] == [
        1007.0, 1008.0, 1009.0, 1010.0, 1011.0
    ]
    assert snaps[0]["metrics"]["m"]["total"] == 7.0


def test_timeseries_query_filters():
    store = TimeSeriesStore(max_snapshots=10)
    store.append({"a": {"kind": "gauge", "value": 1.0}}, now=10.0)
    store.append(
        {
            "a": {"kind": "gauge", "value": 2.0},
            "b": {"kind": "counter", "total": 5.0},
        },
        now=20.0,
    )
    # since: strictly newer.
    assert [s["time"] for s in store.query(since=10.0)] == [20.0]
    # name: filters each snapshot; snapshots missing the series are
    # skipped entirely.
    only_b = store.query(name="b")
    assert len(only_b) == 1 and set(only_b[0]["metrics"]) == {"b"}
    # limit keeps the NEWEST.
    assert [s["time"] for s in store.query(limit=1)] == [20.0]


def test_compact_summary_strips_heavy_fields():
    compact = compact_summary(
        {
            "h": {
                "kind": "histogram",
                "description": "x",
                "count": 3,
                "sum": 6.0,
                "p50": 2.0,
                "p99": 3.0,
                "buckets": {"le_1": 1, "inf": 3},
                "by_tags": {
                    "app=a": {
                        "count": 3,
                        "p99": 3.0,
                        "buckets": {"inf": 3},
                    }
                },
            }
        }
    )
    entry = compact["h"]
    assert entry["count"] == 3 and entry["p99"] == 3.0
    assert "buckets" not in entry and "description" not in entry
    assert entry["by_tags"]["app=a"] == {"count": 3, "p99": 3.0}


# ---------------------------------------------------------------------------
# goodput classification (pure arithmetic)
# ---------------------------------------------------------------------------


def _rec(job="j1", wall=100.0, step=70.0, data=20.0, h2d=5.0,
         ckpt=0.0, warmup=False):
    rec = {
        "job": job,
        "wall_ms": wall,
        "step_ms": step,
        "data_wait_ms": data,
        "h2d_ms": h2d,
        "ckpt_block_ms": ckpt,
    }
    if warmup:
        rec["warmup"] = True
    return rec


def test_goodput_basic_classification():
    rows = goodput_from_records(
        [_rec(), _rec(wall=100.0, step=80.0, data=10.0, h2d=10.0)]
    )
    row = rows["j1"]
    assert row["steps"] == 2
    assert row["wall_ms"] == 200.0
    assert row["productive_ms"] == 150.0
    assert row["stall_ms"] == 45.0
    assert row["idle_ms"] == 5.0
    # Partition is exact: productive + stall + idle == wall.
    assert (
        row["productive_ms"] + row["stall_ms"] + row["idle_ms"]
        == row["wall_ms"]
    )
    assert row["goodput"] == 0.75
    assert row["stalls"]["data_wait_ms"] == 30.0


def test_goodput_caps_and_skips():
    rows = goodput_from_records(
        [
            _rec(warmup=True),  # warmup: skipped
            {"job": "j1", "step_ms": 50.0},  # no wall: skipped
            # Overreported phases: stall capped at wall, productive
            # capped at the remainder — the partition stays exact.
            _rec(wall=100.0, step=90.0, data=80.0, h2d=40.0),
        ]
    )
    row = rows["j1"]
    assert row["steps"] == 1
    assert row["wall_ms"] == 100.0
    assert row["stall_ms"] == 100.0  # 80 + capped-to-20 h2d
    assert row["stalls"]["h2d_ms"] == 20.0
    assert row["productive_ms"] == 0.0
    assert row["goodput"] == 0.0
    assert (
        row["productive_ms"] + row["stall_ms"] + row["idle_ms"]
        == row["wall_ms"]
    )


def test_goodput_keeps_jobs_apart():
    rows = goodput_from_records(
        [_rec(job="a", step=90.0, data=10.0, h2d=0.0),
         _rec(job="b", step=10.0, data=90.0, h2d=0.0)]
    )
    assert rows["a"]["goodput"] == 0.9
    assert rows["b"]["goodput"] == 0.1


# ---------------------------------------------------------------------------
# live-cluster integration
# ---------------------------------------------------------------------------


def _goodput_of_three_steps(rt):
    """Three hand-rolled steps of 100 ms, 40 of them stalled -> the
    job's row of the head's `step_summary` goodput."""
    from ray_tpu._private.step_telemetry import add_phase, report_step
    from ray_tpu.util import metrics

    for step in range(1, 4):
        add_phase("data_wait_ms", 30.0)
        add_phase("h2d_ms", 10.0)
        report_step(step, rank=0, wall_ms=100.0)
    metrics.flush()
    summary = rt.api._worker().call("step_summary")["summary"]
    (row,) = summary["goodput"].values()
    assert row["steps"] == 3
    return row


def test_goodput_in_doctor_and_step_summary(rt_session):
    """Acceptance: the doctor's per-job goodput fraction classifies
    productive + stall to the reported step wall within 5%."""
    rt = rt_session
    from ray_tpu._private.step_telemetry import take_phases

    # A hand-rolled loop drains the thread's bucket before it starts
    # (`report_step`): this thread is pytest's, and whatever compiled
    # on it in an earlier test left its `compile_ms` there.
    take_phases()
    row = _goodput_of_three_steps(rt)
    assert row["goodput"] == pytest.approx(0.6, abs=0.01)
    total = row["productive_ms"] + row["stall_ms"] + row["idle_ms"]
    assert total == pytest.approx(row["wall_ms"], rel=0.05)
    # Same numbers through the doctor verdict.
    verdict = rt.diagnose(capture_stacks=False)
    doctor_row = next(iter(verdict["steps"]["goodput"].values()))
    assert doctor_row["goodput"] == row["goodput"]


@pytest.mark.parametrize("drained", [True, False])
def test_phases_left_before_a_loop_bill_its_first_step(
    rt_session, drained
):
    """What made the test above fail in every whole-suite run: a
    phase billed on this thread BEFORE a hand-rolled loop (a cold
    compile's `compile_ms`, seconds of it) waits in the thread's
    bucket for the next `report_step`. Undrained it is capped at the
    first step's wall and that step reads as all stall (2 productive
    steps of 3: 0.4); after `take_phases()` the loop reads 0.6."""
    from ray_tpu._private.step_telemetry import add_phase, take_phases

    take_phases()
    add_phase("compile_ms", 5000.0)
    if drained:
        assert take_phases() == {"compile_ms": 5000.0}
    row = _goodput_of_three_steps(rt_session)
    want = 0.6 if drained else 0.4
    assert row["goodput"] == pytest.approx(want, abs=0.01)
    assert row["stalls"]["compile_ms"] == pytest.approx(
        0.0 if drained else 60.0
    )


def test_timeseries_live_ring_and_endpoint():
    """Head snapshot loop + /api/timeseries: bounded history spanning
    >= 2 snapshot intervals, counter trend visible by differencing."""
    import ray_tpu as rt

    rt.init(
        num_cpus=2,
        _system_config={"metrics_timeseries_interval_s": 0.2},
    )
    try:
        from ray_tpu.util.metrics import (
            Counter,
            flush,
            metrics_timeseries,
        )

        counter = Counter("ts_probe_total")
        counter.inc(1.0)
        flush()
        deadline = time.time() + 30
        snaps = []
        while time.time() < deadline:
            snaps = metrics_timeseries(name="ts_probe_total")
            if len(snaps) >= 2:
                break
            counter.inc(1.0)
            flush()
            time.sleep(0.1)
        assert len(snaps) >= 2, "ring never spanned two intervals"
        totals = [
            s["metrics"]["ts_probe_total"]["total"] for s in snaps
        ]
        assert totals == sorted(totals)  # counter never goes down
        assert totals[-1] >= 1.0
        # HTTP surface agrees (query-param filtered).
        from ray_tpu.dashboard import start_dashboard

        dash = start_dashboard(port=0)
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{dash.port}/api/timeseries"
                "?name=ts_probe_total&limit=2",
                timeout=30,
            ) as resp:
                payload = json.loads(resp.read())
        finally:
            dash.stop()
        assert len(payload) == 2
        assert "ts_probe_total" in payload[-1]["metrics"]
    finally:
        rt.shutdown()


@pytest.mark.slow
@pytest.mark.timeout(300)
def test_serve_request_path_e2e_two_nodes():
    """2-node cluster, HTTP traffic through a serve deployment:
    /metrics exposes parseable per-deployment request-latency
    histograms, /api/serve reports consistent counts and non-zero
    percentiles, and request ids round-trip as headers."""
    import ray_tpu as rt
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(
        head_resources={"CPU": 3.0},
        system_config={"metrics_timeseries_interval_s": 0.2},
    )
    try:
        cluster.add_node(num_cpus=2)
        cluster.wait_for_nodes(2, timeout=60)
        rt.init(address=cluster.address)
        import ray_tpu.serve as serve

        @serve.deployment(num_replicas=2)
        class Echo:
            def __call__(self, request):
                time.sleep(0.005)
                return {"path": request.path}

        try:
            port = serve.start(http_port=0)
            serve.run(Echo.bind(), name="app", route_prefix="/")
            n_requests = 20
            for i in range(n_requests):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/echo/{i}",
                    headers={"x-request-id": f"req-{i:04d}"},
                )
                with urllib.request.urlopen(req, timeout=30) as resp:
                    assert resp.status == 200
                    # The id the client sent comes back.
                    assert (
                        resp.headers.get("x-request-id")
                        == f"req-{i:04d}"
                    )

            # Wait until every replica's records reached the head.
            deadline = time.time() + 60
            detail = {}
            while time.time() < deadline:
                detail = serve.status_detail().get("app/Echo", {})
                if detail.get("requests_total", 0) >= n_requests:
                    break
                time.sleep(0.25)
            assert detail.get("requests_total", 0) >= n_requests, (
                detail
            )
            assert detail["errors_total"] == 0
            assert detail["p50_ms"] > 0
            assert detail["p99_ms"] >= detail["p50_ms"]
            assert detail["replicas"] == 2
            assert "queue_depth" in detail and "in_flight" in detail

            from ray_tpu.dashboard import start_dashboard

            dash = start_dashboard(port=0)
            try:
                def fetch(path):
                    with urllib.request.urlopen(
                        f"http://127.0.0.1:{dash.port}{path}",
                        timeout=30,
                    ) as resp:
                        return resp.read().decode()

                prom = fetch("/metrics")
                # Parseable: every non-comment line is `series value`.
                for line in prom.splitlines():
                    if not line or line.startswith("#"):
                        continue
                    series, _, value = line.rpartition(" ")
                    assert series, line
                    float(value)  # must parse
                assert (
                    "# TYPE serve_request_latency_ms histogram"
                    in prom
                )
                assert 'deployment="Echo"' in prom
                assert 'le="+Inf"' in prom
                # /metrics and /api/serve agree on completed counts.
                prom_total = sum(
                    float(line.rsplit(" ", 1)[1])
                    for line in prom.splitlines()
                    if line.startswith("serve_requests_total{")
                    and 'deployment="Echo"' in line
                )
                api_detail = json.loads(fetch("/api/serve"))[
                    "app/Echo"
                ]
                assert prom_total == api_detail["requests_total"]
                assert api_detail["p50_ms"] > 0

                # Bounded history across >= 2 snapshot intervals.
                deadline = time.time() + 30
                snaps = []
                while time.time() < deadline:
                    snaps = json.loads(
                        fetch(
                            "/api/timeseries"
                            "?name=serve_requests_total"
                        )
                    )
                    if len(snaps) >= 2:
                        break
                    time.sleep(0.2)
                assert len(snaps) >= 2
            finally:
                dash.stop()
        finally:
            serve.shutdown()
    finally:
        rt.shutdown()
        cluster.shutdown()
