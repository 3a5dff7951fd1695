"""Distributed-correctness linter tests (`ray_tpu lint`,
devtools/lint.py + rules.py) and regression tests for the four bug
classes that motivated it (found by an earlier round's review:
tcp_channel payload-dedup, autoscaler request packing, worker
namespace pinning, sdk num_cpus truncation).

Every rule RT001-RT010 has a positive fixture (must fire) and a
negative fixture (must stay quiet); the repo lints itself clean — so
a new framework idiom either passes the rules or carries an explicit
`# rt: noqa[RTxxx]` reviewed in the diff.
"""

import io
import json
import os
import struct
import textwrap
import threading

import pytest

from ray_tpu.devtools.lint import lint_paths, lint_source, main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fired(source: str, path: str):
    return {f.rule for f in lint_source(textwrap.dedent(source), path)}


# ---------------------------------------------------------------------------
# one positive + one negative fixture per rule
# ---------------------------------------------------------------------------

CASES = [
    # (rule, path, source, expect_fire)
    (
        "RT001",
        "serve/actor_mod.py",
        """
        import ray_tpu as rt

        @rt.remote
        class Pool:
            def gather(self, ref):
                return rt.get(ref)
        """,
        True,
    ),
    (
        "RT001",
        "serve/async_mod.py",
        """
        import ray_tpu as rt

        async def gather(ref):
            return rt.get(ref)
        """,
        True,
    ),
    (
        "RT001",
        "serve/driver_mod.py",
        """
        import ray_tpu as rt

        def gather(ref):  # plain driver-side helper: fine
            return rt.get(ref)
        """,
        False,
    ),
    (
        "RT002",
        "dag/some_channel.py",
        """
        class Chan:
            def put(self, payload):
                retry = payload == self._tx_payload  # the old bug
                return retry
        """,
        True,
    ),
    (
        "RT002",
        "dag/some_channel.py",
        """
        class Chan:
            def put(self, payload, seq):
                retry = seq == self._tx_seq  # identity, not content
                return retry
        """,
        False,
    ),
    (
        "RT003",
        "dag/proto.py",
        """
        import time

        def frame_record(data):
            return (time.time(), data)
        """,
        True,
    ),
    (
        "RT003",
        "dag/proto.py",
        """
        import time

        def frame_record(data, seq):
            deadline = time.monotonic() + 5  # local timing: fine
            return (seq, data, deadline)
        """,
        False,
    ),
    (
        "RT004",
        "_private/fork_loaded.py",
        """
        import threading

        _lock = threading.Lock()
        """,
        True,
    ),
    (
        "RT004",
        "_private/fork_loaded.py",
        """
        import threading

        def start():
            return threading.Thread(target=print)  # lazy: post-fork
        """,
        False,
    ),
    (
        "RT005",
        "autoscaler/mysdk.py",
        """
        def request_capacity(num_cpus: float = 0):
            return int(num_cpus)
        """,
        True,
    ),
    (
        "RT005",
        "autoscaler/mysdk.py",
        """
        def request_capacity(num_cpus: float = 0):
            if isinstance(num_cpus, float) and not num_cpus.is_integer():
                raise ValueError("fractional num_cpus")
            return int(num_cpus)
        """,
        False,
    ),
    (
        "RT006",
        "serve/lookup.py",
        """
        def controller(get_actor):
            return get_actor("controller", namespace="default")
        """,
        True,
    ),
    (
        # the session-context module itself may name the default
        "RT006",
        "x/ray_tpu/api.py",
        """
        def controller(get_actor):
            return get_actor("controller", namespace="default")
        """,
        False,
    ),
    (
        "RT007",
        "_private/daemon_like.py",
        """
        def _h_submit(conn, msg):
            try:
                dispatch(msg)
            except Exception:
                pass
        """,
        True,
    ),
    (
        "RT007",
        "_private/daemon_like.py",
        """
        def _h_submit(conn, msg):
            try:
                dispatch(msg)
            except Exception as e:
                conn.reply(msg["_mid"], {"_error": repr(e)})
        """,
        False,
    ),
    (
        "RT008",
        "util/sync.py",
        """
        def drain(evt):
            evt.wait()
        """,
        True,
    ),
    (
        "RT008",
        "util/sync.py",
        """
        def drain(evt):
            evt.wait(5.0)
        """,
        False,
    ),
    (
        "RT009",
        "serve/metrics_mod.py",
        """
        from ray_tpu.util.metrics import Counter, Histogram

        requests = Counter("serve.requests", tag_keys=("app",))
        latency = Histogram(
            "serve_latency_ms", tag_keys=("Deployment-Name",)
        )
        """,
        True,
    ),
    (
        "RT009",
        "serve/metrics_mod.py",
        """
        from ray_tpu.util.metrics import Counter, Histogram

        requests = Counter(
            "serve_requests_total", tag_keys=("app", "deployment")
        )
        latency = Histogram(
            "serve_latency_ms", tag_keys=("app", "deployment")
        )
        """,
        False,
    ),
    (
        "RT010",
        "serve/metrics_mod.py",
        """
        from ray_tpu.util.metrics import Counter

        requests = Counter(
            "serve_requests_total", tag_keys=("app", "request_id")
        )
        """,
        True,
    ),
    (
        "RT010",
        "llm/engine_mod.py",
        """
        from ray_tpu.util.metrics import Gauge

        def record(gauge, oid, nbytes):
            gauge.set(nbytes, tags={"object_id": oid})
        """,
        True,
    ),
    (
        "RT010",
        "serve/metrics_mod.py",
        """
        from ray_tpu.util.metrics import Counter, Histogram

        requests = Counter(
            "serve_requests_total", tag_keys=("app", "deployment")
        )

        def record(hist, job, ms):
            # job labels are bounded by design (goodput/ledger key
            # on them); ids are what RT010 rejects.
            hist.observe(ms, tags={"job": job})
        """,
        False,
    ),
    (
        # The XLA compile-series cardinality contract (ISSUE 15): a
        # per-shape-digest label mints one series per arg-shape set —
        # unbounded under exactly the recompile storm the series
        # exists to catch.
        "RT010",
        "user/compile_metrics_mod.py",
        """
        from ray_tpu.util.metrics import Counter

        compiles = Counter(
            "my_compiles_total", tag_keys=("program", "digest")
        )

        def record(hist, shape_digest, ms):
            hist.observe(ms, tags={"shape_digest": shape_digest})
        """,
        True,
    ),
    (
        # ...while the program NAME alone (a bounded registered
        # family) is the sanctioned label — the shape of
        # rt_jax_compiles_total / rt_jax_compile_ms.
        "RT010",
        "user/compile_metrics_mod.py",
        """
        from ray_tpu.util.metrics import Counter, Histogram

        compiles = Counter(
            "my_compiles_total", tag_keys=("program",)
        )

        def record(hist, program, ms):
            hist.observe(ms, tags={"program": program})
        """,
        False,
    ),
    (
        # The transfer-matrix cardinality contract (ISSUE 20): a
        # fused src-dst pair label is N^2 series no PromQL
        # aggregation can decompose; so is a per-pull flow id.
        "RT010",
        "user/transfer_metrics_mod.py",
        """
        from ray_tpu.util.metrics import Counter

        transfers = Counter(
            "my_transfer_bytes_total", tag_keys=("job", "flow")
        )

        def record(hist, src, dst, ms):
            hist.observe(ms, tags={"src_dst": src + ":" + dst})
        """,
        True,
    ),
    (
        "RT010",
        "user/transfer_metrics_mod.py",
        """
        from ray_tpu.util.metrics import Counter

        def record(counter, fid, nbytes):
            counter.inc(nbytes, tags={"flow_id": fid})
        """,
        True,
    ),
    (
        # ...while src_node / dst_node as SEPARATE labels are the
        # sanctioned shape (node granularity is bounded; either side
        # aggregates) — the shape of rt_object_transfer_bytes_total.
        "RT010",
        "user/transfer_metrics_mod.py",
        """
        from ray_tpu.util.metrics import Counter

        transfers = Counter(
            "my_transfer_bytes_total",
            tag_keys=("job", "src_node", "dst_node"),
        )

        def record(counter, job, src, dst, nbytes):
            counter.inc(
                nbytes,
                tags={"job": job, "src_node": src, "dst_node": dst},
            )
        """,
        False,
    ),
]


@pytest.mark.parametrize(
    "rule,path,source,expect",
    CASES,
    ids=[f"{c[0]}-{'fires' if c[3] else 'quiet'}-{i}" for i, c in enumerate(CASES)],
)
def test_rule_fixtures(rule, path, source, expect):
    rules = fired(source, path)
    if expect:
        assert rule in rules, f"{rule} did not fire on its fixture"
    else:
        assert rule not in rules, f"{rule} false-positived"


def test_rt002_would_have_caught_the_shipped_bug():
    """The exact dedup line tcp_channel.py shipped (pre-fix) trips
    RT002 under the real file path."""
    old_code = """
    class TcpChannel:
        def put_bytes(self, payload, timeout=None):
            if self._tx:
                retry = payload == self._tx_payload
                self._flush(sock)
                if retry:
                    self._tx_payload = None
                    return
    """
    rules = fired(old_code, "ray_tpu/dag/tcp_channel.py")
    assert "RT002" in rules


def test_rule_scoping_is_path_based():
    # Same nondeterminism source outside the replayable scope: quiet.
    src = "import time\n\ndef f():\n    return time.time()\n"
    assert "RT003" in {f.rule for f in lint_source(src, "dag/x.py")}
    assert "RT003" not in {f.rule for f in lint_source(src, "serve/x.py")}


# ---------------------------------------------------------------------------
# suppressions / output modes / self-check
# ---------------------------------------------------------------------------


def test_noqa_suppressions():
    bad = "import threading\n_lock = threading.Lock()"
    path = "_private/m.py"
    assert {f.rule for f in lint_source(bad, path)} == {"RT004"}
    # targeted suppression
    ok = bad + "  # rt: noqa[RT004]"
    assert lint_source(ok, path) == []
    # suppression for a DIFFERENT rule does not apply — and the
    # useless suppression is itself reported (noqa hygiene, RT090).
    wrong = bad + "  # rt: noqa[RT001]"
    assert {f.rule for f in lint_source(wrong, path)} == {
        "RT004",
        "RT090",
    }
    # blanket suppression
    blanket = bad + "  # rt: noqa"
    assert lint_source(blanket, path) == []
    # multi-rule form: RT004 is suppressed, but naming RT001 — which
    # never fires on that line — is a stale suppression.
    multi = bad + "  # rt: noqa[RT001,RT004]"
    assert {f.rule for f in lint_source(multi, path)} == {"RT090"}


def test_json_output_mode(tmp_path):
    target = tmp_path / "dag" / "badchan.py"
    target.parent.mkdir()
    target.write_text(
        "def dedup(payload, prev):\n    return payload == prev\n"
    )
    out = io.StringIO()
    code = main(["--json", str(target)], out=out)
    assert code == 1
    findings = json.loads(out.getvalue())
    assert len(findings) == 1
    f = findings[0]
    assert f["rule"] == "RT002"
    assert f["path"] == str(target)
    assert f["line"] == 2
    assert "sequence number" in f["message"]


def test_rules_filter_and_errors(tmp_path):
    target = tmp_path / "dag" / "multi.py"
    target.parent.mkdir()
    target.write_text(
        "import time\n"
        "def f(payload, prev):\n"
        "    t = time.time()\n"
        "    return payload == prev, t\n"
    )
    # both rules fire unfiltered; --rules restricts to one
    unfiltered = io.StringIO()
    assert main([str(target)], out=unfiltered) == 1
    assert "RT002" in unfiltered.getvalue()
    assert "RT003" in unfiltered.getvalue()
    out = io.StringIO()
    assert main(["--rules", "RT003", str(target)], out=out) == 1
    assert "RT002" not in out.getvalue()
    assert "RT003" in out.getvalue()
    # unknown rule id and missing path are usage errors
    assert main(["--rules", "RT999", str(target)], out=io.StringIO()) == 2
    assert main([str(tmp_path / "nope.py")], out=io.StringIO()) == 2


def test_repo_lints_clean():
    """`ray_tpu lint ray_tpu/` exits 0: every intentional pattern in
    the tree carries an explicit `# rt: noqa[RTxxx]`."""
    out = io.StringIO()
    code = main([os.path.join(REPO, "ray_tpu")], out=out)
    assert code == 0, f"repo lint not clean:\n{out.getvalue()}"


def test_every_rule_has_id_title_and_doc():
    from ray_tpu.devtools.rules import ALL_RULES

    ids = [r.id for r in ALL_RULES]
    assert ids == [f"RT{i:03d}" for i in range(1, 11)]
    for rule in ALL_RULES:
        assert rule.title
        assert rule.__doc__


# ---------------------------------------------------------------------------
# regression: tcp_channel sequence-number framing
# ---------------------------------------------------------------------------


@pytest.fixture
def tcp_pair(monkeypatch):
    """Reader/writer TcpChannel endpoints rendezvousing through an
    in-process fake KV (no cluster needed)."""
    import ray_tpu.dag.tcp_channel as tc

    kv = {}

    def fake_kv(method, **kw):
        key = (kw.get("ns"), kw["key"])
        if method == "kv_put":
            kv[key] = kw["value"]
            return {}
        if method == "kv_get":
            return {"value": kv.get(key)}
        if method == "kv_del":
            kv.pop(key, None)
            return {}
        raise AssertionError(method)

    monkeypatch.setattr(tc, "_kv_call", fake_kv)
    reader = tc.TcpChannel(1 << 16, chan_id="lint-regress")
    writer = tc.TcpChannel(1 << 16, chan_id="lint-regress")
    reader.bind_reader()
    yield reader, writer
    reader.close()
    writer.close()


def test_tcp_equal_payloads_are_distinct_records(tcp_pair):
    """The shipped bug: a put whose bytes equal the pending record was
    swallowed as a 'retry'. Equal payloads must all be delivered."""
    reader, writer = tcp_pair
    got = []

    def drain():
        for _ in range(3):
            got.append(reader.get_bytes(timeout=10))

    t = threading.Thread(target=drain)
    t.start()
    assert writer.put_bytes(b"same", timeout=5) == 0
    assert writer.put_bytes(b"same", timeout=5) == 1  # NOT deduped
    assert writer.put_bytes(b"same", timeout=5) == 2
    t.join(10)
    assert got == [b"same", b"same", b"same"]


def test_tcp_retry_token_dedups_exactly_once(tcp_pair):
    """A retry carrying the timed-out record's seq finishes delivering
    THAT record; it never queues a duplicate. (White-box: stage the
    'timed out before any byte was sent' writer state directly.)"""
    reader, writer = tcp_pair
    writer._ensure("writer", 5)
    payload = b"retry-me"
    # Stage a pending record exactly as a timed-out put leaves it.
    seq = writer._next_tx_seq
    writer._next_tx_seq += 1
    writer._tx = memoryview(
        struct.pack("<QQ", len(payload), seq) + payload
    )
    writer._tx_seq = seq
    # The retry (same payload + token) flushes the pending record once.
    assert writer.put_bytes(payload, timeout=5, seq=seq) == seq
    # A later token-less put of EQUAL bytes is a brand-new record.
    assert writer.put_bytes(payload, timeout=5) == seq + 1
    got = [reader.get_bytes(timeout=10) for _ in range(2)]
    assert got == [payload, payload]
    # Re-retrying an already-delivered token is a no-op...
    assert writer.put_bytes(payload, timeout=5, seq=seq) == seq
    # ...and an unknown (future) token is rejected loudly.
    with pytest.raises(ValueError):
        writer.put_bytes(payload, seq=writer._next_tx_seq + 7)
    # The stream stayed in sync: a fresh record still round-trips.
    writer.put(("v", 42), timeout=5)
    assert reader.get(timeout=10) == ("v", 42)


def test_execute_retry_resumes_torn_fanout():
    """A timed-out execute() leaves some input channels without its
    record; the NEXT execute() must finish that fanout exactly once
    per channel (using the transport's retry token where one was
    issued) before submitting the new record — so per-channel streams
    stay aligned with the DAG's seq accounting and nothing double-
    delivers."""
    from ray_tpu.dag.channels import ChannelTimeoutError
    from ray_tpu.dag.compiled import _WHOLE, CompiledDAG

    class FakeChan:
        def __init__(self, fail_first=False, token=None):
            self.records = []
            self.fail_first = fail_first
            self.token = token
            self.seq_retries = []

        def put(self, record, timeout=None, **kw):
            if "seq" in kw and kw["seq"] is not None:
                # retry token: the pending record completes, once.
                self.seq_retries.append(kw["seq"])
                self.records.append(record)
                return
            if self.fail_first:
                self.fail_first = False
                err = ChannelTimeoutError("put")
                err.seq = self.token
                raise err
            self.records.append(record)

    good = FakeChan()
    slow = FakeChan(fail_first=True, token=7)
    untried = FakeChan()

    class FakeOut:
        def __init__(self, records):
            self.records = list(records)

        def get(self, timeout=None):
            return self.records.pop(0)

    dag = CompiledDAG.__new__(CompiledDAG)
    dag._lock = threading.Lock()
    dag._read_mutex = threading.Lock()
    dag._submit_mutex = threading.Lock()
    dag._torn_down = False
    dag._next_seq = 0
    dag._next_read_seq = 0
    dag._results = {}
    dag._orphan_seqs = set()
    dag._pending_inputs = []
    dag._root = None  # not a MultiOutputNode: single output value
    dag._input_channels = [
        (good, _WHOLE), (slow, _WHOLE), (untried, _WHOLE)
    ]

    with pytest.raises(ChannelTimeoutError):
        dag.execute("v1", timeout=0.1)
    # good got the record; slow + untried are parked with v1's tail.
    assert [r for _, r, _ in dag._pending_inputs] == [
        ("v", "v1"), ("v", "v1")
    ]
    assert dag._pending_inputs[0][2] == 7  # slow's retry token
    assert dag._orphan_seqs == {0}  # seq 0 raised: nobody holds a ref

    ref = dag.execute("v2", timeout=5)
    assert dag._pending_inputs == []
    # Every channel saw v1 exactly once, then v2 exactly once.
    for chan in (good, slow, untried):
        assert chan.records == [("v", "v1"), ("v", "v2")], chan.records
    # slow's v1 landed via its retry token, not a duplicate record.
    assert slow.seq_retries == [7]
    # The torn execute still consumed DAG seq 0; the retry got seq 1.
    assert ref._seq == 1

    # The orphaned seq-0 output is read-and-discarded (never cached):
    # ref(1).get() skips past it and nothing leaks in _results.
    dag._output_channels = [FakeOut([("v", "r0"), ("v", "r1")])]
    assert ref.get(timeout=5) == "r1"
    assert dag._results == {}
    assert dag._orphan_seqs == set()


# ---------------------------------------------------------------------------
# regression: request_resources packs against node TOTALS
# ---------------------------------------------------------------------------


class _FakeProvider:
    head_address = "unused"

    def __init__(self):
        self.nodes = ["n0"]
        self.created = []

    def non_terminated_nodes(self):
        return list(self.nodes)

    def node_type(self, p):
        return "cpu"

    def cluster_node_id(self, p):
        return "daemon-0"

    def create_node(self, node_type, resources, labels):
        name = f"new-{len(self.created)}"
        self.created.append(name)
        self.nodes.append(name)
        return name

    def terminate_node(self, p):
        self.nodes.remove(p)


def _autoscaler_with_busy_node():
    from ray_tpu.autoscaler.autoscaler import (
        NodeTypeConfig,
        StandardAutoscaler,
    )

    provider = _FakeProvider()
    autoscaler = StandardAutoscaler(
        provider,
        {"cpu": NodeTypeConfig(resources={"CPU": 4.0}, max_workers=5)},
        idle_timeout_s=999.0,
    )
    load = {
        "infeasible": [],
        "pending_placement_groups": [],
        # ONE live node, busy: 0.5 of its 4 CPUs available.
        "nodes": [
            {
                "node_id": "daemon-0",
                "available": {"CPU": 0.5},
                "total": {"CPU": 4.0},
                "queued": 0,
                "labels": {},
            }
        ],
        "resource_requests": [],
    }
    autoscaler._load = lambda: load
    return autoscaler, provider, load


def test_request_resources_pack_against_totals_not_available():
    """A standing {CPU:2} target on a busy 4-CPU node must NOT launch
    a new node (HandleRequestClusterResourceConstraint packs against
    totals) — and the satisfying node is held against scale-down."""
    autoscaler, provider, load = _autoscaler_with_busy_node()
    load["resource_requests"] = [{"CPU": 2.0}]
    result = autoscaler.update()
    assert result["launched"] == []
    assert result["unsatisfied_requests"] == 0
    assert provider.created == []
    assert "n0" in autoscaler._last_busy  # held (busy-marked), no flap


def test_request_resources_still_launches_when_totals_exhausted():
    autoscaler, provider, load = _autoscaler_with_busy_node()
    # 2 bundles: the first consumes half the node's TOTAL, the second
    # (4 CPUs) no longer fits any total -> exactly one launch.
    load["resource_requests"] = [{"CPU": 2.0}, {"CPU": 4.0}]
    result = autoscaler.update()
    assert len(result["launched"]) == 1
    assert result["unsatisfied_requests"] == 0


def test_task_demand_still_packs_against_available():
    """Pending TASK demand genuinely consumes capacity, so it must
    keep packing against availability: a 2-CPU infeasible task on the
    busy (0.5 CPU free) node launches a worker."""
    autoscaler, provider, load = _autoscaler_with_busy_node()
    load["infeasible"] = [{"CPU": 2.0}]
    result = autoscaler.update()
    assert len(result["launched"]) == 1


# ---------------------------------------------------------------------------
# regression: session namespace reaches workers
# ---------------------------------------------------------------------------


def test_namespace_propagates_into_tasks_and_nested_actors():
    import ray_tpu as rt

    rt.init(num_cpus=2, namespace="apps")
    try:

        @rt.remote
        class Registry:
            def ping(self):
                return "ok"

        registry = Registry.options(name="registry").remote()
        assert rt.get(registry.ping.remote(), timeout=60) == "ok"

        @rt.remote
        def lookup():
            # No explicit namespace: must resolve in the SESSION
            # namespace, not a hardcoded "default".
            return rt.get_actor("registry").actor_id.hex()

        assert (
            rt.get(lookup.remote(), timeout=60)
            == registry.actor_id.hex()
        )

        @rt.remote
        def make_named():
            @rt.remote
            class Inner:
                def ping(self):
                    return "pong"

            handle = Inner.options(name="inner").remote()
            rt.get(handle.ping.remote(), timeout=60)
            return handle.actor_id.hex()

        inner_id = rt.get(make_named.remote(), timeout=90)
        # Registered in the session namespace...
        assert (
            rt.get_actor("inner", namespace="apps").actor_id.hex()
            == inner_id
        )
        # ...and NOT leaked into "default".
        with pytest.raises(ValueError):
            rt.get_actor("inner", namespace="default")
    finally:
        rt.shutdown()


# ---------------------------------------------------------------------------
# regression: request_resources(num_cpus=...) validation
# ---------------------------------------------------------------------------


def test_request_resources_rejects_bad_num_cpus_up_front():
    """Validation precedes any cluster traffic (no init() needed):
    fractional and negative targets raise instead of truncating."""
    from ray_tpu.autoscaler.sdk import request_resources

    with pytest.raises(ValueError, match="whole number"):
        request_resources(num_cpus=2.5)
    with pytest.raises(ValueError, match=">= 0"):
        request_resources(num_cpus=-1)
    with pytest.raises(TypeError):
        request_resources(num_cpus="4")
    with pytest.raises(TypeError):
        request_resources(num_cpus=True)
    # Valid shapes pass validation and reach the session gate.
    for num_cpus in (None, 0, 4, 4.0):
        with pytest.raises(RuntimeError, match="init"):
            request_resources(num_cpus=num_cpus)
