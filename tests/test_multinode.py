"""Multi-node cluster tests (reference test model: ray_start_cluster
fixture + python/ray/tests/test_multi_node*.py — scheduling spillback,
cross-node objects, node failure handling)."""

import os
import time

import numpy as np
import pytest


@pytest.fixture(params=["unix", "tcp"])
def cluster(request):
    """Every multinode scenario runs twice: once over Unix sockets
    (single-host fast path) and once with all daemons forced onto TCP
    loopback — the cross-host DCN transport (review round-1 item 1)."""
    from ray_tpu.cluster_utils import Cluster

    c = Cluster(
        initialize_head=True,
        head_resources={"CPU": 2.0},
        use_tcp=(request.param == "tcp"),
    )
    yield c
    c.shutdown()


@pytest.fixture
def rt_cluster(cluster):
    import ray_tpu as rt

    rt.init(address=cluster.address)
    yield rt, cluster
    rt.shutdown()


def test_spillback_to_fitting_node(rt_cluster):
    rt, cluster = rt_cluster
    cluster.add_node(num_cpus=2, resources={"special": 2.0})
    cluster.wait_for_nodes(2)

    @rt.remote(resources={"special": 1.0})
    def where():
        import os as _os
        return _os.environ.get("RT_SOCKET", "")

    socket = rt.get(where.remote(), timeout=30)
    assert "node-1" in socket


def test_broadcast_to_many_nodes(rt_cluster):
    """One producer, consumers on several nodes: every node pulls the
    full object correctly (pipelined chunk window + randomized source
    selection — PushManager-style broadcast spread)."""
    rt, cluster = rt_cluster
    for i in range(3):
        cluster.add_node(num_cpus=1, resources={f"n{i}": 1.0})
    cluster.wait_for_nodes(4)

    @rt.remote(resources={"n0": 0.5})
    def produce():
        return np.arange(2_000_000, dtype=np.float64)  # ~16 MB

    ref = produce.remote()

    @rt.remote
    def check(x):
        return float(x[1_234_567]) == 1_234_567.0 and x.nbytes

    checks = [
        check.options(resources={f"n{i}": 1.0}).remote(ref)
        for i in range(3)
    ]
    results = rt.get(checks, timeout=120)
    assert all(r == 16_000_000 for r in results), results


def test_cross_node_large_object_transfer(rt_cluster):
    rt, cluster = rt_cluster
    node = cluster.add_node(num_cpus=2, resources={"special": 2.0})
    cluster.wait_for_nodes(2)

    @rt.remote(resources={"special": 1.0})
    def produce():
        return np.arange(300_000, dtype=np.float64)  # ~2.4 MB

    ref = produce.remote()
    arr = rt.get(ref, timeout=30)
    assert arr.shape == (300_000,)
    assert float(arr[12345]) == 12345.0

    # Large driver-side arg consumed on the remote node.
    big = np.ones(250_000, dtype=np.float64)
    big_ref = rt.put(big)

    @rt.remote(resources={"special": 1.0})
    def total(x):
        return float(x.sum())

    assert rt.get(total.remote(big_ref), timeout=30) == 250_000.0


def test_node_affinity_strategy(rt_cluster):
    rt, cluster = rt_cluster
    cluster.add_node(num_cpus=2)
    cluster.wait_for_nodes(2)
    target = next(
        n for n in rt.nodes() if not n["is_head"] and n["alive"]
    )

    from ray_tpu.util import NodeAffinitySchedulingStrategy

    @rt.remote
    def where():
        import os as _os
        return _os.environ.get("RT_SOCKET", "")

    strategy = NodeAffinitySchedulingStrategy(node_id=target["node_id"])
    socket = rt.get(
        where.options(scheduling_strategy=strategy).remote(), timeout=30
    )
    # Workers always ride their node's session Unix socket even when
    # the node advertises TCP; identify the node by session dir.
    target_node = next(
        n for n in cluster.nodes
        if n.node_id.hex() == target["node_id"]
    )
    assert socket == target_node.socket_path


def test_node_label_strategy(rt_cluster):
    rt, cluster = rt_cluster
    cluster.add_node(num_cpus=2, labels={"zone": "us-a"})
    cluster.add_node(num_cpus=2, labels={"zone": "us-b"})
    cluster.wait_for_nodes(3)

    from ray_tpu.util import NodeLabelSchedulingStrategy

    @rt.remote
    def where():
        import os as _os
        return _os.environ.get("RT_SOCKET", "")

    strategy = NodeLabelSchedulingStrategy(hard={"zone": ["us-b"]})
    socket = rt.get(
        where.options(scheduling_strategy=strategy).remote(), timeout=30
    )
    expected_id = next(
        n["node_id"] for n in rt.nodes()
        if n["labels"].get("zone") == "us-b"
    )
    expected = next(
        n.socket_path for n in cluster.nodes
        if n.node_id.hex() == expected_id
    )
    assert socket == expected


def test_spread_strategy_uses_multiple_nodes(rt_cluster):
    rt, cluster = rt_cluster
    cluster.add_node(num_cpus=2)
    cluster.add_node(num_cpus=2)
    cluster.wait_for_nodes(3)

    @rt.remote
    def where():
        time.sleep(0.05)
        import os as _os
        return _os.environ.get("RT_SOCKET", "")

    refs = [
        where.options(scheduling_strategy="SPREAD").remote()
        for _ in range(12)
    ]
    sockets = set(rt.get(refs, timeout=60))
    assert len(sockets) >= 2


def test_infeasible_task_waits_for_node(rt_cluster):
    rt, cluster = rt_cluster

    @rt.remote(resources={"accel": 1.0})
    def need_accel():
        return "ran"

    ref = need_accel.remote()
    ready, _ = rt.wait([ref], timeout=0.5)
    assert not ready  # infeasible: no node has `accel`
    cluster.add_node(num_cpus=1, resources={"accel": 1.0})
    assert rt.get(ref, timeout=30) == "ran"


def test_remote_actor_and_named_lookup(rt_cluster):
    rt, cluster = rt_cluster
    cluster.add_node(num_cpus=2, resources={"special": 1.0})
    cluster.wait_for_nodes(2)

    @rt.remote(resources={"special": 1.0}, name="counter")
    class Counter:
        def __init__(self):
            self.n = 0

        def incr(self, k=1):
            self.n += k
            return self.n

        def node(self):
            import os as _os
            return _os.environ.get("RT_SOCKET", "")

    counter = Counter.remote()
    assert rt.get(counter.incr.remote(), timeout=30) == 1
    assert rt.get(counter.incr.remote(5), timeout=30) == 6
    assert "node-1" in rt.get(counter.node.remote(), timeout=30)

    fetched = rt.get_actor("counter")
    assert rt.get(fetched.incr.remote(), timeout=30) == 7


def test_task_retry_on_node_death(rt_cluster):
    rt, cluster = rt_cluster
    node = cluster.add_node(num_cpus=2, resources={"special": 1.0})
    cluster.wait_for_nodes(2)

    from ray_tpu.util import NodeAffinitySchedulingStrategy

    @rt.remote(max_retries=2)
    def slow_value():
        time.sleep(1.5)
        return "done"

    target = next(n for n in rt.nodes() if not n["is_head"])
    ref = slow_value.options(
        scheduling_strategy=NodeAffinitySchedulingStrategy(
            node_id=target["node_id"], soft=True
        )
    ).remote()
    time.sleep(0.6)  # let it start on the doomed node
    cluster.remove_node(node)
    # Retried on a surviving node (head) and completes.
    assert rt.get(ref, timeout=60) == "done"


def test_actor_restart_on_node_death(rt_cluster):
    rt, cluster = rt_cluster
    node = cluster.add_node(num_cpus=2, resources={"special": 1.0})
    cluster.wait_for_nodes(2)

    @rt.remote(resources={"CPU": 1.0}, max_restarts=1)
    class Stateful:
        def __init__(self):
            self.n = 0

        def incr(self):
            self.n += 1
            return self.n

        def node(self):
            import os as _os
            return _os.environ.get("RT_SOCKET", "")

    from ray_tpu.util import NodeAffinitySchedulingStrategy

    target = next(n for n in rt.nodes() if not n["is_head"])
    actor = Stateful.options(
        scheduling_strategy=NodeAffinitySchedulingStrategy(
            node_id=target["node_id"], soft=True
        )
    ).remote()
    assert rt.get(actor.incr.remote(), timeout=30) == 1
    assert "node-1" in rt.get(actor.node.remote(), timeout=30)

    cluster.remove_node(node)
    # Restarted (state reset) on a surviving node.
    deadline = time.time() + 60
    value = None
    while time.time() < deadline:
        try:
            value = rt.get(actor.incr.remote(), timeout=10)
            break
        except Exception:
            time.sleep(0.3)
    assert value == 1
    assert "head" in rt.get(actor.node.remote(), timeout=30)


def test_cluster_resources_aggregate(rt_cluster):
    rt, cluster = rt_cluster
    cluster.add_node(num_cpus=3, resources={"special": 5.0})
    cluster.wait_for_nodes(2)
    total = rt.cluster_resources()
    assert total["CPU"] == 5.0  # 2 head + 3 node
    assert total["special"] == 5.0


def test_nested_task_submission_from_remote_node(rt_cluster):
    rt, cluster = rt_cluster
    cluster.add_node(num_cpus=2, resources={"special": 2.0})
    cluster.wait_for_nodes(2)

    @rt.remote
    def inner(x):
        return x * 2

    @rt.remote(resources={"special": 1.0})
    def outer():
        import ray_tpu as rt2

        refs = [inner.remote(i) for i in range(4)]
        return sum(rt2.get(refs, timeout=30))

    assert rt.get(outer.remote(), timeout=60) == 12


def test_versioned_heartbeats_elide_unchanged_load(rt_cluster):
    """Resource snapshots ride heartbeats only when they CHANGED since
    the head's last ack (reference: ray_syncer versioned resource
    messages) — idle nodes beat liveness-only."""
    rt, cluster = rt_cluster
    node = cluster.add_node(num_cpus=1, resources={"special": 1.0})
    cluster.wait_for_nodes(2)

    head = cluster.head
    seen = []
    orig = head._h_node_heartbeat

    def spy(conn, msg):
        if msg.get("node_id") == node.node_id.binary():
            seen.append("available" in msg)
        return orig(conn, msg)

    head.server._handlers["node_heartbeat"] = spy
    try:
        time.sleep(1.5)  # ~6 idle beats
        idle = list(seen)
        assert len(idle) >= 3
        # After the initial (changed) beat, payloads stop.
        assert not any(idle[1:]), idle

        seen.clear()

        @rt.remote(resources={"special": 1.0})
        def touch():
            time.sleep(0.8)  # hold the resource across several beats
            return 1

        assert rt.get(touch.remote(), timeout=30) == 1
        # Running a task changed availability -> payload reappears.
        deadline = time.time() + 10
        while time.time() < deadline and not any(seen):
            time.sleep(0.1)
        assert any(seen), seen
        # Head's view converges back to fully available once the
        # lease returns (idle lease timeout ~1s).
        deadline = time.time() + 10
        info = head.control.nodes[node.node_id]
        while time.time() < deadline:
            if info.available.get("special") == 1.0:
                break
            time.sleep(0.1)
        assert info.available.get("special") == 1.0
    finally:
        head.server._handlers["node_heartbeat"] = orig


def test_stream_crosses_nodes(rt_cluster):
    """A stream's items reach a consumer wherever it runs: the
    producer's node daemon forwards each append to the head, which
    holds the run, and a consumer's node daemon forwards its one
    parked request there (no thread to wait on it)."""
    rt, cluster = rt_cluster
    cluster.add_node(num_cpus=2, resources={"producer": 2.0})
    cluster.add_node(num_cpus=2, resources={"consumer": 2.0})
    cluster.wait_for_nodes(3)

    @rt.remote(num_returns="streaming", resources={"producer": 1.0})
    def words(n):
        for i in range(n):
            time.sleep(0.005)
            yield f"w{i}"
        raise ValueError("after the last word")

    def consume(n):
        got, gen = [], words.remote(n)
        try:
            for ref in gen:
                got.append(rt.get(ref, timeout=30))
        except ValueError as e:
            got.append(str(e))
        return got, gen.stream_items

    expected = [f"w{i}" for i in range(50)] + ["after the last word"]
    assert consume(50) == (expected, 50)  # the driver, at the head
    there = rt.remote(consume).options(resources={"consumer": 1.0})
    got, items = rt.get(there.remote(50), timeout=60)
    assert (got, items) == (expected, 50)
