"""Core task/actor/object API tests (modeled on the reference's
python/ray/tests/test_basic*.py / test_actor*.py coverage)."""

import numpy as np
import pytest

import ray_tpu as rt
from ray_tpu import exceptions as exc


@pytest.fixture(autouse=True)
def _session():
    rt.init(num_cpus=4, ignore_reinit_error=True)
    yield
    rt.shutdown()


def test_simple_task():
    @rt.remote
    def add(a, b):
        return a + b

    assert rt.get(add.remote(1, 2)) == 3


def test_task_kwargs_and_closure():
    base = 100

    @rt.remote
    def f(a, b=10):
        return a + b + base

    assert rt.get(f.remote(1)) == 111
    assert rt.get(f.remote(1, b=20)) == 121


def test_many_tasks():
    @rt.remote
    def sq(x):
        return x * x

    refs = [sq.remote(i) for i in range(50)]
    assert rt.get(refs) == [i * i for i in range(50)]


def test_put_get_roundtrip_small():
    ref = rt.put({"a": [1, 2, 3], "b": "hello"})
    assert rt.get(ref) == {"a": [1, 2, 3], "b": "hello"}


def test_put_get_large_numpy_zero_copy():
    arr = np.arange(500_000, dtype=np.float32).reshape(500, 1000)
    ref = rt.put(arr)
    out = rt.get(ref)
    np.testing.assert_array_equal(out, arr)
    # Large objects come back as views over shared memory (zero-copy).
    assert not out.flags.writeable


def test_object_ref_as_arg():
    @rt.remote
    def total(x):
        return float(x.sum())

    arr = np.ones(300_000, dtype=np.float64)
    ref = rt.put(arr)
    assert rt.get(total.remote(ref)) == 300_000.0


def test_chained_tasks():
    @rt.remote
    def inc(x):
        return x + 1

    ref = inc.remote(0)
    for _ in range(5):
        ref = inc.remote(ref)
    assert rt.get(ref) == 6


def test_num_returns():
    @rt.remote(num_returns=3)
    def three():
        return 1, 2, 3

    a, b, c = three.remote()
    assert rt.get([a, b, c]) == [1, 2, 3]


def test_task_error_propagates_type():
    @rt.remote
    def boom():
        raise ValueError("broken")

    with pytest.raises(ValueError, match="broken"):
        rt.get(boom.remote())


def test_error_propagates_through_dependency():
    @rt.remote
    def boom():
        raise KeyError("first")

    @rt.remote
    def use(x):
        return x

    with pytest.raises(KeyError):
        rt.get(use.remote(boom.remote()))


def test_get_timeout():
    @rt.remote
    def slow():
        import time

        time.sleep(30)

    with pytest.raises(exc.GetTimeoutError):
        rt.get(slow.remote(), timeout=0.2)


def test_wait():
    @rt.remote
    def fast(i):
        return i

    @rt.remote
    def slow():
        import time

        time.sleep(30)

    refs = [fast.remote(i) for i in range(3)] + [slow.remote()]
    ready, remaining = rt.wait(refs, num_returns=3, timeout=10)
    assert len(ready) == 3
    assert len(remaining) == 1


def test_an_answered_wait_leaves_no_timer_thread_behind():
    """A wait with a timeout parks a `threading.Timer` in the head's
    process; it must end with the wait, not live out its timeout (a
    token stream waits once per chunk, each with a 60 s bound)."""
    import threading
    import time

    def timers():
        return sum(
            isinstance(t, threading.Timer) for t in threading.enumerate()
        )

    before = timers()
    for i in range(12):
        # A stored object: the wait is the head's to answer.
        ready, _ = rt.wait([rt.put(i)], timeout=300)
        assert len(ready) == 1
    deadline = time.monotonic() + 5
    while timers() > before and time.monotonic() < deadline:
        time.sleep(0.05)
    assert timers() <= before


def test_nested_tasks():
    @rt.remote
    def inner(x):
        return x * 2

    @rt.remote
    def outer(x):
        return rt.get(inner.remote(x)) + 1

    assert rt.get(outer.remote(10)) == 21


def test_actor_basics():
    @rt.remote
    class Counter:
        def __init__(self, start=0):
            self.v = start

        def inc(self, by=1):
            self.v += by
            return self.v

        def value(self):
            return self.v

    c = Counter.remote(5)
    assert rt.get(c.inc.remote()) == 6
    assert rt.get(c.inc.remote(by=4)) == 10
    assert rt.get(c.value.remote()) == 10


def test_actor_ordering():
    @rt.remote
    class Appender:
        def __init__(self):
            self.items = []

        def append(self, x):
            self.items.append(x)

        def get(self):
            return self.items

    a = Appender.remote()
    refs = [a.append.remote(i) for i in range(20)]
    rt.get(refs)  # surface append errors instead of discarding refs
    assert rt.get(a.get.remote()) == list(range(20))


def test_named_actor():
    @rt.remote
    class Registry:
        def ping(self):
            return "pong"

    Registry.options(name="reg").remote()
    handle = rt.get_actor("reg")
    assert rt.get(handle.ping.remote()) == "pong"


def test_actor_handle_passing():
    @rt.remote
    class Store:
        def __init__(self):
            self.v = 0

        def set(self, v):
            self.v = v

        def get(self):
            return self.v

    @rt.remote
    def writer(store):
        rt.get(store.set.remote(42))
        return True

    s = Store.remote()
    rt.get(writer.remote(s))
    assert rt.get(s.get.remote()) == 42


def test_actor_error():
    @rt.remote
    class Bad:
        def fail(self):
            raise RuntimeError("actor method failed")

    b = Bad.remote()
    with pytest.raises(RuntimeError, match="actor method failed"):
        rt.get(b.fail.remote())


def test_kill_actor():
    @rt.remote
    class Victim:
        def ping(self):
            return "alive"

    v = Victim.remote()
    assert rt.get(v.ping.remote()) == "alive"
    rt.kill(v)
    with pytest.raises(
        (exc.ActorDiedError, exc.ActorUnavailableError, exc.WorkerCrashedError)
    ):
        rt.get(v.ping.remote(), timeout=10)


def test_actor_restart_keeps_creation_args_pinned():
    """Creation args must survive the caller dropping its ObjectRef and
    the first creation completing: restarts re-run the creation task
    with the same args (reference: lineage pinning, reference_count.h)."""

    @rt.remote(max_restarts=1)
    class Holder:
        def __init__(self, payload):
            self.total = int(payload.sum())

        def value(self):
            return self.total

        def die(self):
            import os

            os._exit(1)

    arr = np.ones(300_000, dtype=np.float32)  # large → real shm object
    ref = rt.put(arr)
    h = Holder.remote(ref)
    assert rt.get(h.value.remote(), timeout=30) == 300_000
    del ref  # caller handle drop must not delete the pinned arg
    import gc

    gc.collect()
    with pytest.raises(
        (exc.ActorDiedError, exc.ActorUnavailableError, exc.WorkerCrashedError)
    ):
        rt.get(h.die.remote(), timeout=30)
    # After restart the creation arg was still available.
    assert rt.get(h.value.remote(), timeout=30) == 300_000


def test_kill_queued_actor_seals_creation_and_unpins():
    """kill() of an actor whose creation task is still queued must fail
    the creation returns and release pinned args (no object leak)."""
    import time

    @rt.remote
    def blocker():
        time.sleep(60)

    arr = np.ones(300_000, dtype=np.float32)
    ref = rt.put(arr)
    blockers = [blocker.remote() for _ in range(4)]  # saturate 4 CPUs
    time.sleep(0.3)

    @rt.remote(num_cpus=1)
    class Queued:
        def __init__(self, payload):
            self.payload = payload

        def ping(self):
            return 1

    q = Queued.remote(ref)
    time.sleep(0.3)
    rt.kill(q)
    with pytest.raises(
        (exc.ActorDiedError, exc.ActorUnavailableError, exc.WorkerCrashedError)
    ):
        rt.get(q.ping.remote(), timeout=10)
    # Dropping the caller's ref must now actually delete the object:
    # the daemon's pin was released by the kill.
    del ref
    import gc

    gc.collect()
    deadline = time.time() + 10
    while time.time() < deadline:
        used = rt.state_summary().get("used", 0)
        if used < arr.nbytes:
            break
        time.sleep(0.2)
    assert used < arr.nbytes, f"creation arg leaked ({used} bytes in use)"
    del blockers


def test_cancel_queued_task():
    @rt.remote
    def blocker():
        import time

        time.sleep(60)

    @rt.remote
    def victim():
        return 1

    # Saturate the 4 CPUs, then queue + cancel the victim.
    blockers = [blocker.remote() for _ in range(4)]
    ref = victim.remote()
    import time

    time.sleep(0.5)
    rt.cancel(ref)
    with pytest.raises((exc.TaskCancelledError, exc.RayTpuError)):
        rt.get(ref, timeout=5)
    del blockers


def test_cluster_resources():
    total = rt.cluster_resources()
    assert total["CPU"] == 4.0


def test_fractional_resources():
    @rt.remote(num_cpus=0.5)
    def half():
        return 1

    assert rt.get([half.remote() for _ in range(8)]) == [1] * 8


def test_task_events_recorded():
    @rt.remote
    def traced():
        return 1

    rt.get(traced.remote())
    # task_done (which records FINISHED) is a fire-and-forget
    # notification that can land just after get() returns.
    import time

    states = []
    for _ in range(50):
        events = rt.timeline()
        states = [e["state"] for e in events if e["name"] == "traced"]
        if "FINISHED" in states:
            break
        time.sleep(0.1)
    assert "RUNNING" in states
    assert "FINISHED" in states


def test_runtime_context():
    """get_runtime_context() exposes job/node/task/actor identity in
    every execution context (reference: runtime_context.py:30)."""
    ctx = rt.get_runtime_context()
    assert len(ctx.get_job_id()) > 0
    assert len(ctx.get_node_id()) == 32
    assert ctx.get_task_id() is None  # driver
    assert ctx.get_actor_id() is None
    assert "TPU" in ctx.get_accelerator_ids()

    @rt.remote
    def inside_task():
        c = rt.get_runtime_context()
        return (c.get_task_id(), c.get_actor_id(), c.get_job_id())

    task_id, actor_id, job_id = rt.get(inside_task.remote(), timeout=30)
    assert task_id is not None and actor_id is None
    assert job_id == ctx.get_job_id()

    @rt.remote
    class Inside:
        def who(self):
            c = rt.get_runtime_context()
            return (c.get_actor_id(), c.get_task_id())

    a = Inside.remote()
    actor_id, task_id = rt.get(a.who.remote(), timeout=30)
    assert actor_id is not None and task_id is not None


def test_runtime_context_async_actor():
    """Task identity inside ASYNC actor methods (coroutines run on the
    shared loop thread; identity rides an asyncio-task-local
    contextvar, so interleaved calls can't cross-contaminate)."""

    @rt.remote(max_concurrency=4)
    class AsyncIdent:
        async def who(self):
            import asyncio

            c = rt.get_runtime_context()
            first = c.get_task_id()
            await asyncio.sleep(0.05)  # force interleaving
            return (first, c.get_task_id())

    a = AsyncIdent.remote()
    pairs = rt.get([a.who.remote() for _ in range(4)], timeout=30)
    ids = set()
    for first, after_await in pairs:
        assert first is not None
        # Identity survives the await AND is unique per call.
        assert first == after_await
        ids.add(first)
    assert len(ids) == 4


def test_duplicate_actor_name_surfaces_error():
    """Creates are pipelined one-way notifies, so a name collision
    can't ride the create's RPC reply — it must still surface as a
    detectable failure on the duplicate handle's method calls
    (reference: ray raises on duplicate named actors; here the dead
    handle errors instead of hanging)."""

    @rt.remote
    class Named:
        def ping(self):
            return "first"

    first = Named.options(name="dup-name").remote()
    assert rt.get(first.ping.remote(), timeout=60) == "first"

    second = Named.options(name="dup-name").remote()
    with pytest.raises(Exception) as exc_info:
        rt.get(second.ping.remote(), timeout=30)
    assert "dead" in str(exc_info.value).lower() or "registration" in str(
        exc_info.value
    ).lower()

    # The original actor is untouched by the failed duplicate.
    assert rt.get(first.ping.remote(), timeout=60) == "first"
