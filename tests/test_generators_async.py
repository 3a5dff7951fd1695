"""Generator returns (num_returns="dynamic"/"streaming") and
concurrent actors (max_concurrency, async methods).

Reference behavior matched: python/ray/remote_function.py:385-391
(dynamic/streaming num_returns), python/ray/_raylet.pyx:269
(ObjectRefGenerator), src/ray/core_worker/transport/
concurrency_group_manager.h (threaded/async actors)."""

import time

import pytest


def test_dynamic_generator(rt_session):
    rt = rt_session

    @rt.remote(num_returns="dynamic")
    def gen(n):
        for i in range(n):
            yield i * 10

    ref = gen.remote(5)
    g = rt.get(ref, timeout=20)
    assert isinstance(g, rt.ObjectRefGenerator)
    assert [rt.get(r, timeout=10) for r in g] == [0, 10, 20, 30, 40]


def test_streaming_generator_incremental(rt_session):
    rt = rt_session

    @rt.remote
    def warm():
        return None

    rt.get(warm.remote(), timeout=30)  # pay worker spawn outside timing

    @rt.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            time.sleep(0.05)
            yield i

    t0 = time.monotonic()
    first_at = None
    got = []
    for r in gen.remote(4):
        got.append(rt.get(r, timeout=10))
        if first_at is None:
            first_at = time.monotonic() - t0
    assert got == [0, 1, 2, 3]
    # First item arrives while the task is still producing.
    assert first_at < 0.15, first_at


def test_streaming_generator_empty_and_error(rt_session):
    rt = rt_session

    @rt.remote(num_returns="streaming")
    def empty():
        return iter(())

    assert list(empty.remote()) == []

    @rt.remote(num_returns="streaming")
    def boom():
        yield 1
        raise ValueError("midstream")

    it = iter(boom.remote())
    assert rt.get(next(it), timeout=10) == 1
    with pytest.raises(ValueError, match="midstream"):
        for r in it:
            rt.get(r, timeout=10)


# -- the streaming transport's contract (stream_runs.py): the same
# code serves a consumer in the driver and one in a worker, so every
# case runs on both sides ------------------------------------------------

def _contract():
    """case -> (what a consumer runs, what it must see). Nested so
    that a worker receives the bodies by value."""
    def _order_kept():
        import ray_tpu as rt

        @rt.remote(num_returns="streaming")
        def count_up(n):
            for i in range(n):
                yield i

        return [rt.get(r, timeout=30) for r in count_up.remote(1000)]


    def _items_then_error():
        import ray_tpu as rt

        @rt.remote(num_returns="streaming")
        def three_then_boom():
            yield from ("a", "b", "c")
            raise ValueError("midstream")

        got = []
        try:
            for r in three_then_boom.remote():
                got.append(rt.get(r, timeout=30))
        except ValueError as e:
            got.append(str(e))
        return got


    def _empty_stream():
        import ray_tpu as rt

        @rt.remote(num_returns="streaming")
        def nothing():
            return iter(())

        gen = nothing.remote()
        items = list(gen)
        return items, gen.stream_items, next(gen, "still over")


    def _ref_escapes():
        """An item's ref handed on as an argument, and nested in a value
        (which makes it an object of the directory first)."""
        import ray_tpu as rt

        @rt.remote(num_returns="streaming")
        def words():
            yield "alpha"
            yield "beta"

        @rt.remote
        def shout(word):
            return word.upper()

        @rt.remote
        def shout_nested(box):
            return rt.get(box["ref"], timeout=30).upper()

        gen = words.remote()
        first, second = next(gen), next(gen)
        return rt.get(
            [shout.remote(first), shout_nested.remote({"ref": second})],
            timeout=30,
        )

    return {
        "order_kept": (_order_kept, list(range(1000))),
        "items_then_error": (
            _items_then_error, ["a", "b", "c", "midstream"]
        ),
        "empty_stream": (_empty_stream, ([], 0, "still over")),
        "ref_escapes": (_ref_escapes, ["ALPHA", "BETA"]),
    }


@pytest.mark.parametrize("consumer", ["driver", "worker"])
@pytest.mark.parametrize(
    "case",
    ["order_kept", "items_then_error", "empty_stream", "ref_escapes"],
)
def test_streaming_transport_contract(rt_session, case, consumer):
    rt = rt_session
    body, expected = _contract()[case]
    if consumer == "driver":
        got = body()
    else:
        got = rt.get(rt.remote(body).remote(), timeout=60)
    if isinstance(expected, tuple):
        got = tuple(got)
    assert got == expected


@pytest.mark.parametrize("consumer", ["driver", "worker"])
def test_streaming_producer_lost_mid_stream(rt_session, consumer):
    """The actor that produces a stream dies under it: the consumer,
    parked on the run, is told (by the marker's future where the call
    went directly, by the marker's error in the directory where it
    went through the daemon) and does not wait for ever."""
    rt = rt_session

    @rt.remote
    class Doomed:
        def tokens(self):
            import os

            yield "first"
            yield "second"
            os._exit(1)

    def consume(actor):
        import ray_tpu as rt

        got = []
        try:
            for ref in actor.tokens.options(
                num_returns="streaming"
            ).remote():
                got.append(rt.get(ref, timeout=30))
        except rt.exceptions.RayTpuError as e:
            got.append(type(e).__name__)
        return got

    actor = Doomed.remote()
    if consumer == "driver":
        got = consume(actor)
    else:
        got = rt.get(rt.remote(consume).remote(actor), timeout=60)
    # What was appended before the death may or may not have been
    # taken; the death itself always arrives.
    assert got[-1] == "ActorDiedError", got
    assert got[:-1] == ["first", "second"][: len(got) - 1]


def test_streaming_item_too_large_for_a_message(rt_session):
    """An item over the inline limit is sealed in the object store
    under its id; the run carries its place in the order."""
    rt = rt_session

    @rt.remote(num_returns="streaming")
    def mixed():
        yield "small"
        yield bytes(2_000_000)
        yield "small again"

    got = [rt.get(r, timeout=30) for r in mixed.remote()]
    assert got[0] == "small" and got[2] == "small again"
    assert got[1] == bytes(2_000_000)


def test_streaming_costs_the_head_nothing_per_item(
    rt_session, monkeypatch
):
    """200 items: no wait through the head and no Timer thread for any
    of them, no object in the directory, and the consumer's one parked
    request at a time is answered with the bytes (the head daemon
    lives in this process, so its threads are this process's)."""
    import threading

    from ray_tpu.util.state import event_stats

    rt = rt_session

    @rt.remote(num_returns="streaming")
    def count_up(n):
        for i in range(n):
            yield i

    assert [rt.get(r) for r in count_up.remote(3)] == [0, 1, 2]  # warm
    timers = []
    real_start = threading.Timer.start

    def counting_start(self):
        timers.append(self)
        real_start(self)

    monkeypatch.setattr(threading.Timer, "start", counting_start)
    calls = lambda name: event_stats().get(name, {}).get("count", 0)  # noqa: E731
    before = {
        name: calls(name)
        for name in ("wait_objects", "get_object", "put_inline",
                     "stream_append", "stream_fetch")
    }
    objects_before = len(rt.api._session.daemon.objects)
    gen = count_up.remote(200)
    assert [rt.get(r, timeout=30) for r in gen] == list(range(200))
    after = {name: calls(name) for name in before}
    assert after["wait_objects"] == before["wait_objects"]
    assert after["get_object"] == before["get_object"]
    assert after["put_inline"] == before["put_inline"]
    assert not timers
    assert after["stream_append"] - before["stream_append"] == 200
    fetches = after["stream_fetch"] - before["stream_fetch"]
    assert fetches == gen.stream_fetches <= 201  # the end may come alone
    assert gen.stream_items == 200
    assert len(rt.api._session.daemon.objects) <= objects_before + 1


def test_a_runs_first_item_carries_its_producers_time_once(rt_session):
    """The producer stamps a run's FIRST item with its epoch time (one
    field on one `stream_append`); the answer that brings item 0
    carries it to the consumer and no other answer does."""
    import time

    from ray_tpu._private.stream_runs import StreamRuns

    class Conn:
        conn_id = 1

        def __init__(self):
            self.replies = []

        def reply(self, mid, reply):
            self.replies.append(reply)

    runs, conn = StreamRuns(), Conn()
    runs.put(b"t", 0, b"a", first_ts=123.5)
    runs.put(b"t", 1, b"b")
    runs.fetch(conn, 1, b"t", 0)
    runs.fetch(conn, 2, b"t", 2)
    runs.put(b"t", 2, b"c")
    runs.end(b"t", 3, None)
    runs.fetch(conn, 3, b"t", 3)
    assert [r["items"] for r in conn.replies] == [[b"a", b"b"], [b"c"], []]
    assert [r.get("first_ts") for r in conn.replies] == [123.5, None, None]
    assert "first_ts" not in conn.replies[1]
    # A producer that stamps nothing (a run relayed by an older node):
    # the answer has no such key.
    runs.put(b"u", 0, b"a")
    runs.fetch(conn, 4, b"u", 0)
    assert "first_ts" not in conn.replies[-1]

    rt = rt_session

    @rt.remote(num_returns="streaming")
    def slow(n):
        for i in range(n):
            time.sleep(0.05)
            yield i

    gen = slow.remote(4)
    assert gen.first_item_ts is None
    before = time.time()
    assert gen.next_value() == 0
    stamp = gen.first_item_ts
    assert before <= stamp <= time.time()
    assert [gen.next_value() for _ in range(3)] == [1, 2, 3]
    assert gen.first_item_ts == stamp


def test_streaming_non_generator_rejected(rt_session):
    rt = rt_session

    @rt.remote(num_returns="dynamic")
    def not_gen():
        return 42

    with pytest.raises(TypeError, match="generator"):
        rt.get(rt.get(not_gen.remote(), timeout=10))

    with pytest.raises(ValueError, match="num_returns"):

        @rt.remote(num_returns="bogus")  # rt: noqa[RT102] — deliberate bad literal under test
        def bad():
            yield 1

        bad.remote()  # rt: noqa[RT106] — submit raises; no ref exists


def test_actor_streaming_method(rt_session):
    rt = rt_session

    @rt.remote
    class Tok:
        def tokens(self, n):
            for i in range(n):
                yield f"tok{i}"

    a = Tok.remote()
    out = [
        rt.get(r, timeout=10)
        for r in a.tokens.options(num_returns="streaming").remote(3)
    ]
    assert out == ["tok0", "tok1", "tok2"]


def test_threaded_actor_concurrency(rt_session):
    rt = rt_session

    @rt.remote(max_concurrency=4)
    class Par:
        def work(self, t):
            time.sleep(t)
            return t

    a = Par.remote()
    rt.get(a.work.remote(0.01), timeout=30)  # warm
    t0 = time.monotonic()
    rt.get([a.work.remote(0.3) for _ in range(4)], timeout=30)
    assert time.monotonic() - t0 < 0.9  # concurrent, not 1.2s serial


def test_async_actor_methods(rt_session):
    rt = rt_session

    @rt.remote(max_concurrency=4)
    class Async:
        async def sleepy(self, t):
            import asyncio

            await asyncio.sleep(t)
            return t

        async def add(self, a, b):
            return a + b

    a = Async.remote()
    assert rt.get(a.add.remote(2, 3), timeout=30) == 5
    t0 = time.monotonic()
    out = rt.get([a.sleepy.remote(0.3) for _ in range(4)], timeout=30)
    assert out == [0.3] * 4
    assert time.monotonic() - t0 < 0.9


def test_serial_actor_stays_serial(rt_session):
    rt = rt_session

    @rt.remote
    class Serial:
        def __init__(self):
            self.active = 0
            self.max_active = 0

        def work(self):
            self.active += 1
            self.max_active = max(self.max_active, self.active)
            time.sleep(0.05)
            self.active -= 1
            return self.max_active

    a = Serial.remote()
    results = rt.get([a.work.remote() for _ in range(5)], timeout=30)
    assert max(results) == 1  # never interleaved
