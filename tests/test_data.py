"""Data tests (reference test model: python/ray/data/tests/ — lazy
transforms, shuffles, file IO round-trips, streaming split)."""

import numpy as np
import pytest


def test_range_map_filter_count(rt_session):
    from ray_tpu import data

    ds = (
        data.range(1000, parallelism=8)
        .map(lambda row: {"id": row["id"], "double": row["id"] * 2})
        .filter(lambda row: row["id"] % 10 == 0)
    )
    assert ds.count() == 100
    rows = ds.take(3)
    assert rows[0] == {"id": 0, "double": 0}


def test_map_batches_numpy(rt_session):
    from ray_tpu import data

    ds = data.range(256, parallelism=4).map_batches(
        lambda batch: {"sq": batch["id"] ** 2},
        batch_size=64,
        batch_format="numpy",
    )
    out = ds.to_numpy()
    np.testing.assert_array_equal(
        out["sq"], np.arange(256) ** 2
    )


def test_flat_map_and_limit(rt_session):
    from ray_tpu import data

    ds = data.from_items([1, 2, 3]).flat_map(
        lambda row: [
            {"v": row["item"]},
            {"v": row["item"] * 10},
        ]
    )
    assert [r["v"] for r in ds.take_all()] == [1, 10, 2, 20, 3, 30]
    assert data.range(100).limit(7).count() == 7


def test_repartition_and_shuffle(rt_session):
    from ray_tpu import data

    ds = data.range(100, parallelism=2).repartition(5).materialize()
    assert ds.num_blocks() == 5
    assert ds.count() == 100

    shuffled = data.range(50, parallelism=4).random_shuffle(seed=7)
    ids = [r["id"] for r in shuffled.take_all()]
    assert sorted(ids) == list(range(50))
    assert ids != list(range(50))


def test_sort(rt_session):
    from ray_tpu import data

    rng = np.random.default_rng(0)
    values = rng.permutation(200).tolist()
    ds = data.from_items(
        [{"v": v} for v in values], parallelism=4
    ).sort("v")
    out = [r["v"] for r in ds.take_all()]
    assert out == sorted(values)
    desc = (
        data.from_items([{"v": v} for v in values], parallelism=4)
        .sort("v", descending=True)
        .take_all()
    )
    assert [r["v"] for r in desc] == sorted(values, reverse=True)


def test_groupby_aggregations(rt_session):
    from ray_tpu import data

    ds = data.range(100, parallelism=4).map(
        lambda row: {"key": row["id"] % 3, "value": row["id"]}
    )
    counts = {
        r["key"]: r["count"]
        for r in ds.groupby("key").count().take_all()
    }
    assert counts == {0: 34, 1: 33, 2: 33}
    means = {
        r["key"]: r["mean(value)"]
        for r in ds.groupby("key").mean("value").take_all()
    }
    assert means[0] == pytest.approx(49.5)


def test_file_round_trips(rt_session, tmp_path):
    from ray_tpu import data

    ds = data.range(64, parallelism=2).map(
        lambda row: {"id": row["id"], "name": f"row{row['id']}"}
    )
    for fmt, reader in [
        ("csv", data.read_csv),
        ("json", data.read_json),
        ("parquet", data.read_parquet),
    ]:
        out_dir = str(tmp_path / fmt)
        getattr(ds, f"write_{fmt}")(out_dir)
        back = reader(out_dir)
        rows = sorted(back.take_all(), key=lambda r: r["id"])
        assert len(rows) == 64
        assert rows[5]["name"] == "row5"


def test_streaming_split_disjoint_and_complete(rt_session):
    from ray_tpu import data

    ds = data.range(300, parallelism=6)
    its = ds.streaming_split(3, equal=True)
    seen = [
        {row["id"] for row in it.iter_rows()} for it in its
    ]
    assert set().union(*seen) == set(range(300))
    assert sum(len(s) for s in seen) == 300  # disjoint


def test_train_dataset_integration_local(rt_session):
    """datasets= flows into the trainer and surfaces as a per-rank
    streaming shard (reference: DataConfig streaming split into
    train.get_dataset_shard)."""
    from ray_tpu import data, train

    ds = data.range(128, parallelism=4)

    def loop(config):
        shard = train.get_dataset_shard("train")
        total = 0
        count = 0
        for batch in shard.iter_batches(batch_size=32):
            total += int(batch["id"].sum())
            count += len(batch["id"])
        train.report({"total": total, "count": count})

    result = train.JaxTrainer(
        loop, train_loop_config={}, datasets={"train": ds}
    ).fit()
    assert result.error is None
    assert result.metrics["count"] == 128
    assert result.metrics["total"] == sum(range(128))


def test_train_dataset_integration_gang(rt_session):
    from ray_tpu import data, train

    ds = data.range(120, parallelism=6)

    def loop(config):
        shard = train.get_dataset_shard("train")
        ids = [row["id"] for row in shard.iter_rows()]
        train.report({"n": len(ids), "sum": sum(ids)})

    result = train.JaxTrainer(
        loop,
        train_loop_config={},
        scaling_config=train.ScalingConfig(
            num_workers=2, resources_per_worker={"CPU": 1}
        ),
        backend=train.CpuTestBackend(),
        datasets={"train": ds},
    ).fit()
    # Trainer wires shards to every rank; the gang result carries
    # rank 0's metrics only, but both shards together cover the data.
    assert result.error is None
    assert 0 < result.metrics["n"] < 120


def test_iter_batches_sizes(rt_session):
    from ray_tpu import data

    batches = list(
        data.range(100, parallelism=3).iter_batches(
            batch_size=32, batch_format="numpy"
        )
    )
    sizes = [len(b["id"]) for b in batches]
    assert sizes == [32, 32, 32, 4]
    all_ids = np.concatenate([b["id"] for b in batches])
    np.testing.assert_array_equal(np.sort(all_ids), np.arange(100))


def _prefetch_threads():
    import threading

    return [
        t
        for t in threading.enumerate()
        if t.name.startswith("rt-data-prefetch") and t.is_alive()
    ]


def test_iter_batches_prefetch_matches_serial(rt_session):
    """prefetch_batches=k must be invisible in the output: identical
    batch boundaries, identical values, identical order vs the serial
    iterator — for full batches and the drop_last tail alike."""
    from ray_tpu import data

    def build():
        return data.range(100, parallelism=3)

    for drop_last in (False, True):
        serial = list(
            build().iter_batches(batch_size=32, drop_last=drop_last)
        )
        prefetched = list(
            build().iter_batches(
                batch_size=32, drop_last=drop_last, prefetch_batches=3
            )
        )
        assert len(serial) == len(prefetched)
        for s, p in zip(serial, prefetched):
            np.testing.assert_array_equal(s["id"], p["id"])
    assert not _prefetch_threads(), "prefetch thread outlived iteration"


def test_iter_batches_prefetch_zero_is_serial_path(rt_session):
    """prefetch_batches=0 must behave exactly like today's iterator:
    same sizes, same values, and no background thread at all."""
    from ray_tpu import data

    batches = []
    for batch in data.range(100, parallelism=3).iter_batches(
        batch_size=32, prefetch_batches=0
    ):
        batches.append(batch)
        # The serial path never starts a producer thread, even while
        # the stream is being consumed.
        assert not _prefetch_threads()
    sizes = [len(b["id"]) for b in batches]
    assert sizes == [32, 32, 32, 4]


def test_iter_batches_prefetch_early_break_no_leaks(rt_session):
    """Breaking out of a prefetching iterator mid-stream must cancel
    the producer: no leaked rt-data-prefetch threads, and the block
    get in flight completes instead of dangling."""
    import time

    from ray_tpu import data

    ds = data.range(400, parallelism=8)
    seen = []
    for batch in ds.iter_batches(batch_size=16, prefetch_batches=4):
        seen.append(batch["id"][0])
        if len(seen) >= 2:
            break  # generator close -> producer cancel
    assert len(seen) == 2
    deadline = time.time() + 5.0
    while _prefetch_threads() and time.time() < deadline:
        time.sleep(0.05)
    assert not _prefetch_threads(), (
        f"leaked prefetch threads: {_prefetch_threads()}"
    )
    # The session still works after the cancelled stream (no dangling
    # gets poisoning the runtime).
    import ray_tpu as rt

    assert rt.get(rt.put(41), timeout=30) == 41


def test_iter_batches_prefetch_propagates_udf_error(rt_session):
    """An exception raised by upstream block tasks must re-raise at
    the consumer's next(), not vanish into the producer thread."""
    import pytest as _pytest

    from ray_tpu import data

    def explode(row):
        if row["id"] == 37:
            raise ValueError("bad row 37")
        return row

    ds = data.range(64, parallelism=4).map(explode)
    with _pytest.raises(Exception, match="bad row 37"):
        for _ in ds.iter_batches(batch_size=8, prefetch_batches=2):
            pass
    assert not _prefetch_threads()


def test_streaming_split_iterator_prefetch(rt_session):
    """DataIterator.iter_batches honours the same prefetch contract
    (this is the object train workers consume via
    get_dataset_shard)."""
    from ray_tpu import data

    ds = data.range(120, parallelism=6)
    (it,) = ds.streaming_split(1)
    serial_ids = np.sort(
        np.concatenate(
            [
                b["id"]
                for b in data.range(120, parallelism=6).iter_batches(
                    batch_size=25
                )
            ]
        )
    )
    pre = list(it.iter_batches(batch_size=25, prefetch_batches=2))
    got = np.sort(np.concatenate([b["id"] for b in pre]))
    np.testing.assert_array_equal(got, serial_ids)
    assert [len(b["id"]) for b in pre] == [25, 25, 25, 25, 20]
    assert not _prefetch_threads()


def test_iter_block_refs_pull_ahead(rt_session):
    """iter_block_refs(prefetch=n) yields the same refs in the same
    order as the serial ref stream."""
    from ray_tpu import data

    import ray_tpu as rt

    ds = data.range(60, parallelism=6).materialize()
    serial = [rt.get(r) for r in ds.iter_block_refs()]
    ahead = [rt.get(r) for r in ds.iter_block_refs(prefetch=3)]
    assert serial == ahead
    assert not _prefetch_threads()


def test_byte_budget_backpressure_skewed_flat_map():
    """Bytes-budget backpressure (reference: _internal/execution/
    backpressure_policy/ resource-based policy): a skewed flat_map
    whose outputs balloon to ~4 MB/block must keep its in-flight
    sealed bytes under the configured budget — submission throttles on
    observed block sizes instead of flooding the store. The uncapped
    run (same plan, no byte budget) demonstrates the test's power:
    it holds a whole window of blocks (~3x the capped peak)."""
    import threading
    import time

    import ray_tpu as rt

    MB = 1024 * 1024

    def run(cap):
        rt.init(
            num_cpus=8,
            _system_config={
                "object_store_memory": 48 * MB,
                "object_eviction_check_interval_s": 0.05,
            },
        )
        try:
            from ray_tpu import data

            daemon = rt.api._session.daemon
            peak = [0]
            stop = [False]

            def watch():
                while not stop[0]:
                    used = sum(
                        entry.size or 0
                        for entry in list(daemon.objects.values())
                        if getattr(entry, "in_shm", False)
                    )
                    peak[0] = max(peak[0], used)
                    time.sleep(0.01)

            watcher = threading.Thread(target=watch, daemon=True)
            watcher.start()

            def explode(row):
                # One input row -> ~4MB of output (the skew).
                return [
                    {"payload": np.zeros(MB, dtype=np.uint8)}
                    for _ in range(4)
                ]

            ds = (
                data.range(12, parallelism=12)
                .flat_map(explode)
                .options(window=8, inflight_bytes=cap)
            )
            rows = 0
            for block_ref in ds.iter_block_refs():
                block = rt.get(block_ref)
                rows += len(block)
                for row in block:
                    assert row["payload"].nbytes == MB
                del block, block_ref
                time.sleep(0.4)  # slow consumer: producers outpace it
            stop[0] = True
            watcher.join(timeout=5)
            return rows, peak[0]
        finally:
            rt.shutdown()

    rows, uncapped_peak = run(None)  # default budget (256MB) >> data
    assert rows == 48
    rows, capped_peak = run(8 * MB)
    assert rows == 48
    # Budget 8MB + at most one in-flight block (4MB) + slack.
    assert capped_peak <= 16 * MB, (
        f"byte budget did not bound in-flight bytes: "
        f"{capped_peak / MB:.1f} MB sealed at peak"
    )
    assert uncapped_peak >= 20 * MB, (
        "test lost its power: the uncapped run no longer builds up "
        f"a window of blocks (peak {uncapped_peak / MB:.1f} MB)"
    )


def _make_warm_udf():
    """Expensive-setup UDF, built inside the test so cloudpickle
    serializes it BY VALUE (workers can't import tests/)."""

    class WarmUdf:
        SETUP_S = 0.4

        def __init__(self):
            import time as _t

            _t.sleep(self.SETUP_S)

        def __call__(self, batch):
            return {
                "v": batch["id"] * 2,
                "who": np.full(len(batch["id"]), id(self) % 2**31),
            }

    return WarmUdf


def test_actor_pool_map_beats_tasks_on_warm_udf(rt_session):
    """compute=ActorPoolStrategy (reference: actor_pool_map_operator
    .py): each pool actor builds the UDF ONCE and reuses it per block,
    so expensive-setup UDFs beat task-per-block (which re-does setup
    every task). Also checks pool bounds: distinct instances <=
    max_size, and > 1 shows autoscaling engaged under backlog."""
    import time

    from ray_tpu import data
    from ray_tpu.data import ActorPoolStrategy

    WarmUdf = _make_warm_udf()
    n_blocks = 10

    def run_actor_pool():
        t0 = time.perf_counter()
        out = (
            data.range(n_blocks * 10, parallelism=n_blocks)
            .map_batches(
                WarmUdf,
                compute=ActorPoolStrategy(
                    min_size=2, max_size=3, max_tasks_per_actor=2
                ),
            )
            .to_numpy()
        )
        return time.perf_counter() - t0, out

    def task_setup_each(batch):
        time.sleep(WarmUdf.SETUP_S)  # cold setup paid per task
        return {
            "v": batch["id"] * 2,
            "who": np.zeros(len(batch["id"])),
        }

    def run_tasks():
        t0 = time.perf_counter()
        out = (
            data.range(n_blocks * 10, parallelism=n_blocks)
            .map_batches(task_setup_each)
            .to_numpy()
        )
        return time.perf_counter() - t0, out

    pool_s, pool_out = run_actor_pool()
    task_s, task_out = run_tasks()

    np.testing.assert_array_equal(
        np.sort(pool_out["v"]), np.sort(task_out["v"])
    )
    instances = set(pool_out["who"].tolist())
    assert 1 <= len(instances) <= 3, instances
    # 10 blocks x 0.4s setup split over 4 CPUs ~= 1.0s+ for tasks;
    # the pool pays <= 3 setups total. Margin kept loose for CI noise.
    assert pool_s < task_s, (
        f"warm actor pool ({pool_s:.2f}s) should beat per-task setup "
        f"({task_s:.2f}s)"
    )


def test_streaming_split_through_actor_pool(rt_session):
    """streaming_split consumes a plan containing an ActorPoolStage:
    the split coordinator drives the pool and both consumers see
    disjoint, complete output (review r4 task 2: route
    streaming_split through actor-pool compute)."""
    from ray_tpu import data
    from ray_tpu.data import ActorPoolStrategy

    ds = data.range(80, parallelism=8).map_batches(
        _make_warm_udf(),
        compute=ActorPoolStrategy(min_size=1, max_size=2),
    )
    left, right = ds.streaming_split(2)
    seen = []
    for it in (left, right):
        for row in it.iter_rows():
            seen.append(int(row["v"]))
    assert sorted(seen) == [2 * i for i in range(80)]


def test_pyarrow_batch_format_round_trip(rt_session):
    """batch_format="pyarrow" hands the UDF an Arrow Table (the
    reference's canonical block format) and converts the returned
    Table back into rows."""
    pa = pytest.importorskip("pyarrow")
    import ray_tpu.data as data

    ds = data.from_items([{"x": i} for i in range(8)])

    def double(table):
        assert isinstance(table, pa.Table)
        return table.set_column(
            0, "x", pa.array([v * 2 for v in table["x"].to_pylist()])
        )

    out = ds.map_batches(
        double, batch_format="pyarrow", batch_size=4
    ).take_all()
    assert sorted(r["x"] for r in out) == [i * 2 for i in range(8)]
