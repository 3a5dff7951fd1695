"""Worker pool lifecycle: prestart warms the first task, idle reaping
shrinks a burst-inflated pool back to the cap (reference:
worker_pool.cc PrestartWorkers / TryKillingIdleWorkers)."""

import time

import pytest

import ray_tpu as rt


def test_burst_pool_shrinks_to_idle_cap():
    rt.init(
        num_cpus=8,
        _system_config={
            "worker_pool_max_idle_workers": 2,
            "object_eviction_check_interval_s": 0.2,
        },
    )
    try:
        daemon = rt.api._session.daemon
        # Shorten the grace so the test doesn't idle for 5s.
        daemon._IDLE_WORKER_GRACE_S = 0.5

        @rt.remote
        def burst(i):
            time.sleep(0.2)
            return i

        # Saturate: forces ~8 concurrent workers.
        assert sorted(
            rt.get([burst.remote(i) for i in range(16)], timeout=60)
        ) == list(range(16))
        peak = len(daemon.workers)
        assert peak >= 4, f"burst should have inflated the pool ({peak})"

        deadline = time.time() + 15
        while time.time() < deadline:
            if len(daemon.workers) <= 2:
                break
            time.sleep(0.2)
        assert len(daemon.workers) <= 2, (
            f"idle pool must shrink to the cap, still {len(daemon.workers)}"
        )

        # The shrunken pool still serves work.
        assert rt.get(burst.remote(99), timeout=30) == 99
    finally:
        rt.shutdown()


def test_actor_pinned_workers_never_reaped():
    rt.init(
        num_cpus=4,
        _system_config={
            "worker_pool_max_idle_workers": 1,
            "object_eviction_check_interval_s": 0.2,
        },
    )
    try:
        daemon = rt.api._session.daemon
        daemon._IDLE_WORKER_GRACE_S = 0.3

        @rt.remote
        class Keeper:
            def ping(self):
                return "alive"

        keepers = [Keeper.remote() for _ in range(3)]
        assert rt.get(
            [k.ping.remote() for k in keepers], timeout=30
        ) == ["alive"] * 3
        time.sleep(2.0)  # several reap cycles
        assert rt.get(
            [k.ping.remote() for k in keepers], timeout=30
        ) == ["alive"] * 3
    finally:
        rt.shutdown()


def test_zero_cpu_actors_pack_past_worker_cap():
    """An EXPLICIT num_cpus=0 actor requests {} — any number of them
    pack onto a node, each on a DEDICATED worker past the task-pool
    cap (reference: ray_option_utils.py num_cpus=0 actors; worker_pool
    starts one process per actor, bounded only by startup
    concurrency). Regression: `resources or {"CPU": 1.0}` turned the
    empty request back into 1 CPU and the pool cap deadlocked the
    creations."""
    rt.init(num_cpus=1, _system_config={"max_workers_per_node": 2})
    try:
        @rt.remote(num_cpus=0)
        class Slot:
            def pid(self):
                import os

                return os.getpid()

        # 6 actors on a 1-CPU node with a 2-worker task cap: only
        # possible if creations bypass the cap with dedicated workers.
        actors = [Slot.remote() for _ in range(6)]
        pids = rt.get([a.pid.remote() for a in actors], timeout=90)
        assert len(set(pids)) == 6

        # Pinned actor workers must not count against the task-pool
        # cap: a plain task still gets a worker spawned for it.
        @rt.remote
        def plain():
            return 42

        assert rt.get(plain.remote(), timeout=60) == 42
    finally:
        rt.shutdown()


def test_forked_proc_detects_recycled_pid():
    """ForkedProc.poll() must not trust a bare signal-0 probe: the
    fork-server reaps children immediately, so an exited worker's pid
    can be recycled by an unrelated process. Liveness requires the
    /proc starttime captured at fork to still match; a mismatch (here
    simulated by tampering the captured value against a live pid)
    reads as dead, and terminate()/kill() then refuse to signal the
    innocent holder of the recycled pid."""
    import os

    from ray_tpu._private.worker_forkserver import (
        ForkedProc,
        _proc_starttime,
    )

    me = os.getpid()
    mine = _proc_starttime(me)
    assert mine is not None
    live = ForkedProc(me, mine)
    assert live.poll() is None  # genuinely alive, starttime matches

    recycled = ForkedProc(me, mine - 1)  # pretend an older child
    assert recycled.poll() == 0
    recycled.kill()  # must be a no-op, not SIGKILL to ourselves
    assert os.getpid() == me

    # Template's reaper won the race: starttime arrives as None and
    # the handle reads dead without trusting the pid at all.
    assert ForkedProc(me, None).poll() == 0

    gone = ForkedProc(2**22 - 17, 123)  # vanishingly unlikely to exist
    assert gone.poll() == 0


def _fake_stat(root, pid, state, starttime, threads):
    """A /proc/<pid>/stat as the kernel writes it, under `root`."""
    import os

    os.makedirs(f"{root}/{pid}", exist_ok=True)
    fields = ["0"] * 50
    fields[0], fields[17], fields[19] = state, str(threads), str(starttime)
    with open(f"{root}/{pid}/stat", "w") as f:
        f.write(f"{pid} (python3 (x)) " + " ".join(fields) + "\n")


@pytest.mark.parametrize(
    "state,threads,forked_at,alive",
    [
        ("S", 9, 77, "Sl"),
        # the leader is a zombie and its thread group is not empty:
        # the kernel is still tearing down what it held (its chips)
        ("Z", 2, 77, "Zl"),
        ("Z", 1, 77, None),     # exited, the template has not reaped it
        ("S", 9, 76, None),     # another process has the pid now
        ("Z", 2, None, None),   # the template's reaper won the race
    ],
)
def test_a_zombie_with_threads_left_is_not_gone(
    tmp_path, state, threads, forked_at, alive
):
    """`ForkedProc.poll()` against a fake /proc: what `daemon.shutdown`
    waits on and `_claim_chips` frees chips by (ISSUE 59)."""
    import os

    from ray_tpu._private.worker_forkserver import ForkedProc

    me = os.getpid()  # a pid `kill(pid, 0)` finds
    _fake_stat(tmp_path, me, state, 77, threads)
    proc = ForkedProc(me, forked_at, proc_root=str(tmp_path))
    assert proc.state() == alive
    assert proc.poll() == (None if alive else 0)


def test_default_actors_exceed_node_cpus():
    """Default actors need 1 CPU to *schedule* but hold 0 for their
    lifetime (reference: DEFAULT_ACTOR_CREATION_CPU_SIMPLE=0 — the
    1 CPU is placement-only and released after scheduling), so more
    default actors than node CPUs still all come up. Regression:
    holding the creation CPU for the lifetime queued the third actor
    forever on a 2-CPU node with no error."""
    rt.init(num_cpus=2)
    try:
        @rt.remote
        class A:
            def ping(self):
                return "up"

        actors = [A.remote() for _ in range(5)]
        assert rt.get(
            [a.ping.remote() for a in actors], timeout=90
        ) == ["up"] * 5

        # The released CPUs are genuinely back: plain 1-CPU tasks
        # still run while all five actors are alive.
        @rt.remote
        def f():
            return 7

        assert rt.get([f.remote() for _ in range(4)], timeout=60) == [7] * 4

        # EXPLICIT num_cpus keeps lifetime-hold semantics: a sixth
        # actor demanding 2 full CPUs schedules too (the default
        # actors freed theirs), and holds them.
        @rt.remote(num_cpus=2)
        class Holder:
            def ping(self):
                return "held"

        h = Holder.remote()
        assert rt.get(h.ping.remote(), timeout=60) == "held"
    finally:
        rt.shutdown()


def test_fork_server_spawns_workers():
    """Workers come from the warm fork-server template by default;
    they must execute tasks and report distinct pids (the template's
    children, not the daemon's)."""
    rt.init(num_cpus=4)
    try:
        daemon = rt.api._session.daemon
        assert daemon._fork_server is not None

        @rt.remote
        def whoami():
            import os

            return os.getpid(), os.getppid()

        pid, ppid = rt.get(whoami.remote(), timeout=60)
        assert pid != ppid
        # The worker's parent is the fork-server template, not the
        # daemon's own process.
        import os as _os

        assert ppid != _os.getpid()
    finally:
        rt.shutdown()


def test_spawn_watcher_judgment():
    """The spawn watcher must count a worker that dies before EVER
    registering as a startup crash, but must NOT count a fast
    register→work→exit lifecycle (short trial, idle reap) — judging by
    the live workers dict alone miscounted healthy short-lived workers
    whenever the watcher thread was starved past their whole lifetime
    (observed: TPE trials under heavy box load)."""
    rt.init(num_cpus=2)
    try:
        daemon = rt.api._session.daemon

        class FakeProc:
            def __init__(self, pid, rc):
                self.pid = pid
                self._rc = rc

            def poll(self):
                return self._rc

        base = daemon._spawn_crash_total

        # Registered-then-exited: pid is in the history set even
        # though it is long gone from daemon.workers.
        reg_pid = 2**22 - 101
        with daemon._lock:
            daemon._registered_pids_ever.add(reg_pid)
        daemon._watch_worker_start(FakeProc(reg_pid, 0))

        # Never-registered exit: a genuine startup crash.
        daemon._watch_worker_start(FakeProc(2**22 - 103, 1))

        deadline = time.time() + 15
        while time.time() < deadline:
            if daemon._spawn_crash_total > base:
                break
            time.sleep(0.1)
        assert daemon._spawn_crash_total == base + 1, (
            "exactly the unregistered exit must count as a crash"
        )
        # Counter hygiene for the session fixture's zero assertion.
        daemon._spawn_crash_total = base
        with daemon._lock:
            daemon._registered_pids_ever.discard(reg_pid)
    finally:
        rt.shutdown()


def test_chips_pass_from_a_dead_actor_to_its_successor():
    """One process per chip set, on a fake 4-chip node: a killed
    actor's chips go to whoever queued for them at once (its death
    must wake the scheduler — train-then-serve hands the chip over
    this way), an idle pooled TPU worker of another shape makes way,
    and one-chip actors land on distinct chips."""
    rt.init(num_cpus=4, num_tpus=4)
    try:
        @rt.remote(num_cpus=0)
        class Holder:
            def chips(self):
                return rt.get_runtime_context().get_accelerator_ids()["TPU"]

        whole = Holder.options(num_tpus=4)
        first = whole.remote()
        assert rt.get(first.chips.remote(), timeout=30) == list("0123")
        rt.kill(first)
        second = whole.remote()  # queued while `first` still holds them
        assert rt.get(second.chips.remote(), timeout=30) == list("0123")
        rt.kill(second)

        @rt.remote(num_tpus=4, num_cpus=0)
        def task_chips():
            return rt.get_runtime_context().get_accelerator_ids()["TPU"]

        # Leaves an idle 4-chip worker in the pool, holding every chip.
        assert rt.get(task_chips.remote(), timeout=30) == list("0123")
        singles = [Holder.options(num_tpus=1).remote() for _ in range(3)]
        held = rt.get([a.chips.remote() for a in singles], timeout=30)
        assert sorted(held) == [["0"], ["1"], ["2"]]
    finally:
        rt.shutdown()
