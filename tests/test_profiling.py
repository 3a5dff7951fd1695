"""On-demand profiler tests.

Reference test model: dashboard reporter profiling endpoints
(py-spy/memray attach) — here the profilers run in-process
(_private/profiling.py), so the unit layer needs no cluster; the
integration layer drives the dashboard /api/profile route through a
live session.
"""

import threading
import time

import pytest

from ray_tpu._private import profiling


def test_dump_stacks_contains_this_function():
    text = profiling.dump_stacks()
    assert "test_dump_stacks_contains_this_function" in text
    assert "thread" in text


def test_sample_cpu_catches_hot_function():
    stop = threading.Event()

    def spin_hot_loop():
        while not stop.is_set():
            sum(i * i for i in range(200))

    thread = threading.Thread(target=spin_hot_loop, daemon=True)
    thread.start()
    try:
        result = profiling.sample_cpu(duration_s=0.6, hz=200)
    finally:
        stop.set()
        thread.join(timeout=5)
    assert result["samples"] > 10
    assert "spin_hot_loop" in result["folded"]
    # Folded format: "frame;frame;... N" per line.
    hot_lines = [
        line
        for line in result["folded"].splitlines()
        if "spin_hot_loop" in line
    ]
    assert hot_lines
    count = int(hot_lines[0].rsplit(" ", 1)[1])
    assert count > 0


def test_sample_cpu_excludes_profiler_thread():
    result = profiling.sample_cpu(duration_s=0.2, hz=100)
    assert "sample_cpu" not in result["folded"]


def test_memory_profile_sees_allocations():
    allocations = []

    def churn():
        deadline = time.monotonic() + 0.4
        while time.monotonic() < deadline:
            allocations.append(bytearray(64 * 1024))
            time.sleep(0.01)

    thread = threading.Thread(target=churn, daemon=True)
    thread.start()
    result = profiling.profile_memory(duration_s=0.5, top=10)
    thread.join(timeout=5)
    assert result["top"], "no allocation sites recorded"
    formatted = "\n".join(
        line
        for entry in result["top"]
        for line in entry["traceback"]
    )
    # format() prints file/line + source text (not function names):
    # the churn allocation site is the bytearray line in this file.
    assert "test_profiling.py" in formatted
    assert "bytearray(64 * 1024)" in formatted
    del allocations


def test_profile_live_worker_via_state_api(rt_session):
    """Driver -> daemon -> worker direct endpoint: cpu profile of a
    busy actor shows its hot method; stack dump works; memory profile
    returns allocation sites."""
    rt = rt_session
    from ray_tpu.util import state

    @rt.remote
    class Busy:
        def pid(self):
            import os

            return os.getpid()

        def spin(self, seconds):
            deadline = time.monotonic() + seconds
            total = 0
            while time.monotonic() < deadline:
                total += sum(i * i for i in range(300))
            return total

    actor = Busy.remote()
    pid = rt.get(actor.pid.remote())
    spin_ref = actor.spin.remote(3.0)

    result = state.profile_worker(
        pid, kind="cpu", duration_s=1.0, hz=200
    )
    assert result["samples"] > 20
    assert "spin" in result["folded"]

    stacks = state.profile_worker(pid, kind="stack")
    assert "stacks" in stacks

    memory = state.profile_worker(
        pid, kind="memory", duration_s=0.3
    )
    assert "top" in memory
    rt.get(spin_ref)


def test_profile_via_dashboard_route(rt_session):
    rt = rt_session
    import json as json_mod
    import urllib.request

    from ray_tpu.dashboard import start_dashboard

    @rt.remote
    class Busy:
        def pid(self):
            import os

            return os.getpid()

        def spin(self, seconds):
            deadline = time.monotonic() + seconds
            while time.monotonic() < deadline:
                sum(i * i for i in range(300))

    actor = Busy.remote()
    pid = rt.get(actor.pid.remote())
    spin_ref = actor.spin.remote(2.0)
    dashboard = start_dashboard(port=0)
    try:
        url = (
            f"http://127.0.0.1:{dashboard.port}/api/profile"
            f"?pid={pid}&kind=cpu&duration_s=0.5&hz=100"
        )
        with urllib.request.urlopen(url, timeout=60) as resp:
            payload = json_mod.loads(resp.read())
        assert payload["samples"] > 5
        assert "spin" in payload["folded"]
    finally:
        dashboard.stop()
    rt.get(spin_ref)


def test_run_profile_dispatch():
    assert "stacks" in profiling.run_profile("stack")
    cpu = profiling.run_profile("cpu", duration_s=0.05, hz=50)
    assert "folded" in cpu
    try:
        profiling.run_profile("nope")
        raise AssertionError("expected ValueError")
    except ValueError:
        pass


@pytest.mark.parametrize("kind, duration_s, start_in_s, want", [
    # the window and the slack, as before
    ("cpu", 5.0, None, 35.0),
    ("stack", 0.0, None, 30.0),
    # a gang capture also stops a `jax.profiler` trace, whose cost
    # grows with the window: 3 s of continuous device work took 81 s
    # to stop on the chip and is given 120 where 33 were outlasted
    ("gang", 3.0, None, 3.0 + 30.0 + 120.0),
    ("gang", 60.0, None, 60.0 + 30.0 + 2400.0),
    # a synchronized window's start is waited for, one that passed is not
    ("gang", 2.0, 0.5, 2.0 + 30.0 + 80.0 + 0.5),
    ("gang", 2.0, -4.0, 2.0 + 30.0 + 80.0),
])
def test_relay_timeout_grows_with_a_gang_window(
    kind, duration_s, start_in_s, want
):
    now = 1_000_000.0
    start_at = None if start_in_s is None else now + start_in_s
    assert profiling.relay_timeout_s(
        kind, duration_s, start_at, now=now
    ) == pytest.approx(want)
    # The relay outlasts the capture it waits for at any window.
    assert profiling.relay_timeout_s(kind, duration_s) > duration_s
