"""Shared fixtures.

JAX tests run hermetically on a virtual 8-device CPU mesh (the
reference's analogous trick is the multi-raylet-in-one-box Cluster
fixture + fake accelerator managers, SURVEY.md §4): sharding/pjit
code paths compile and run without TPU hardware.
"""

import os

# Must be set before jax is imported anywhere in the test process:
# tests run hermetically on a virtual 8-device CPU mesh whatever the
# machine holds (a chip belongs to chip_smoke.py and the benchmark).
_flag = "--xla_force_host_platform_device_count=8"
if _flag not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _flag).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import signal

import pytest

#: Hard per-test wall-clock cap (review r2 weak #8: a wedged session
#: must FAIL the test, not hang the suite; faulthandler_timeout only
#: dumps). SIGALRM raises in the main thread, which interrupts Python
#: code and most blocking socket/lock waits. Slow-marked tests get 4x.
_HARD_TIMEOUT = int(os.environ.get("RT_TEST_TIMEOUT", "120"))


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    # Wraps setup+call+teardown: a hang in rt.init()/shutdown() inside
    # a fixture must fail too, not just hangs in the test body.
    if not hasattr(signal, "SIGALRM"):
        yield
        return
    timeout = _HARD_TIMEOUT * (4 if item.get_closest_marker("slow") else 1)
    marker = item.get_closest_marker("timeout")
    if marker and marker.args:
        timeout = int(marker.args[0])

    def _on_alarm(signum, frame):
        raise TimeoutError(
            f"{item.nodeid} exceeded the {timeout}s hard test timeout"
        )

    old = signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(timeout)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture
def rt_session():
    """A fresh single-node session per test (reference fixture:
    ray_start_regular, python/ray/tests/conftest.py:463)."""
    import ray_tpu as rt

    session = rt.init(num_cpus=4, ignore_reinit_error=False)
    yield rt
    # Workers crashing BEFORE registering are never a legitimate test
    # outcome (tests that kill workers kill REGISTERED ones): a
    # nonzero startup-failure count is the crash-loop-under-load bug
    # class (review r4 weak #7) and must fail the test that hit it,
    # with a pointer at the worker logs carrying the traceback.
    try:
        daemon = rt.api._session.daemon
        failures = daemon._spawn_crash_total
        session_dir = daemon.session_dir
    except Exception:
        failures, session_dir = 0, "?"
    rt.shutdown()
    assert failures == 0, (
        f"{failures} worker(s) crashed at startup during this test — "
        f"see {session_dir}/worker-*.out"
    )


@pytest.fixture(scope="module")
def rt_shared():
    """Module-scoped session for cheap read-only tests (reference:
    ray_start_regular_shared)."""
    import ray_tpu as rt

    rt.init(num_cpus=4, ignore_reinit_error=True)
    yield rt
    rt.shutdown()
