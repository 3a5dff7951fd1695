"""Liveness smoke test — MUST stay first in collection order.

review r2 item 1: round 2 snapshotted a repo whose ``rt.init()`` never
completed (half-landed RPC nonce handshake), wedging the whole suite
and the bench. This file is the guardrail: it collects first
(``test_00_``), has a tight hard timeout, and fails fast if the
control plane cannot complete a full init → task → get → shutdown
cycle. Reference analog: the first thing ray's CI runs is
``test_basic.py::test_simple_task`` class smoke coverage.
"""

import time

import pytest


@pytest.mark.timeout(15)
def test_init_roundtrip_is_fast():
    import ray_tpu as rt

    t0 = time.monotonic()
    rt.init(num_cpus=2)
    try:

        @rt.remote
        def f(x):
            return x + 1

        assert rt.get(f.remote(41)) == 42
        ref = rt.put({"k": [1, 2, 3]})
        assert rt.get(ref) == {"k": [1, 2, 3]}
        # The timeout marker is the liveness gate: a wedged handshake
        # (which hangs forever) fails here in 15s instead of stalling
        # the suite. No wall-clock assert — cold caches on a loaded CI
        # box can make a healthy init slow without anything being
        # wedged.
        print(f"init+roundtrip in {time.monotonic() - t0:.2f}s")
    finally:
        rt.shutdown()
