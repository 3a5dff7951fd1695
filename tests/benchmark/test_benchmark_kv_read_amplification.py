"""The reader of the decode steps' key counters (PR 24): on a
hand-made `run`, and on the `run` a program without those counters
gives (the parent of PR 24): nothing, and no exception."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402


def read(run):
    return harness.load_module("layer_metrics", "kv_read_amplification").reduce(run)


def serve_run(before, after):
    return {"engine": {"before": before, "after": after}}


def test_kv_read_amplification_is_keys_read_over_keys_live():
    # 100 steps of 16 slots walking 2,048 keys, 3 rows alive at 1,250.
    run = serve_run(
        {"kv_keys_live": 1_000_000, "kv_keys_read": 8_000_000},
        {"kv_keys_live": 1_000_000 + 100 * 3 * 1250,
         "kv_keys_read": 8_000_000 + 100 * 16 * 2048},
    )
    assert read(run) == pytest.approx(16 * 2048 / (3 * 1250))
    # An engine that started counting inside the window counts from 0.
    run = serve_run({"steps": 3}, {"kv_keys_live": 500, "kv_keys_read": 1500})
    assert read(run) == pytest.approx(3.0)


@pytest.mark.parametrize("run", [
    {},
    {"engine": None},
    # the parent's engine: no key counters
    serve_run({"steps": 1}, {"steps": 9}),
    # no decode step in the window
    serve_run(
        {"kv_keys_live": 40, "kv_keys_read": 90},
        {"kv_keys_live": 40, "kv_keys_read": 90},
    ),
], ids=["train", "no-engine", "parent", "idle"])
def test_kv_read_amplification_gives_nothing_where_there_is_nothing(run):
    assert read(run) is None
