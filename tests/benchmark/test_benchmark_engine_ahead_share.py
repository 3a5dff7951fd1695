"""The reader of the engine's dispatch-ahead counters (PR 27), on a
hand-made `run`; on the `run` of a program without them (the parent of
PR 27) it gives nothing, and no exception. (Its entries in the
manifest are checked with every other per-layer entry,
`test_benchmark_yardstick.py`
`test_layer_reader_agrees_with_the_manifest`.)"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402


def read(run):
    return harness.load_module("layer_metrics", "engine_ahead_share").reduce(run)


def serve_run(before, after):
    return {"engine": {"before": before, "after": after}}


def test_ahead_share_is_programs_ahead_over_programs_in_the_window():
    # The lead-in dispatched 500 programs; the window 2,000 more, all
    # but 40 of them with an earlier one in flight.
    run = serve_run(
        {"programs": 500, "programs_ahead": 470},
        {"programs": 2500, "programs_ahead": 2430},
    )
    assert read(run) == pytest.approx(98.0)
    # Counters that first show up inside the window count from zero.
    run = serve_run({"steps": 3}, {"programs": 10, "programs_ahead": 5})
    assert read(run) == pytest.approx(50.0)


@pytest.mark.parametrize("run", [
    {},
    {"engine": None},
    # the parent's engine: neither counter
    serve_run({"steps": 1}, {"steps": 9}),
    # half of the pair is not the pair
    serve_run({"programs": 1}, {"programs": 9}),
    # nothing was dispatched in the window
    serve_run(
        {"programs": 7, "programs_ahead": 6},
        {"programs": 7, "programs_ahead": 6},
    ),
], ids=["train", "no-engine", "parent", "one-key", "idle"])
def test_ahead_share_gives_nothing_where_there_is_nothing(run):
    assert read(run) is None
