"""The reader of ISSUE 60's counter (`prefill_joined_share`: the chunks
an engine dispatched behind another in one iteration of its loop, over
all its chunks) on the `engine` counters a run recorded: its number
from a hand-made window, and nothing, without an exception, where a
counter is absent, as on the parent of PR 60. (Its two entries in the
manifest are checked with every other per-layer entry,
`manifest_checks.py` `layer_entry_agrees_with_its_reader`.)"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

# A window in which 700 prompts ran 1,050 chunks, 350 of them short
# last chunks, and 336 chunks were dispatched behind one of those.
BEFORE = {
    "prefill_chunks": 40, "prefill_short_chunks": 10,
    "prefill_joined_chunks": 9,
}
AFTER = {
    "prefill_chunks": 1090, "prefill_short_chunks": 360,
    "prefill_joined_chunks": 345,
}


def read(run):
    return harness.load_module(
        "layer_metrics", "prefill_joined_share"
    ).reduce(run)


def without(counters, name):
    return {k: v for k, v in counters.items() if k != name}


WINDOWS = {
    "chunks_joined": (BEFORE, AFTER, 32.0),
    "one_chunk_an_iteration": (
        BEFORE, {**AFTER, "prefill_joined_chunks": 9}, 0.0,
    ),
    # a driver that took no reading before the window counts from 0
    "no_reading_before": ({}, AFTER, 100.0 * 345 / 1090),
    "no_chunk_in_the_window": (AFTER, AFTER, None),
    # the parent of PR 60 counts chunks and short chunks alone
    "the_parent": (
        without(BEFORE, "prefill_joined_chunks"),
        without(AFTER, "prefill_joined_chunks"), None,
    ),
    "before_pr_43": ({}, {"prefill_joined_chunks": 3}, None),
}


@pytest.mark.parametrize("window", list(WINDOWS))
def test_reader_on_a_hand_made_window(window):
    before, after, expected = WINDOWS[window]
    got = read({"engine": {"before": before, "after": after}})
    assert got == (pytest.approx(expected) if expected is not None else None)


@pytest.mark.parametrize("run", [{}, {"engine": None}, {"engine": {}}])
def test_a_run_that_kept_no_engine_counters_gives_nothing(run):
    assert read(run) is None
