"""The reader of the streaming transport's two counters (PR 34), on
the `engine_timers` a run recorded; on the run of a program without
them (the parent of PR 34) it gives nothing, and no exception. (Its
entries in the manifest are checked with every other per-layer entry,
`test_benchmark_yardstick.py`
`test_layer_reader_agrees_with_the_manifest`.)"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

# `docqa_closed --rehearse --trace 1 --seed 3400000001` on the CPU
# (counts, not speeds): [sum, count] of each series at the window's two
# ends. 214 streams of 4 tokens ended in the window; each took its 4
# items in 5 requests, the last of which brought only the stream's end.
RECORDED = {
    "before": {
        "serve_stream_items": [8.0, 4.0],
        "serve_stream_fetches": [10.0, 4.0],
        "serve_http_request_latency_ms": [1051.9031359945075, 4.0],
        "serve_request_latency_ms": [1014.2840850021457, 5.0],
    },
    "after": {
        "serve_stream_items": [868.0, 218.0],
        "serve_stream_fetches": [1079.0, 218.0],
        "serve_http_request_latency_ms": [16813.76005799393, 218.0],
        "serve_request_latency_ms": [15612.25703396849, 226.0],
    },
}


def read(run):
    return harness.load_module(
        "layer_metrics", "stream_items_per_fetch"
    ).reduce(run)


def without(name):
    return {
        end: {k: v for k, v in series.items() if k != name}
        for end, series in RECORDED.items()
    }


def test_items_per_fetch_of_the_recorded_run():
    assert read({"engine_timers": RECORDED}) == pytest.approx(
        (868.0 - 8.0) / (1079.0 - 10.0)
    )
    # Series that first show up inside the window count from zero.
    run = {"engine_timers": {"before": {}, "after": RECORDED["after"]}}
    assert read(run) == pytest.approx(868.0 / 1079.0)
    # A consumer that fell behind takes several items a request.
    late = {
        "before": RECORDED["before"],
        "after": dict(
            RECORDED["after"], serve_stream_fetches=[440.0, 218.0]
        ),
    }
    assert read({"engine_timers": late}) == pytest.approx(2.0)


@pytest.mark.parametrize("run", [
    {},
    {"engine_timers": None},
    # the parent's proxy: neither series among its timers
    {"engine_timers": {
        end: {
            k: v for k, v in series.items()
            if not k.startswith("serve_stream_")
        } for end, series in RECORDED.items()
    }},
    # half of the pair is not the pair
    {"engine_timers": without("serve_stream_fetches")},
    # no stream ended in the window
    {"engine_timers": {
        "before": RECORDED["after"], "after": RECORDED["after"]
    }},
], ids=["train", "no-timers", "parent", "one-series", "idle"])
def test_items_per_fetch_gives_nothing_where_there_is_nothing(run):
    assert read(run) is None
