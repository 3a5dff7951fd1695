"""The eight readers of ISSUE 59 (the engine's queue by cause,
slot-time by state, a streamed request's end and its time above the
replica as an identity per request), on the `engine` counters and the
`engine_timers` a run recorded: each gives its number from a hand-made
window, and nothing, without an exception, where its counter or series
is absent, as on the parent of PR 59. (Their entries in the manifest
are checked with every other per-layer entry, `manifest_checks.py`
`layer_entry_agrees_with_its_reader`.)"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

CAUSES = (
    "no_slot", "behind_prefill", "no_pages", "no_window_pages",
    "no_state_slots", "admissible",
)
# A window of 48 s over 32 slots in which 50 requests were admitted
# after 100 s of waiting in all: 60 s for a slot, 25 behind the prompt
# that was prefilling, 10 for memory (6 + 3 + 1) and 5 for the loop;
# the slots decoded 70 % of their time, prefilled 3 %, stood empty
# 20 % with the queue standing and 7 % without. Per streamed request
# (60 ended): 90 ms above the replica, of which 1 to the call sent, 9
# in the mailbox, 4 from the handler's exhaustion to the transport and
# 6 from there to the proxy's last write: 70 are its tokens' way out.
WAITED = dict(zip(CAUSES, (60e3, 25e3, 6e3, 3e3, 1e3, 5e3)))
SLOT_MS = {
    "decoding": 0.70, "prefilling": 0.03, "empty_queued": 0.20,
    "empty_idle": 0.07,
}
ENGINE = {
    "before": {
        "admitted": 10, "admit_wait_ms_total": 700.0,
        "admit_wait_by_cause_ms_total": {c: 100.0 for c in CAUSES}
        | {"no_slot": 200.0},
        "slot_ms": {s: 1000.0 for s in SLOT_MS},
    },
    "after": {
        "admitted": 60, "admit_wait_ms_total": 700.0 + 100e3,
        "admit_wait_by_cause_ms_total": {
            c: (200.0 if c == "no_slot" else 100.0) + WAITED[c]
            for c in CAUSES
        },
        "slot_ms": {
            s: 1000.0 + share * 32 * 48e3 for s, share in SLOT_MS.items()
        },
    },
}
PER_REQUEST = {
    "serve_ingress_overhead_ms": 90.0,
    "serve_stream_end_handoff_ms": 4.0,
    "serve_stream_end_transit_ms": 6.0,
    "serve_http_dispatch_ms": 1.0,
    "serve_queue_wait_ms": 9.0,
}
TIMERS = {
    "before": {name: [123.0, 10.0] for name in PER_REQUEST},
    "after": {
        name: [123.0 + 60 * ms, 70.0] for name, ms in PER_REQUEST.items()
    },
}
EXPECTED = {
    "queue_no_slot_share": 60.0,
    "queue_behind_prefill_share": 25.0,
    "queue_no_memory_share": 10.0,
    "slot_empty_queued_share": 20.0,
    "end_handoff_mean_ms": 4.0,
    "end_transit_mean_ms": 6.0,
    "ingress_overhead_req_mean_ms": 90.0,
    "ingress_unaccounted_mean_ms": 70.0,
}
#: What each reader reads: without any one of them it gives nothing.
NEEDS = {
    "queue_no_slot_share": ["admit_wait_by_cause_ms_total"],
    "queue_behind_prefill_share": ["admit_wait_by_cause_ms_total"],
    "queue_no_memory_share": ["admit_wait_by_cause_ms_total"],
    "slot_empty_queued_share": ["slot_ms"],
    "end_handoff_mean_ms": ["serve_stream_end_handoff_ms"],
    "end_transit_mean_ms": ["serve_stream_end_transit_ms"],
    "ingress_overhead_req_mean_ms": ["serve_ingress_overhead_ms"],
    "ingress_unaccounted_mean_ms": list(PER_REQUEST),
}
#: What the parent of PR 59 keeps of all this: the way in's two timers
#: and the admission counters. No reader finds its number there.
NEW_IN_PR_59 = (
    "admit_wait_by_cause_ms_total", "slot_ms", "serve_ingress_overhead_ms",
    "serve_stream_end_handoff_ms", "serve_stream_end_transit_ms",
)


def run_without(*names):
    def cut(ends):
        return {
            end: {k: v for k, v in series.items() if k not in names}
            for end, series in ends.items()
        }

    return {"engine_timers": cut(TIMERS), "engine": cut(ENGINE)}


def read(reader, run):
    return harness.load_module("layer_metrics", reader).reduce(run)


@pytest.mark.parametrize("reader", list(EXPECTED))
def test_reader_on_a_hand_made_window(reader):
    assert read(reader, run_without()) == pytest.approx(EXPECTED[reader])
    for name in NEEDS[reader]:
        assert read(reader, run_without(name)) is None, name
    # Nor from a run that kept neither (a train cell), nor from a
    # window in which nobody waited or ended; never an exception.
    assert read(reader, {}) is None
    assert read(reader, {"engine_timers": None, "engine": None}) is None
    idle = {
        "engine_timers": {"before": TIMERS["after"], "after": TIMERS["after"]},
        "engine": {"before": ENGINE["after"], "after": ENGINE["after"]},
    }
    assert read(reader, idle) is None
    assert read(reader, run_without(*NEW_IN_PR_59)) is None  # the parent


def test_the_three_shares_and_the_loops_own_sum_to_the_wait():
    run = run_without()
    shares = [
        read(f"queue_{name}_share", run)
        for name in ("no_slot", "behind_prefill", "no_memory")
    ]
    assert sum(shares) + 100.0 * WAITED["admissible"] / 100e3 == (
        pytest.approx(100.0)
    )
    # the by-cause counter sums to the counter the mean wait reads
    ends = ENGINE["before"], ENGINE["after"]
    gained = [
        sum(e["admit_wait_by_cause_ms_total"].values()) for e in ends
    ]
    assert gained[1] - gained[0] == pytest.approx(
        ends[1]["admit_wait_ms_total"] - ends[0]["admit_wait_ms_total"]
    )
    assert read("engine_admit_wait_mean_ms", run) == pytest.approx(2000.0)
