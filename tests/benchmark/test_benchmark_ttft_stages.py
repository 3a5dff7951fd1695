"""The six readers of a streamed request's first-token stages (PR 41),
on the `engine_timers` and the engine's counters a run recorded: each
gives its number from known sums and counts, and nothing, without an
exception, where a series or a counter is absent, as on the parent of
PR 41. And each serve cell's rehearsal names them. (Their entries in
the manifest are checked with every other per-layer entry,
`test_benchmark_yardstick.py`
`test_layer_reader_agrees_with_the_manifest`.)"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import manifest_checks as checks  # noqa: E402  (this directory)

READERS = (
    "ttft_server_mean_ms", "ttft_dispatch_mean_ms", "ttft_mailbox_mean_ms",
    "ttft_prefill_mean_ms", "ttft_way_out_mean_ms", "ttft_unaccounted_share",
)
# A window in which 60 requests crossed every boundary (made up: round
# means, [sum, count] at the window's two ends). Per request: 2 ms to
# the call sent, 40 in the mailbox, 1 to submit, 8 for a slot, 70 to
# the first token, 3 to the handler's yield, 4 to the proxy's socket,
# 136 from the request read to its first bytes: 8 are nobody's.
TIMERS = {
    "before": {
        "serve_http_first_byte_ms": [1000.0, 10.0],
        "serve_http_dispatch_ms": [50.0, 10.0],
        "serve_queue_wait_ms": [300.0, 10.0],
        "serve_handler_submit_ms": [2000.0, 10.0],
        "serve_first_item_handoff_ms": [20.0, 10.0],
        "serve_first_item_transit_ms": [30.0, 10.0],
    },
    "after": {
        "serve_http_first_byte_ms": [1000.0 + 60 * 136.0, 70.0],
        "serve_http_dispatch_ms": [50.0 + 60 * 2.0, 70.0],
        "serve_queue_wait_ms": [300.0 + 60 * 40.0, 70.0],
        "serve_handler_submit_ms": [2000.0 + 60 * 1.0, 70.0],
        "serve_first_item_handoff_ms": [20.0 + 60 * 3.0, 70.0],
        "serve_first_item_transit_ms": [30.0 + 60 * 4.0, 70.0],
    },
}
ENGINE = {
    "before": {
        "admitted": 10, "admit_wait_ms_total": 5.0,
        "first_tokens": 10, "prefill_ms_total": 900.0,
    },
    "after": {
        "admitted": 70, "admit_wait_ms_total": 5.0 + 60 * 8.0,
        "first_tokens": 70, "prefill_ms_total": 900.0 + 60 * 70.0,
    },
}
EXPECTED = {
    "ttft_server_mean_ms": 136.0,
    "ttft_dispatch_mean_ms": 2.0,
    "ttft_mailbox_mean_ms": 40.0,
    "ttft_prefill_mean_ms": 70.0,
    "ttft_way_out_mean_ms": 7.0,
    "ttft_unaccounted_share": 100.0 * 8.0 / 136.0,
}
#: What each reader reads: without any one of them it gives nothing.
NEEDS = {
    "ttft_server_mean_ms": ["serve_http_first_byte_ms"],
    "ttft_dispatch_mean_ms": ["serve_http_dispatch_ms"],
    "ttft_mailbox_mean_ms": ["serve_queue_wait_ms"],
    "ttft_prefill_mean_ms": ["first_tokens", "prefill_ms_total"],
    "ttft_way_out_mean_ms": [
        "serve_first_item_handoff_ms", "serve_first_item_transit_ms",
    ],
    "ttft_unaccounted_share": [
        name for end in (TIMERS["after"], ENGINE["after"]) for name in end
    ],
}
#: The parent of PR 41: the mailbox's timer and the admission counters
#: are older than this PR, nothing else here is.
PARENT = {"serve_queue_wait_ms", "admitted", "admit_wait_ms_total"}


def run_without(*names):
    def cut(ends):
        return {
            end: {k: v for k, v in series.items() if k not in names}
            for end, series in ends.items()
        }

    return {"engine_timers": cut(TIMERS), "engine": cut(ENGINE)}


def read(reader, run):
    return harness.load_module("layer_metrics", reader).reduce(run)


@pytest.mark.parametrize("reader", READERS)
def test_stage_reader_on_known_sums_and_counts(reader):
    assert read(reader, run_without()) == pytest.approx(EXPECTED[reader])
    # Without any one of what it reads, nothing; nor from a run that
    # kept no timers (a train cell), nor from a window in which no
    # request crossed the boundary; never an exception.
    for name in NEEDS[reader]:
        assert read(reader, run_without(name)) is None, name
    assert read(reader, {}) is None
    assert read(reader, {"engine_timers": None, "engine": None}) is None
    idle = {
        "engine_timers": {"before": TIMERS["after"], "after": TIMERS["after"]},
        "engine": {"before": ENGINE["after"], "after": ENGINE["after"]},
    }
    assert read(reader, idle) is None
    # On the parent tree only the mailbox's reader finds its series.
    new_in_pr_41 = (set(TIMERS["after"]) | set(ENGINE["after"])) - PARENT
    parent = run_without(*new_in_pr_41)
    assert (read(reader, parent) is not None) == (
        reader == "ttft_mailbox_mean_ms"
    )


def test_unaccounted_share_has_a_sign():
    """A stage counted twice reads negative, one without a timer
    positive: the share is not clamped."""
    twice = json.loads(json.dumps(TIMERS))
    twice["after"]["serve_queue_wait_ms"][0] += 60 * 20.0
    run = {"engine_timers": twice, "engine": ENGINE}
    assert read("ttft_unaccounted_share", run) == pytest.approx(
        100.0 * (8.0 - 20.0) / 136.0
    )


@pytest.mark.timeout(400)
@pytest.mark.parametrize("cell, tag", [
    ("chat_loaded", "itl"), ("docqa_closed", "tput"),
    ("doc_score_moe", "tput"),
])
def test_rehearsal_names_the_stage_readers_not_on_the_grown_copy(
    cell, tag, tmp_path
):
    """A whole run a serve cell. (`test_benchmark_grown.py` runs this
    directory with `-k "not grown"`, which keeps whole runs like these
    out of its copy by their name.)"""
    root = checks.checkout(tmp_path)
    env = {
        k: v for k, v in os.environ.items()
        if k != "JAX_COMPILATION_CACHE_DIR"
    }
    env.update(
        JAX_PLATFORMS="cpu", OMP_NUM_THREADS="1",
        XLA_FLAGS="--xla_cpu_multi_thread_eigen=false",
    )
    proc = subprocess.run(
        [
            sys.executable, os.path.join(root, "benchmark", "run.py"),
            "--workload", cell, "--seed", "4100000003", "--rehearse",
            "--trace", "1",
        ],
        cwd=root, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["failed"] == 0
    assert {f"{reader}.{tag}" for reader in READERS} <= set(
        line["metric_names"]
    )
