"""The reader of the engine's prefill padding counters (PR 43): on a
hand-made `run`, and on the `run` a program without those counters
gives (the parent of PR 43): nothing, and no exception. Its two entries
in the manifest are held here (`HELD`, in the form of
`manifest_checks.HELD`, which is a benchmark PR's to grow) and checked
with every other per-layer entry (`test_benchmark_yardstick.py`
`test_layer_reader_agrees_with_the_manifest`)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import manifest_checks as checks  # noqa: E402  (this directory)

#: Entry -> (moves, better, cells it must still list), as
#: `manifest_checks.HELD` holds the entries of the PRs before this one.
HELD = {
    "prefill_padding_share.tput": (checks.TPUT, "lower", checks.CLOSED),
    "prefill_padding_share.itl": (checks.ITL, "lower", checks.CHAT),
}

#: What an engine before PR 43 counts of a prompt's chunks.
PARENT = {"steps": 9, "programs": 40, "admitted": 12, "first_tokens": 12}


def read(run):
    return harness.load_module("layer_metrics", "prefill_padding_share").reduce(run)


def serve_run(before, after):
    return {"engine": {"before": before, "after": after}}


@pytest.mark.parametrize("computed, needed, share", [
    # doc_score_moe's 16 prompts a round: whole chunks, then the plan
    (16 * 3072, 16 * 2112, 31.25),
    (16 * 2432, 16 * 2112, 100 * (1 - 2112 / 2432)),
    # every chunk ends where its prompt ends
    (4096, 4096, 0.0),
])
def test_padding_share_is_what_the_prompts_did_not_need(computed, needed, share):
    before = {"prefill_tokens_computed": 7000, "prefill_tokens_needed": 5000}
    after = {
        "prefill_tokens_computed": 7000 + computed,
        "prefill_tokens_needed": 5000 + needed,
        "prefill_chunks": 24, "prefill_short_chunks": 8,
    }
    assert read(serve_run(before, after)) == pytest.approx(share)
    # An engine that started counting inside the window counts from 0.
    started = {k: v - before.get(k, 0) for k, v in after.items()}
    assert read(serve_run(PARENT, started)) == pytest.approx(share)


@pytest.mark.parametrize("run", [
    {},
    {"engine": None},
    serve_run(PARENT, {**PARENT, "steps": 90}),
    serve_run(PARENT, {**PARENT, "prefill_tokens_computed": 512}),
    serve_run(
        {"prefill_tokens_computed": 512, "prefill_tokens_needed": 400},
        {"prefill_tokens_computed": 512, "prefill_tokens_needed": 400},
    ),
], ids=["train", "no-engine", "parent", "half-a-parent", "idle"])
def test_padding_share_gives_nothing_where_there_is_nothing(run):
    assert read(run) is None


@pytest.mark.parametrize("name", sorted(HELD))
def test_padding_share_entries_are_held(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    moves, better, cells = HELD[name]
    checks.layer_entry_agrees_with_its_reader(manifest, name)
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert (entry["moves"], entry["better"]) == (moves, better)
    assert set(entry["workloads"]) >= cells
    assert (entry["layer"], entry["source"]) == ("engine", "program_counter")
    # a metric no serve cell reports: the entry's cells would not report it
    moved = dict(entry, moves=checks.TOKENS)
    with pytest.raises(AssertionError):
        checks.layer_entry_agrees_with_its_reader(
            {**manifest, "per_layer": [moved]}, name
        )
