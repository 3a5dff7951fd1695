"""`benchmark/manifest_diff.py`: a manifest may grow at the ends of its
lists and in no other way, and the command says which entry is not
where, or what, it was."""

import copy
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, manifest_diff  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import manifest_checks as checks  # noqa: E402  (this directory)

MANIFEST = harness.load_manifest()
NEW_ENTRY = {
    "name": "new_reader", "unit": "x", "better": "lower",
    "source": "program_counter", "layer": "engine",
    "moves": "serve_tokens_per_s", "workloads": ["docqa_closed"],
}


def _inserted_before_the_last(m):
    m["per_layer"].insert(len(m["per_layer"]) - 1, dict(NEW_ENTRY))


def _list_reordered(m):
    entry = next(e for e in m["per_layer"] if e["name"] == "shed_share.tput")
    entry["workloads"].reverse()


def _name_not_at_the_end_of_its_list(m):
    entry = next(e for e in m["end_to_end"] if e["name"] == "serve_tokens_per_s")
    entry["workloads"].insert(0, "new_cell")


def _bound_changed(m):
    next(e for e in m["end_to_end"] if e["name"] == "itl_p95_ms")["bound"] = 0.1


def _cell_taken_out(m):
    m["workloads"] = [c for c in m["workloads"] if c["name"] != "chat_loaded"]


def _run_seconds_changed(m):
    m["run_seconds"] = 30


def _end_to_end_metric_added(m):
    m["end_to_end"].append({
        "name": "ttft_p50_ms", "unit": "ms", "better": "lower", "bound": 0.1,
        "source": "host_clock", "workloads": ["chat_loaded"],
    })


def test_the_manifest_against_itself_and_against_a_grown_copy(tmp_path):
    assert manifest_diff.diff(MANIFEST, copy.deepcopy(MANIFEST)) == ([], [])
    grown = checks.grow(checks.checkout(tmp_path), "qwen2.5-3b", "docqa_closed")
    appended, problems = manifest_diff.diff(MANIFEST, grown)
    assert problems == []
    assert {"configs + stub-model", "workloads + stub_cell",
            "end_to_end serve_tokens_per_s: workloads + stub_cell",
            "per_layer + stub_requests", "per_layer + stub_steps",
            "per_layer kv_read_amplification.tput: workloads + stub_cell"} <= set(appended)
    # the other way round, everything that was added has gone
    _, problems = manifest_diff.diff(grown, MANIFEST)
    assert "workloads stub_cell: taken out" in problems
    assert any(p.startswith("per_layer shed_share.tput: workloads") and "stub_cell taken out" in p
               for p in problems)


@pytest.mark.parametrize("change, names", [
    (_inserted_before_the_last, [
        "per_layer new_reader: added at place",
        # the entry that was last, whichever it is by then
        f"per_layer {MANIFEST['per_layer'][-1]['name']}: moved from place",
    ]),
    (_list_reordered, ["per_layer shed_share.tput: workloads", "reordered"]),
    (_name_not_at_the_end_of_its_list, ["end_to_end serve_tokens_per_s: workloads"]),
    (_bound_changed, ["end_to_end itl_p95_ms: bound 0.08 -> 0.1"]),
    (_cell_taken_out, ["workloads chat_loaded: taken out",
                       "workloads docqa_closed: moved from place 1 to 0"]),
    (_run_seconds_changed, ["run_seconds: 48 -> 30"]),
    (_end_to_end_metric_added, ["end_to_end ttft_p50_ms: added"]),
], ids=lambda x: x.__name__.strip("_") if callable(x) else None)
def test_what_is_not_an_addition_is_named(change, names, tmp_path):
    changed = copy.deepcopy(MANIFEST)
    change(changed)
    _, problems = manifest_diff.diff(MANIFEST, changed)
    for name in names:
        assert any(name in p for p in problems), (name, problems)
    old, new = tmp_path / "old.json", tmp_path / "new.json"
    old.write_text(json.dumps(MANIFEST))
    new.write_text(json.dumps(changed))
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.manifest_diff", str(old), str(new)],
        cwd=ROOT, capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 1 and "NOT AN ADDITION" in proc.stdout
    assert all(name in proc.stdout for name in names)
    same = subprocess.run(
        [sys.executable, "-m", "benchmark.manifest_diff", str(old), "-"],
        cwd=ROOT, input=json.dumps(MANIFEST), capture_output=True, text=True, timeout=60,
    )
    assert same.returncode == 0 and "the same" in same.stdout
