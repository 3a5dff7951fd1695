"""Is a new `BENCHMARK.json` the old one with entries appended?

    git show HEAD:BENCHMARK.json | python3 -m benchmark.manifest_diff - BENCHMARK.json

A PR that is not a `benchmark` PR may add entries at the END of
`configs`, `workloads` and `per_layer`, and names at the END of the
`workloads` list inside a metric, and nothing else: no entry moves,
changes or goes, and `command`, `paths`, `run_seconds` and every bound
stay. `diff` says what was appended and names every entry that is not
where, or what, it was; the command prints both and exits 1 where
there is any of the second. `-` for a file reads standard input, so
the old manifest needs no file outside the checkout.
"""

from __future__ import annotations

import json
import sys
from typing import List, Tuple

#: Lists a PR of any kind may append entries to.
GROW = ("configs", "workloads", "per_layer")
#: Entries whose inner `workloads` list may grow at its end.
HOLD_CELLS = ("end_to_end", "per_layer")


def _entry_changes(section: str, old: dict, new: dict) -> Tuple[list, list]:
    """(appended, problems) of one entry against the one that stood in
    its place."""
    where = f"{section} {old['name']}"
    if new.get("name") != old["name"]:
        return [], [f"{where}: {new.get('name')!r} stands in its place"]
    appended, problems = [], []
    for key in sorted(set(old) | set(new)):
        a, b = old.get(key), new.get(key)
        if a == b:
            continue
        grows = (
            key == "workloads" and section in HOLD_CELLS
            and isinstance(a, list) and isinstance(b, list)
            and b[:len(a)] == a
        )
        if grows:
            appended += [f"{where}: workloads + {name}" for name in b[len(a):]]
        elif key == "workloads" and isinstance(a, list) and isinstance(b, list):
            gone = [n for n in a if n not in b]
            problems.append(
                f"{where}: workloads {a} -> {b}"
                + (f" ({', '.join(gone)} taken out)" if gone else
                   " (reordered, or a name not at the end)")
            )
        else:
            problems.append(f"{where}: {key} {a!r} -> {b!r}")
    return appended, problems


def diff(old: dict, new: dict) -> Tuple[List[str], List[str]]:
    """(appended, problems): what `new` adds at the ends of `old`'s
    lists, and every way in which it is not `old` plus that."""
    appended: List[str] = []
    problems: List[str] = []
    for key in sorted(set(old) | set(new)):
        if key in GROW or key in HOLD_CELLS:
            continue
        if old.get(key) != new.get(key):
            problems.append(f"{key}: {old.get(key)!r} -> {new.get(key)!r}")
    for section in sorted(set(GROW) | set(HOLD_CELLS)):
        before, after = old.get(section, []), new.get(section, [])
        names = [e.get("name") for e in after]
        for i, entry in enumerate(before):
            if i < len(after) and after[i].get("name") == entry["name"]:
                a, p = _entry_changes(section, entry, after[i])
                appended += a
                problems += p
            elif entry["name"] in names:
                at = names.index(entry["name"])
                problems.append(
                    f"{section} {entry['name']}: moved from place {i} to {at}"
                )
                _, p = _entry_changes(section, entry, after[at])
                problems += p
            else:
                problems.append(f"{section} {entry['name']}: taken out")
        held = {e["name"] for e in before}
        for i, entry in enumerate(after):
            if entry.get("name") in held:
                continue
            if section not in GROW:
                problems.append(
                    f"{section} {entry.get('name')}: added (only a "
                    "benchmark PR adds an end-to-end metric)"
                )
            elif i < len(before):
                problems.append(
                    f"{section} {entry.get('name')}: added at place {i}, "
                    f"before the end ({len(before)})"
                )
            else:
                appended.append(f"{section} + {entry.get('name')}")
    return appended, problems


def _load(path: str) -> dict:
    if path == "-":
        return json.load(sys.stdin)
    with open(path) as f:
        return json.load(f)


def main(argv: List[str]) -> int:
    if len(argv) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    appended, problems = diff(_load(argv[0]), _load(argv[1]))
    for line in appended:
        print(f"appended  {line}")
    for line in problems:
        print(f"NOT AN ADDITION  {line}")
    if not appended and not problems:
        print("the two manifests are the same")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
