"""From a JAX profiler trace (`*.xplane.pb`) to the device numbers.

Two steps, so the second can be checked on a small recorded trace:

  read(path)      planes -> {"device": {plane: [[name, start_ns,
                  dur_ns], ...]}, "host": [[thread, name, start_ns,
                  dur_ns], ...]} with `jax.profiler.ProfileData`
  summarize(ev)   busy/idle seconds, top device operations, longest
                  idle gaps with what the host was doing, collective
                  time with no compute running

A device plane is one named `/device:TPU:<n>` (any `/device:` plane
that is not a host); its operations are the events of the line named
`XLA Ops`. That line is the core's serial instruction stream: a
`while` or `call` holds its body's operations as nested events, so
busy time is the union of intervals, and a *leaf* is an event that
holds no other. A collective that is a leaf on this stream is time the
core spent in (or waiting on) communication with nothing else running,
which is what `collective_exposed_s` sums.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast",
)
#: Only the longest gaps are given a host label (each costs a pass over
#: the host events); the rest are too short to matter.
LABELLED_GAPS = 60
_HOST_SKIP = re.compile(r"^(\$|ThreadpoolListener|tsl::|EventCount)")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read(path: str, max_host_events: int = 60000) -> dict:
    """Needs jax (any backend); never touches a device."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    device: Dict[str, list] = {}
    host: List[list] = []
    for plane in data.planes:
        name = plane.name
        if name.startswith("/device:") and "host" not in name.lower():
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                device.setdefault(name, []).extend(
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events
                )
        elif name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns <= 0 or _HOST_SKIP.match(e.name):
                        continue
                    if len(host) < max_host_events:
                        host.append([
                            line.name, e.name, float(e.start_ns),
                            float(e.duration_ns),
                        ])
    return {"device": device, "host": host}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def _leaves(events: list) -> list:
    """Events that hold no other event (the stream's real work)."""
    ordered = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (name, start, dur) in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is not None and nxt[1] < start + dur and nxt[1] >= start:
            if nxt[1] + nxt[2] <= start + dur + 1e-6:
                continue  # holds the next event: a container
        out.append((name, start, dur))
    return out


def op_family(name: str) -> str:
    """`%fusion.123 = ...` -> `fusion`; keeps a kernel's own name."""
    name = name.split(" = ")[0].lstrip("%").strip()
    return re.sub(r"[.\d]+$", "", name) or name


def _host_label(host: list, start: float, end: float) -> str:
    """The shortest host event that covers most of [start, end), else
    the one that overlaps it most; `(no host event)` if none does."""
    best, best_key = "(no host event)", None
    span = max(end - start, 1.0)
    for thread, name, h_start, h_dur in host:
        overlap = min(end, h_start + h_dur) - max(start, h_start)
        if overlap <= 0:
            continue
        covers = overlap >= 0.5 * span
        key = (covers, -h_dur if covers else overlap)
        if best_key is None or key > best_key:
            best_key, best = key, name
    return best


def summarize(events: dict, top: int = 10) -> dict:
    """All times in seconds. `window_s` is the longest span from a
    device plane's first operation to its last; `busy_s`, and the
    collective numbers are averaged over the device planes."""
    planes = events["device"]
    if not planes:
        return {"planes": 0}
    busy, exposed, collective, window = [], [], [], []
    by_family: Dict[str, float] = {}
    gaps: List[Tuple[float, float, float]] = []
    for ops in planes.values():
        if not ops:
            continue
        merged = _union([(s, s + d) for _, s, d in ops])
        first, last = merged[0][0], merged[-1][1]
        window.append((last - first) / 1e9)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        leaves = _leaves(ops)
        coll = [(s, d) for n, s, d in leaves if COLLECTIVE.search(n)]
        exposed.append(sum(d for _, d in coll) / 1e9)
        collective.append(
            sum(d for n, _, d in ops if COLLECTIVE.search(n)) / 1e9
        )
        for name, _, dur in leaves:
            family = op_family(name)
            by_family[family] = by_family.get(family, 0.0) + dur / 1e9
        for (_, end), (start, _) in zip(merged, merged[1:]):
            gaps.append((start - end, end, start))
    n = len(busy)
    if not n:
        return {"planes": 0}
    by_label: Dict[str, float] = {}
    for dur, start, end in sorted(gaps, reverse=True)[:LABELLED_GAPS]:
        label = _host_label(events["host"], start, end)
        by_label[label] = by_label.get(label, 0.0) + dur / 1e9 / n
    ranked = sorted(by_family.items(), key=lambda kv: -kv[1])
    return {
        "planes": n,
        "window_s": max(window),
        "busy_s": sum(busy) / n,
        "collective_s": sum(collective) / n,
        "collective_exposed_s": sum(exposed) / n,
        "device_ops": [[k, v / n] for k, v in ranked[:top]],
        "idle_gaps": [
            [k, v] for k, v in
            sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
        ],
    }


def main() -> None:
    """`python -m benchmark.trace.xplane <trace_dir>` prints the
    summary as JSON: for a process that must stay off the chip."""
    import json
    import sys

    print(json.dumps(summarize(read(find_xplane(sys.argv[1])))))


if __name__ == "__main__":
    main()
