"""What every cell shares: the manifest, files found by name, the
metric readers and the one result line.

Everything that belongs to one configuration, one traffic mix or one
metric is a file of its own, found by the name `BENCHMARK.json` gives:

  configs/<config>.json             sizes, geometry, source, reduced
  traffic/<traffic>.json            parameters of one mix; its `kind`
                                    names the generator traffic/<kind>.py
  end_to_end/<metric>.py            reduce(run) -> float | None
  layer_metrics/<reader>.py         reduce(run) -> float | None

A per-layer entry may be named `<reader>.<tag>`: the tag tells apart
entries that read the same number but `moves` another end-to-end
metric (the contract lets a metric name one). The part before the
first dot names the reader file.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchmarkError(RuntimeError):
    """The run cannot give a result (exit code 1, no result line)."""


def describe(devices) -> dict:
    """The device as JAX reports it, in the result line's keys."""
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def peak_bytes(devices) -> int:
    """`peak_bytes_in_use` on the fullest chip."""
    return max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in devices
    )


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find_cell(manifest: dict, name: str) -> dict:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    known = ", ".join(c["name"] for c in manifest["workloads"])
    raise BenchmarkError(f"no workload {name!r}; BENCHMARK.json has {known}")


def load_config(manifest: dict, name: str, root: str = ROOT) -> dict:
    for entry in manifest["configs"]:
        if entry["name"] == name:
            return load_json(os.path.join(root, entry["file"]))
    raise BenchmarkError(f"no config {name!r} in BENCHMARK.json")


def load_traffic(name: str, root: str = ROOT) -> dict:
    return load_json(
        os.path.join(root, "benchmark", "traffic", f"{name}.json")
    )


def load_module(directory: str, name: str, root: str = ROOT):
    """Import benchmark/<directory>/<name>.py by path (names may hold
    characters an import statement cannot)."""
    path = os.path.join(root, "benchmark", directory, f"{name}.py")
    if not os.path.exists(path):
        raise BenchmarkError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{directory}.{name.replace('.', '_')}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reader_name(metric_name: str) -> str:
    return metric_name.split(".", 1)[0]


def metrics_of_cell(manifest: dict, section: str, cell: str) -> List[dict]:
    """Entries of `end_to_end` or `per_layer` this cell reports: those
    with no `workloads` key, or with the cell listed in it."""
    return [
        m for m in manifest[section]
        if "workloads" not in m or cell in m["workloads"]
    ]


def reduce_metrics(
    manifest: dict, section: str, directory: str, run: dict,
    rehearse: bool = False,
) -> Dict[str, dict]:
    """{name: {"value", "unit"}} for the cell's metrics of a section.
    A reader that finds nothing to read returns None and the metric is
    left out of the line. Only a rehearsal may meet a device without
    published peaks: its readers then have nothing to read."""
    from .flops import UnknownDevice

    out: Dict[str, dict] = {}
    for entry in metrics_of_cell(manifest, section, run["cell"]["name"]):
        reduce: Callable[[dict], Optional[float]] = load_module(
            directory, reader_name(entry["name"])
        ).reduce
        try:
            value = reduce(run)
        except UnknownDevice:
            if not rehearse:
                raise
            value = None
        if value is None:
            continue
        out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def apply_rehearsal(settings: dict) -> dict:
    """A config or traffic file may carry a `rehearsal` group: the keys
    the CPU walk-through replaces (tiny sizes). Merged one level deep."""
    out = dict(settings)
    for key, value in (settings.get("rehearsal") or {}).items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = {**out[key], **value}
        else:
            out[key] = value
    return out


def check(name: str, value, limit, at_least: bool = False, **detail) -> dict:
    """One comparison `correct` (or the exit code) rests on: a reading
    beside its limit. Sound where `value <= limit`, or `>=` for a
    reading that has to reach its limit (`at_least`). `detail` says
    where the reading was taken (a row, a program's name, a status)."""
    value, limit = float(value), float(limit)
    ok = value >= limit if at_least else value <= limit
    return dict(name=name, value=value, limit=limit, ok=bool(ok), **detail)


def check_lines(checks: List[dict]) -> List[str]:
    """What standard error ends on: every check with its reading and
    its limit, then one `failed:` line for each that failed."""
    def words(c: dict) -> str:
        detail = ", ".join(
            f"{k} {v}" for k, v in c.items()
            if k not in ("name", "value", "limit", "ok")
        )
        return f"{c['name']} {c['value']:.6g} against {c['limit']:.6g}" + (
            f" ({detail})" if detail else ""
        )

    lines = [
        f"[benchmark] check: {words(c)}: {'ok' if c['ok'] else 'FAILED'}"
        for c in checks
    ]
    return lines + [
        f"[benchmark] failed: {words(c)}" for c in checks if not c["ok"]
    ]


def checks_of(run: dict) -> Dict[str, dict]:
    """{name: reading, limit, ok, where} for the result line."""
    return {
        c["name"]: {k: v for k, v in c.items() if k != "name"}
        for c in run.get("checks") or []
    }


def result_line(run: dict, metrics: Dict[str, dict]) -> str:
    """The contract's last line of standard output; the numbers
    `correct` compared, each beside its limit, come last in it."""
    line: Dict[str, Any] = {
        "correct": bool(run["correct"]),
        "attempted": int(run["attempted"]),
        "failed": int(run["failed"]),
        "metrics": metrics,
        "device": run["device"],
    }
    if run.get("breakdown"):
        line["breakdown"] = run["breakdown"]
    line["checks"] = checks_of(run)
    return json.dumps(line)
