"""Run cells several times as the driver does and print, per metric,
each set's median and spread (quartile distance over the median).

    python benchmark/measure.py --workload <cell> [--workload ...] \
        --sets 2 --runs 6 [--seconds S] [--traced 1] [--seed0 100]

Every run is a new process of `benchmark/run.py`; the runs of a set
have seeds `seed0`, `seed0 + 1`, ..., the same in every set;
result lines and timings go to `chiprun_out/measure.<cell>.jsonl`.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark.stats import median, spread  # noqa: E402


def one_run(cell: str, seed: int, seconds, trace: int, log) -> dict:
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell,
           "--seed", str(seed), "--trace", str(trace)]
    if seconds is not None:
        cmd += ["--seconds", str(seconds)]
    t0 = time.time()
    proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    row = {"cell": cell, "seed": seed, "trace": trace, "rc": proc.returncode,
           "wall_s": time.time() - t0}
    lines = proc.stdout.strip().splitlines()
    try:
        row["result"] = json.loads(lines[-1])
    except (IndexError, ValueError):
        row["stderr"] = proc.stderr[-3000:]
    row["notes"] = [x for x in lines if x.startswith("[benchmark]")]
    log.write(json.dumps(row) + "\n")
    log.flush()
    return row


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", action="append", required=True)
    parser.add_argument("--sets", type=int, default=2)
    parser.add_argument("--runs", type=int, default=6)
    parser.add_argument("--traced", type=int, default=1)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--seed0", type=int, default=100)
    args = parser.parse_args()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    bad = 0
    for cell in args.workload:
        path = os.path.join(ROOT, "chiprun_out", f"measure.{cell}.jsonl")
        with open(path, "a") as log:
            for s in range(args.sets):
                # the same seeds in every set, as the driver's sets have
                rows = [
                    one_run(cell, args.seed0 + i, args.seconds, 0, log)
                    for i in range(args.runs)
                ]
                good = [r["result"] for r in rows if "result" in r]
                bad += len(rows) - len(good)
                names = sorted({n for g in good for n in g["metrics"]})
                for name in names:
                    values = [g["metrics"][name]["value"] for g in good
                              if name in g["metrics"]]
                    print(json.dumps({
                        "cell": cell, "set": s, "metric": name, "n": len(values),
                        "median": median(values), "spread": spread(values),
                        "values": values,
                        "correct": all(g["correct"] for g in good),
                    }), flush=True)
            for i in range(args.traced):
                row = one_run(
                    cell, args.seed0 + args.runs + i, args.seconds, 1, log
                )
                print(json.dumps({"cell": cell, "traced": row.get(
                    "result", row.get("stderr"))}), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
