"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process per run. It never imports JAX: the worker or replica
that leases the chips does, so one process uses each chip. It starts a
local cluster, loads, warms the cell's own shapes, measures for
`--seconds`, and prints the contract's JSON object as the last line of
standard output. Without a TPU (or with fewer chips than the cell
asks for) it exits non-zero and prints no result.

    --rehearse   the same code at the tiny sizes the files carry under
                 `rehearsal`, on the CPU with virtual devices; prints
                 platform=cpu, metric names and no device number.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

STARTED = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main() -> int:
    from benchmark import harness

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=[0, 1], default=0)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument(
        "--rate", type=float, default=None,
        help="open-loop cells: offer this rate instead of the file's "
        "(one point of the knee; each rate in a process of its own, since "
        "windows above the knee leave a serve process slow)",
    )
    parser.add_argument(
        "--control", action="store_true",
        help="serve cells: also read the control (benchmark/control.py's "
        "int8 weights under the reference) over the run's own sample of "
        "served requests; how a limit's upper reading is taken, no part "
        "of a run",
    )
    args = parser.parse_args()

    manifest = harness.load_manifest()
    cell = harness.find_cell(manifest, args.workload)
    config = harness.load_config(manifest, cell["config"])
    traffic = harness.load_traffic(cell["traffic"])
    if args.rehearse:
        config = harness.apply_rehearsal(config)
        traffic = harness.apply_rehearsal(traffic)
        os.environ["JAX_PLATFORMS"] = "cpu"
        flag = "--xla_force_host_platform_device_count="
        kept = [
            f for f in os.environ.get("XLA_FLAGS", "").split()
            if not f.startswith(flag)
        ]
        os.environ["XLA_FLAGS"] = " ".join(kept + [f"{flag}{cell['chips']}"])
    if args.rate is not None:
        traffic = dict(traffic, rate_per_s=args.rate)
    seconds = args.seconds
    if seconds is None:
        seconds = traffic.get("rehearsal_seconds", 3.0) if args.rehearse \
            else float(manifest["run_seconds"])

    from ray_tpu._private.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    # Programs that compile in under a second are cached too: set-up
    # is then the same work in every run after the first.
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    for key, value in (config.get("runtime_env") or {}).items():
        os.environ[key] = str(value)

    generator = harness.load_module("traffic", traffic["kind"])
    driver = harness.load_module("drivers", generator.DRIVER)
    scratch = os.path.join(ROOT, ".scratch", f"{cell['name']}.{os.getpid()}")
    os.makedirs(scratch, exist_ok=True)
    try:
        run = driver.run({
            "cell": cell, "config": config, "traffic": traffic,
            "generator": generator, "seed": args.seed, "seconds": seconds,
            "trace": bool(args.trace), "rehearse": args.rehearse,
            "started_epoch": STARTED, "scratch": scratch,
            "control": args.control,
        })
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    run.update(cell=cell, config=config, traffic=traffic, seconds=seconds)

    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise harness.BenchmarkError("the driver initialised a JAX backend")

    section, directory = (
        ("per_layer", "layer_metrics") if args.trace
        else ("end_to_end", "end_to_end")
    )
    metrics = harness.reduce_metrics(
        manifest, section, directory, run, rehearse=args.rehearse
    )
    print("[benchmark] notes " + json.dumps(run.get("notes", {})), flush=True)
    # The exit code says only that something failed; these lines, the
    # last on standard error, and `checks` in the last line of standard
    # output say what, with each reading beside its limit.
    verdict = "\n".join(harness.check_lines(run["checks"]))
    if args.rehearse:
        # A CPU walk-through proves paths and counts; it never prints a
        # number under the name of a device metric.
        run["device"].pop("memory_peak_bytes", None)
        print(json.dumps({
            "rehearsal": True, "correct": bool(run["correct"]),
            "attempted": run["attempted"], "failed": run["failed"],
            "metric_names": sorted(metrics), "device": run["device"],
            "checks": harness.checks_of(run),
        }))
        print(verdict, file=sys.stderr, flush=True)
        return 0 if run["correct"] and not run["failed"] else 1
    trace = run.get("trace") or {}
    if args.trace:
        if not trace.get("busy_s"):
            raise harness.BenchmarkError(
                "the traced window shows no operation on the device"
            )
        run["device"].update(
            busy_s=trace["busy_s"], window_s=trace["window_s"]
        )
        run["breakdown"] = {
            "device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]
        }
    elif len(metrics) < len(
        harness.metrics_of_cell(manifest, "end_to_end", cell["name"])
    ):
        raise harness.BenchmarkError(f"metrics missing: got {sorted(metrics)}")
    print(harness.result_line(run, metrics), flush=True)
    print(verdict, file=sys.stderr, flush=True)
    return 0 if run["correct"] and not run["failed"] else 1


if __name__ == "__main__":
    sys.exit(main())
