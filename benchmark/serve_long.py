"""One serve process past several hundred requests: does anything grow?

    python3 benchmark/serve_long.py [--rates 0.8,1.0,1.2,1.4,1.6] [--seconds 96] [--seed 7]

Not a cell and not judged. It deploys `chat_loaded`'s configuration as
the serve driver does, then offers one open-loop window per rate in
one process, as the knee sweeps of PR 22 and PR 25 that slowed down did (answers
cut short, median 48 and at most 128 tokens, so that a window holds a
hundred requests; each window another seed, cut 5 s after its edge
like a measured one) and prints one line per window: the engine loop's
phases per iteration (`engine.stats()["loop_ms"]` deltas), the client's
lateness and gaps, the means of the serve path's own timers and the
live threads of this process, which is driver and head daemon (the load
comes from `drivers/serve_client.py`, a process of its own, as in a
cell). A phase that grows names the engine; flat phases beside a
growing lateness name the path between the client and the engine. At the end it
asks the runtime's own profiler for a `jax.profiler` trace of the
replica (`profile_worker(kind="gang")`), under a little load, and
prints what came back.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SHORT_ANSWERS = {
    "dist": "lognormal", "median": 48, "sigma": 0.5, "min": 1, "max": 128,
}


def thread_families() -> dict:
    """Live threads of this process (the driver and the head daemon it
    hosts), counted by what they run."""
    out: dict = {}
    for thread in threading.enumerate():
        target = getattr(thread, "_target", None)
        family = getattr(target, "__qualname__", None) or type(thread).__name__
        out[family] = out.get(family, 0) + 1
    return out


def gang_profile(serve, replica, port: int, request: dict) -> dict:
    """What the runtime's own profilers return for the replica."""
    from ray_tpu.util import state

    out = {}
    try:
        state.profile_gang(duration_s=1.0)
        out["profile_gang"] = "returned"
    except Exception as e:  # noqa: BLE001 — what came back is the point
        out["profile_gang"] = repr(e)[:300]
    stop = threading.Event()

    def load() -> None:
        while not stop.is_set():
            serve.stream_request(port, request, time.perf_counter, {})

    threads = [threading.Thread(target=load, daemon=True) for _ in range(4)]
    for thread in threads:
        thread.start()
    time.sleep(2.0)
    try:
        reply = state.profile_worker(
            replica.probe()["pid"], kind="gang", duration_s=3.0
        )
    finally:
        stop.set()
        for thread in threads:
            thread.join(timeout=180)
    trace_dir = reply.get("jax_trace_dir")
    out.update(
        samples=reply.get("samples"), threads=reply.get("threads"),
        slices=len(reply.get("events") or ()), jax_trace_dir=trace_dir,
    )
    if trace_dir:
        summary = subprocess.run(
            [sys.executable, "-c", (
                "import json, sys\n"
                "from benchmark.trace import xplane\n"
                "events = xplane.read(xplane.find_xplane(sys.argv[1]))\n"
                "phases = {}\n"
                "for _, name, _, dur in events['host']:\n"
                "    if name.startswith('engine.'):\n"
                "        phases[name] = phases.get(name, 0.0) + dur / 1e9\n"
                "print(json.dumps({'host_phases_s': phases,\n"
                "                  'summary': xplane.summarize(events)}))\n"
            ), trace_dir],
            cwd=ROOT, capture_output=True, text=True, timeout=600,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        out["trace"] = (
            json.loads(summary.stdout.strip().splitlines()[-1])
            if summary.returncode == 0 else summary.stderr[-2000:]
        )
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--rates", default="0.8,1.0,1.2,1.4,1.6")
    parser.add_argument("--seconds", type=float, default=96.0)
    parser.add_argument("--seed", type=int, default=7)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args()

    from benchmark import harness
    from benchmark.drivers import serve

    manifest = harness.load_manifest()
    cell = harness.find_cell(manifest, "chat_loaded")
    config = harness.load_config(manifest, cell["config"])
    traffic = harness.load_traffic(cell["traffic"])
    if args.rehearse:
        config = harness.apply_rehearsal(config)
        traffic = harness.apply_rehearsal(traffic)
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        traffic = dict(traffic, output_tokens=SHORT_ANSWERS)
    generator = harness.load_module("traffic", traffic["kind"])
    vocab = config["model"]["vocab_size"]

    from ray_tpu._private.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    for key, value in (config.get("runtime_env") or {}).items():
        os.environ[key] = str(value)

    import ray_tpu as rt
    import ray_tpu.serve as rt_serve

    scratch = os.path.join(ROOT, ".scratch", f"serve_long.{os.getpid()}")
    os.makedirs(scratch, exist_ok=True)
    rt.init(num_tpus=1 if args.rehearse else None)
    try:
        port = serve.deploy(config, args.seed)
        replica = serve.Replica(config["name"])
        warmup = generator.warmup(traffic, args.seed, vocab)
        deadline = time.monotonic() + 1000
        while not all(
            serve.stream_request(port, r, time.perf_counter, {})["ok"]
            for r in warmup
        ):
            if time.monotonic() > deadline:
                raise harness.BenchmarkError("warm-up never completed")
        served = 0
        rates = [float(r) for r in args.rates.split(",")]
        for window, rate in enumerate(rates):
            with serve.ClientWindow(
                port, dict(traffic, rate_per_s=rate), args.seed + 1 + window,
                args.seconds, vocab, scratch,
            ) as client:
                replica.wait_idle()
                time.sleep(1.0)  # let the replica's metric buffer flush
                client.open()
                before, edge = serve.window_ends(replica, client)
                rows = client.records()
            served += len(rows)
            point = serve.sweep_point(rate, rows, args.seconds)
            b, a = before["engine"], edge["engine"]
            iterations = a["loop_iterations"] - b["loop_iterations"]
            print("[serve_long] window " + json.dumps({
                "window": window, "rate_per_s": rate, "served_so_far": served,
                "client": {k: point[k] for k in (
                    "requests", "failed", "shed", "cut", "late_p99_ms",
                    "itl_p50_ms", "itl_mean_ms", "itl_p95_ms", "ttft_p50_ms",
                    "in_flight_end",
                )},
                "driver_threads": thread_families(),
                "engine": {
                    "iterations": iterations,
                    "steps": a["steps"] - b["steps"],
                    "admitted": a["admitted"] - b["admitted"],
                    "loop_ms_per_iteration": {
                        phase: (ms - b["loop_ms"].get(phase, 0.0))
                        / max(iterations, 1)
                        for phase, ms in sorted(a["loop_ms"].items())
                    },
                },
                "serve_timer_means_ms": serve.timer_means(
                    before["metrics"], edge["metrics"]
                ),
            }), flush=True)
        profile = gang_profile(serve, replica, port, warmup[0])
        print("[serve_long] profile " + json.dumps(profile), flush=True)
        rt_serve.shutdown()
    finally:
        rt.shutdown()
        shutil.rmtree(scratch, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
