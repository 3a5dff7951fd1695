"""Serve cells: HTTP -> proxy -> router -> replica -> engine, the path a
client of `serve.run(build_llm_app(..))` takes.

Order of a run: the cluster and one replica that leases the chip and
is handed the benchmark's weights (`serve_replica.py`), warm-up
requests until the engine has loaded them and compiled its programs,
then the lead-in (where the traffic file has one) and the window; and
once the window has closed, the replica's peak memory has been read
and the cluster is shut down (the chip free again), the comparison
with the reference: a sample of the requests the window finished,
their served tokens held against the reference's logits in a child
that holds the chip and exits (`serve_probe.py`). Nothing of it is
part of `setup_s` (until PR 36 a probe of the program's forwards ran
first and its 18-26 s, the reference's pass included, were). This
process hosts the head daemon and never touches JAX. It sends the
warm-up itself and stamps nothing that is judged: the load of a window
comes from `serve_client.py`, a process of its own, which hands its
records back in a file (`ClientWindow`).

The window opens `lead_in_s` after the client's first request is due,
on an engine that already carries its standing population; `setup_s`
ends there. It ends at its edge: the engine's counters and timers are
read at both ends, closed-loop callers stop at the edge, and what is
still in flight is cut by the client `DRAIN_S` later (open loop) or at
once (closed loop). A cut request is neither failed nor complete: what
it streamed inside the window counts.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

from ..harness import ROOT, BenchmarkError, check, load_module
from . import serve_client, serve_probe
from .serve_client import DRAIN_S, monotonic, stream_request

APP, ROUTE = "llm", serve_client.ROUTE
#: From telling the client the clock's origin to its first request.
CLIENT_START_S = 0.25


def run_probe(ctx: dict, served: list) -> dict:
    """The reference's pass over `served` (`serve_probe.sample`), in a
    child that holds the chip; `ctx["control"]` asks for the control's
    reading beside it (`run.py --control`)."""
    config = ctx["config"]
    spec = {
        "model": config["model"], "dtype": config["dtype"],
        "reference": config.get("reference"), "engine": config["engine"],
        "tolerance": config["tolerance"], "served": served,
        "probe_lengths": config["probe_lengths"],
        "control": bool(ctx.get("control")), "seed": ctx["seed"],
        "chips": ctx["cell"]["chips"], "rehearse": ctx["rehearse"],
    }
    path = os.path.join(ctx["scratch"], "served.json")
    with open(path, "w") as f:
        json.dump(spec, f)
    # The replica that held the chip has just been shut down: where its
    # process has not let go yet the child finds no chip (exit code 1),
    # and is tried again. Exit code 2 is a comparison that could not be
    # made, and its last line says why.
    for attempt in range(3):
        proc = subprocess.run(
            [sys.executable, "-m", "benchmark.drivers.serve_probe", path],
            cwd=ROOT, capture_output=True, text=True, timeout=1000,
        )
        if proc.returncode == 0:
            out = json.loads(proc.stdout.strip().splitlines()[-1])
            return dict(out, attempts=attempt + 1)
        sys.stderr.write(proc.stderr[-4000:])
        if proc.returncode == 2:
            break
        if attempt < 2:
            time.sleep(3.0)
    said = (proc.stderr.strip().splitlines() or ["nothing"])[-1]
    raise BenchmarkError(f"serve probe exited {proc.returncode}: {said[:400]}")


def deploy(config: dict, seed: int):
    """What `build_llm_app` does, with the benchmark's subclass."""
    import ray_tpu.serve as serve
    from ray_tpu.serve.deployment import deployment
    from ray_tpu.util.accelerators.tpu import cluster_tpu_chips

    from .serve_replica import BenchLLMServer

    engine = dict(config["engine"])
    dep = deployment(
        name=APP, num_replicas=1,
        max_ongoing_requests=engine["slots"] * 4,
        ray_actor_options={"num_tpus": 1} if cluster_tpu_chips() else None,
    )(BenchLLMServer)
    family = {
        "kind": "benchmark", "seed": seed,
        "config": dict(config["model"], dtype=config["dtype"]),
        "reference": config.get("reference"),
    }
    app = dep.bind(
        {config["name"]: family}, default_family=None, engine=engine,
    )
    serve.run(app, name=APP, route_prefix=ROUTE)
    return serve.start(http_port=0)


class Replica:
    """The one replica, reached past the router for what is not a
    request: engine counters and the probe."""

    def __init__(self, family: str):
        import ray_tpu as rt
        from ray_tpu.serve.controller import CONTROLLER_NAME

        self._rt, self._family = rt, family
        controller = rt.get_actor(CONTROLLER_NAME, namespace="serve")
        rows = rt.get(controller.get_replicas.remote(APP, APP), timeout=60)
        if len(rows) != 1:
            raise BenchmarkError(f"expected one replica, got {rows}")
        self._actor = rows[0]["actor"]

    def call(self, method: str, *args, timeout: float = 120.0):
        return self._rt.get(
            self._actor.handle_request.remote(method, args, {}),
            timeout=timeout,
        )

    def engine(self) -> dict:
        return self.call("engine_stats").get(self._family) or {}

    def probe(self, op: str = "snapshot", arg: str = "") -> dict:
        return self.call("bench_probe", op, arg)

    def wait_idle(self, timeout_s: float = 60.0) -> dict:
        deadline = time.monotonic() + timeout_s
        while True:
            stats = self.engine()
            busy = (
                stats.get("slots_used") or stats.get("waiting")
                or stats.get("prefilling")
            )
            if not busy or time.monotonic() > deadline:
                return stats
            time.sleep(0.1)


class ClientWindow:
    """One window of load from a client process of its own.

    Made early: the client draws its load while the engine drains.
    `open()` fixes the clock's origin (the time the window opens, the
    traffic's `lead_in_s` after the first request is due) and tells the
    client; `clock()` is seconds after it, negative in the lead-in;
    `records()` waits for the client and reads what it wrote."""

    def __init__(
        self, port: int, traffic: dict, seed: int, seconds: float,
        vocab_size: int, scratch: str,
    ):
        self.seconds = seconds
        self.lead_in_s = float(traffic.get("lead_in_s", 0.0))
        stem = os.path.join(scratch, f"client.{seed}.{time.time_ns()}")
        self._out = stem + ".records.json"
        with open(stem + ".json", "w") as f:
            json.dump({
                "port": port, "traffic": traffic, "seed": seed,
                "seconds": seconds, "vocab_size": vocab_size,
                "out": self._out,
            }, f)
        self._proc = subprocess.Popen(
            [sys.executable, serve_client.__file__, stem + ".json"],
            cwd=ROOT, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True,
        )
        self.origin = self.epoch = None

    def __enter__(self) -> "ClientWindow":
        return self

    def __exit__(self, *exc) -> None:
        if self._proc.poll() is None:
            self._proc.kill()
        self._proc.wait()
        for pipe in (self._proc.stdin, self._proc.stdout):
            pipe.close()

    def open(self) -> None:
        if self._proc.stdout.readline().strip() != "ready":
            raise BenchmarkError("the load generator's process did not start")
        self.origin = monotonic() + CLIENT_START_S + self.lead_in_s
        self.epoch = time.time() + (self.origin - monotonic())
        self._proc.stdin.write(f"{self.origin!r}\n")
        self._proc.stdin.flush()

    def clock(self) -> float:
        return monotonic() - self.origin

    def sleep_until(self, t_s: float) -> None:
        time.sleep(max(0.0, t_s - self.clock()))

    def records(self) -> list:
        left = self.seconds + DRAIN_S + 60.0 - self.clock()
        try:
            code = self._proc.wait(timeout=max(left, 1.0))
        except subprocess.TimeoutExpired:
            raise BenchmarkError("the load generator outlived its window")
        if code != 0:
            raise BenchmarkError(f"the load generator exited {code}")
        with open(self._out) as f:
            return json.load(f)


def sweep_point(rate: float, rows: list, seconds: float) -> dict:
    """One window of an open-loop cell, at the file's rate or at
    `run.py --rate` for a point of the knee: did completions keep up?
    Holds the judged statistics and their neighbours, so a reading can
    be checked against them. The lead-in's requests count among those
    in flight and stream gaps into the window; the per-request samples
    are the window's own."""
    from ..stats import (
        in_window, lateness_ms, percentile, pooled_gaps_ms, served, ttfts_ms,
    )

    own = in_window(rows)
    ttft = ttfts_ms(own)
    gaps = pooled_gaps_ms(rows, seconds)
    step = percentile(gaps, 50) or 0.0

    def in_flight(t: float) -> int:
        return sum(1 for r in rows if r["due_s"] <= t < r["done_s"])

    point = {
        "rate_per_s": rate, "requests": len(own),
        "lead_in_requests": len(rows) - len(own),
        "failed": sum(1 for r in rows if not served(r)),
        "shed": sum(1 for r in rows if r["status"] == 503),
        "cut": sum(1 for r in rows if r["cut"]),
        "ttft_mean_ms": sum(ttft) / max(len(ttft), 1),
        "ttft_p50_second_half_ms": percentile(
            ttfts_ms([r for r in own if r["due_s"] >= seconds / 2]), 50
        ),
        "in_flight_start": in_flight(0.0),
        "in_flight_mid": in_flight(seconds / 2),
        "in_flight_end": in_flight(seconds - 1e-3),
        "drain_s": max(r["done_s"] for r in rows) - seconds,
        "late_p99_ms": percentile(lateness_ms(
            [r["due_s"] for r in own], [r["sent_s"] for r in own]
        ), 99),
        "gaps": len(gaps),
        "itl_mean_ms": sum(gaps) / max(len(gaps), 1),
        "itl_over_1p25_step_share": sum(
            1 for g in gaps if g > 1.25 * step
        ) / max(len(gaps), 1),
    }
    for q in (50, 80, 90):
        point[f"ttft_p{q}_ms"] = percentile(ttft, q)
    for q in (50, 90, 95, 99):
        point[f"itl_p{q}_ms"] = percentile(gaps, q)
    return point


def cluster_metrics() -> dict:
    """{name: (sum, count)} of the serve path's timers (`serve_*`:
    proxy, router, replica and the engine's fenced ones), from the
    head's metrics table (processes flush every 0.5 s)."""
    from ray_tpu.util.metrics import metrics_summary

    summary = metrics_summary()
    return {
        name: [float(row.get("sum", 0.0)), float(row.get("count", 0.0))]
        for name, row in summary.items()
        if name.startswith("serve_") and "count" in row
    }


def timer_means(before: dict, after: dict) -> dict:
    """Mean of every timer of two `cluster_metrics` tables over the
    time between them."""
    out = {}
    for name, (total, count) in after.items():
        total0, count0 = before.get(name, (0.0, 0.0))
        if count > count0:
            out[name] = (total - total0) / (count - count0)
    return out


def window_ends(replica: Replica, window: ClientWindow) -> tuple:
    """The engine's counters and the program's timers at the window's
    two ends. The timers reach the head's table up to half a second
    late: a mean over 48 s does not see it."""
    ends = []
    for at_s in (0.0, window.seconds):
        window.sleep_until(at_s)
        ends.append({
            "engine": replica.engine(), "metrics": cluster_metrics()
        })
    return tuple(ends)


def engine_point(before: dict, edge: dict) -> dict:
    """What the engine and the serve path's own timers say of one
    window of an open-loop cell: how full the batch ran (the per-layer
    reader's number) and the mean of every `serve_*` timer."""
    run = {"engine": {"before": before["engine"], "after": edge["engine"]}}
    return {
        "steps": edge["engine"]["steps"] - before["engine"]["steps"],
        "tokens_per_step": load_module(
            "layer_metrics", "engine_tokens_per_step"
        ).reduce(run),
        "timer_means_ms": timer_means(before["metrics"], edge["metrics"]),
    }


def trace_window(replica: Replica, trace_dir: str, start_s, length_s, clock):
    delay = start_s - clock()
    if delay > 0:
        time.sleep(delay)
    replica.probe("trace_start", trace_dir)
    time.sleep(length_s)
    replica.probe("trace_stop")


def run(ctx: dict) -> dict:
    import ray_tpu as rt
    import ray_tpu.serve as serve

    config, traffic = ctx["config"], ctx["traffic"]
    seconds = ctx["seconds"]
    vocab = config["model"]["vocab_size"]
    # The warm-up alone: the window's requests are drawn in one place,
    # the client's process.
    warm_requests = ctx["generator"].warmup(traffic, ctx["seed"], vocab)
    loop = ctx["generator"].LOOP
    marks = {"start": time.time()}

    rt.init(num_tpus=ctx["cell"]["chips"] if ctx["rehearse"] else None)
    try:
        if int(rt.cluster_resources().get("TPU", 0)) < ctx["cell"]["chips"]:
            raise BenchmarkError("the runtime found no chip to lease")
        port = deploy(config, ctx["seed"])
        replica = Replica(config["name"])
        marks["deployed"] = time.time()

        # Warm-up: the first request loads the weights and compiles;
        # it may outlast the router's per-chunk bound, so failed
        # requests are sent again until the engine answers.
        deadline = time.monotonic() + 1000
        warm, warm_rounds = [], []
        while True:
            warm = [
                stream_request(port, r, time.perf_counter, {})
                for r in warm_requests
            ]
            warm_rounds.append([
                [round(r["done_s"] - r["sent_s"], 3), r["status"]]
                for r in warm
            ])
            if all(r["ok"] for r in warm):
                break
            if time.monotonic() > deadline:
                raise BenchmarkError(f"warm-up never completed: {warm[-1]}")
        # Greedy replay through the same path: both sends find the
        # prompt in the prefix cache (a hit and a miss may round
        # differently and are never compared token for token).
        replays = [
            stream_request(port, warm_requests[0], time.perf_counter, {})
            for _ in range(2)
        ]
        replay_equal = (
            all(r["ok"] for r in replays)
            and replays[0]["tokens"] == replays[1]["tokens"]
        )
        marks["warmed"] = time.time()
        with ClientWindow(
            port, traffic, ctx["seed"], seconds, vocab, ctx["scratch"]
        ) as window:
            replica.wait_idle()
            time.sleep(1.0)  # let the replica's metric buffer flush
            # Compiles are counted from before the lead-in: a shape the
            # warm-up missed makes the run incorrect there too.
            before = {"probe": replica.probe()}
            marks["idle"] = time.time()
            window.open()
            marks["window"] = window.epoch
            tracer = None
            trace_dir = os.path.join(ctx["scratch"], "trace")
            if ctx["trace"]:
                tracer = threading.Thread(
                    target=trace_window, daemon=True, args=(
                        replica, trace_dir, 0.4 * seconds,
                        min(float(traffic["trace_seconds"]), 0.5 * seconds),
                        window.clock,
                    ),
                )
                tracer.start()
            at_open, edge = window_ends(replica, window)
            before.update(at_open)
            records = window.records()
        if tracer is not None:
            tracer.join(timeout=120)
        after = {"engine": replica.engine(), "probe": replica.probe()}
        serve.shutdown()
    finally:
        rt.shutdown()

    trace = None
    if ctx["trace"] and after["probe"]["platform"] != "cpu":
        out = subprocess.run(
            [sys.executable, "-m", "benchmark.trace.xplane", trace_dir],
            cwd=ROOT, capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        if out.returncode != 0:
            sys.stderr.write(out.stderr[-4000:])
            raise BenchmarkError("could not reduce the replica's trace")
        trace = json.loads(out.stdout.strip().splitlines()[-1])

    # `correct`'s comparison with the reference: after the window, on
    # the chip the replica has given back, outside `setup_s`.
    marks["closed"] = time.time()
    served = serve_probe.sample(records, ctx["seed"])
    for r in records:
        r.pop("prompt", None)  # the sample has its own
    if not served:
        raise BenchmarkError("the window finished no request to compare")
    probe = run_probe(ctx, served)
    marks["probe_done"] = time.time()

    grew = {
        name: count - before["probe"]["compiles"].get(name, 0)
        for name, count in after["probe"]["compiles"].items()
        if count > before["probe"]["compiles"].get(name, 0)
    }
    compiled = sum(grew.values())
    lost = [r for r in records if not (r["ok"] or r["cut"])]
    failed = len(lost)
    out_of_range = sum(
        1 for r in records + warm for t in r["tokens"] if not 0 <= t < vocab
    )
    worst = probe["requests_rows"][probe["worst_request"]]
    where = {
        "served_gap_max": f"{probe['tokens']} served tokens of "
        f"{probe['requests']} requests, the widest at token "
        f"{probe['worst_token']} of a request of {worst['n_prompt']} + "
        f"{worst['n_out']}",
        "logits_rel_rms": f"prefill {probe['prefill']:.6g}, decode "
        f"{probe['decode']:.6g}, {len(probe['rows'])} slots",
        "logits_rel_rms_row": probe["worst_row"],
    }
    checks = [
        check(name, probe[name], limit, where=where[name])
        for name, limit in probe["limits"].items()
    ] + [
        check(
            "compiles_in_window", compiled, 0,
            **({"programs": " ".join(sorted(grew))} if grew else {}),
        ),
        check("replay_equal", replay_equal, 1, at_least=True),
        check(
            "tokens_in_range", out_of_range == 0, 1, at_least=True,
            **({"out_of_range": out_of_range} if out_of_range else {}),
        ),
        check("engine_dead", bool(after["engine"].get("dead")), 0),
        check(
            "platform",
            probe["device"]["platform"] == after["probe"]["platform"], 1,
            at_least=True, probe=probe["device"]["platform"],
            replica=after["probe"]["platform"],
        ),
    ]
    correct = all(c["ok"] for c in checks)
    # not part of `correct`: a failed request fails the run by itself
    checks.append(check(
        "failed", failed, 0, **({
            "first_status": lost[0]["status"],
            **({"first_error": str(lost[0]["error"])[:120]}
               if lost[0].get("error") else {}),
        } if lost else {}),
    ))
    device = {
        "platform": after["probe"]["platform"],
        "kind": after["probe"]["kind"],
        "count": after["probe"]["count"],
        "memory_peak_bytes": after["probe"]["memory_peak_bytes"],
    }
    run = {
        "kind": "serve",
        "device": device,
        "correct": correct,
        "checks": checks,
        "attempted": len(records),
        "failed": failed,
        "setup_s": marks["window"] - ctx["started_epoch"],
        "loop": loop,
        "requests": records,
        "window_s": seconds,
        "engine": {"before": before["engine"], "after": edge["engine"]},
        "engine_timers": {
            "before": before["metrics"], "after": edge["metrics"]
        },
        "trace": trace,
        "notes": {
            "probe": probe, "compiles_in_window": compiled,
            "replay_equal": replay_equal,
            "statuses": sorted({r["status"] for r in records}),
            "setup_parts_s": {
                "before_cluster": marks["start"] - ctx["started_epoch"],
                "cluster_and_deploy": marks["deployed"] - marks["start"],
                "load_and_warm": marks["window"] - marks["deployed"],
                # `load_and_warm` again, by what the driver waited for:
                # the warm-up's requests (the first loads the weights
                # and the programs), the replays, the engine idle and
                # its metrics flushed, the client told to start (with
                # the lead-in, where the traffic has one)
                "warm_up": marks["warmed"] - marks["deployed"],
                "idle_and_flush": marks["idle"] - marks["warmed"],
                "client_start": marks["window"] - marks["idle"],
            },
            # seconds and status of each warm-up request, round by round
            "warm_rounds": warm_rounds,
            # what the first request spent in the replica before the
            # engine was built (`serve_replica.build_model`)
            "replica_load_s": before["probe"]["load_s"],
            "replica_compile_ms": before["probe"]["compile_ms"],
            # after the window, no part of `setup_s`
            "probe_s": marks["probe_done"] - marks["closed"],
            "lead_in_s": window.lead_in_s,
            "window": (
                dict(
                    sweep_point(traffic["rate_per_s"], records, seconds),
                    **engine_point(before, edge)
                ) if loop == "open" else None
            ),
            "cut": sum(1 for r in records if r["cut"]),
        },
    }
    # An untraced run says too what the engine did in its window: where
    # two runs' throughput differs, whether the hit share or the step did.
    run["notes"]["engine_window"] = {
        name: load_module("layer_metrics", name).reduce(run)
        for name in (
            "prefix_hit_token_share", "engine_tokens_per_step",
            "decode_step_mean_ms", "prefill_chunk_mean_ms",
            "engine_host_share",
        )
    }
    return run
