"""Serve cells: HTTP -> proxy -> router -> replica -> engine, the path a
client of `serve.run(build_llm_app(..))` takes.

Order of a run: the correctness probe (a child that holds the chip and
exits), then the cluster and one replica that leases the chip, warm-up
requests until the engine has loaded its weights and compiled its two
programs, then the window. This process sends the load (one scheduler,
a bounded pool of sender threads) and never touches JAX.

Open loop: each request is sent when due and timed from when it was
due; how late the sender ran is recorded. Closed loop: `clients`
callers take the next request of one shared list as soon as their
last reply ended.

The window ends at its edge: the engine's counters and timers are read
there, closed-loop callers stop there, and what is still in flight is
cut (the client closes the connection) after `DRAIN_S`, which only
lets a request that was due late in the window deliver its first
token. A cut request is neither failed nor complete: what it streamed
inside the window counts.
"""

from __future__ import annotations

import http.client
import json
import os
import socket
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from ..harness import ROOT, BenchmarkError

APP, ROUTE = "llm", "/llm"
REQUEST_TIMEOUT_S = 120.0
DRAIN_S = 5.0
NEVER = threading.Event()


def stream_request(
    port: int, request: dict, clock, record: dict, stop=NEVER
) -> dict:
    """POST one prompt and read the token stream to its end, or until
    `cut` closes the connection once `stop` is set. Times are `clock()`
    seconds; every streamed token (digits and a space) gets the time of
    the read that completed it."""
    body = json.dumps({
        "prompt": request["prompt"],
        "max_new_tokens": request["max_new_tokens"],
    })
    record.update(
        n_prompt=len(request["prompt"]), want=request["max_new_tokens"],
        token_s=[], status=0,
    )
    data = b""
    conn = http.client.HTTPConnection(
        "127.0.0.1", port, timeout=REQUEST_TIMEOUT_S
    )
    try:
        record["sent_s"] = clock()
        conn.connect()
        record["sock"] = conn.sock  # for `cut`
        if stop.is_set():
            raise OSError("cut before it was sent")
        conn.request(
            "POST", ROUTE, body=body,
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        record["status"] = resp.status
        while True:
            chunk = resp.read1(65536)
            if not chunk:
                break
            now = clock()
            record["token_s"].extend([now] * chunk.count(b" "))
            data += chunk
    except (OSError, http.client.HTTPException) as e:
        record["error"] = repr(e)
    finally:
        conn.close()
        record.pop("sock", None)
    record["done_s"] = clock()
    whole = data[: data.rfind(b" ") + 1] if record["status"] == 200 else b""
    record["tokens"] = [int(t) for t in whole.split()]
    record["n_out"] = len(record["tokens"])
    record["ok"] = (
        record["status"] == 200 and record["n_out"] == record["want"]
    )
    record["cut"] = (
        stop.is_set() and not record["ok"] and record["status"] in (0, 200)
    )
    if record["token_s"]:
        record["first_s"] = record["token_s"][0]
    return record


def cut(record: dict) -> None:
    """Close a request's connection under its reader (`stop` is set
    first, so a sender that has not connected yet gives up itself)."""
    sock = record.get("sock")
    if sock is not None:
        try:
            sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass


def run_probe(ctx: dict) -> dict:
    config = ctx["config"]
    spec = {
        "model": config["model"], "dtype": config["dtype"],
        "engine": config["engine"], "tolerance": config["tolerance"],
        "probe_lengths": config["probe_lengths"], "seed": ctx["seed"],
        "chips": ctx["cell"]["chips"], "rehearse": ctx["rehearse"],
    }
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.drivers.serve_probe",
         json.dumps(spec)],
        cwd=ROOT, capture_output=True, text=True, timeout=1000,
    )
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr[-4000:])
        raise BenchmarkError(f"serve probe exited {proc.returncode}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


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
        "kind": "init", "seed": seed,
        "config": dict(config["model"], dtype=config["dtype"]),
    }
    app = dep.bind(
        {config["name"]: family}, default_family=None, engine=engine,
        engine_enabled=True,
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


def offer_open(pool, port: int, requests: list, clock, stop) -> list:
    """Send each request when it is due; returns once the last is on
    its way, with the records the sender threads fill."""
    records = [{"due_s": r["due_s"]} for r in requests]
    for request, record in zip(requests, records):
        delay = request["due_s"] - clock()
        if delay > 0:
            time.sleep(delay)
        pool.submit(stream_request, port, request, clock, record, stop)
    return records


def offer_closed(
    pool, port, requests, clients: int, seconds: float, clock, stop
) -> list:
    """Start `clients` callers that take requests until the window's
    edge; returns the shared list of records, which grows."""
    records, lock = [], threading.Lock()

    def client() -> None:
        while True:
            with lock:
                if stop.is_set() or clock() >= seconds:
                    return
                request = next(requests)
                record = {"shared_tokens": request["shared_tokens"]}
                records.append(record)
            stream_request(port, request, clock, record, stop)
            record["due_s"] = record["sent_s"]

    for _ in range(clients):
        pool.submit(client)
    return records


def finish(pool, records: list, clock, deadline_s: float, stop) -> None:
    """Wait for what is in flight until `deadline_s`, then cut it."""
    while clock() < deadline_s and any("done_s" not in r for r in records):
        time.sleep(0.05)
    stop.set()
    for record in list(records):
        cut(record)
    pool.shutdown(wait=True)


def sweep_point(rate: float, rows: list, seconds: float) -> dict:
    """One window of an open-loop cell, or one offered rate of the knee
    sweep: did completions keep up? Holds the judged statistics and
    their neighbours, so a reading can be checked against them."""
    from ..stats import (
        lateness_ms, percentile, pooled_gaps_ms, served, ttfts_ms,
    )

    ttft = ttfts_ms(rows)
    gaps = pooled_gaps_ms(rows, seconds)
    step = percentile(gaps, 50) or 0.0

    def in_flight(t: float) -> int:
        return sum(1 for r in rows if r["due_s"] <= t < r["done_s"])

    point = {
        "rate_per_s": rate, "requests": len(rows),
        "failed": sum(1 for r in rows if not served(r)),
        "shed": sum(1 for r in rows if r["status"] == 503),
        "cut": sum(1 for r in rows if r["cut"]),
        "ttft_mean_ms": sum(ttft) / max(len(ttft), 1),
        "ttft_p50_second_half_ms": percentile(
            ttfts_ms([r for r in rows if r["due_s"] >= seconds / 2]), 50
        ),
        "in_flight_mid": in_flight(seconds / 2),
        "in_flight_end": in_flight(seconds - 1e-3),
        "drain_s": max(r["done_s"] for r in rows) - seconds,
        "late_p99_ms": percentile(lateness_ms(
            [r["due_s"] for r in rows], [r["sent_s"] for r in rows]
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
    """{name: (sum, count)} of the engine's fenced timers, from the
    head's metrics table (replicas flush every 0.5 s)."""
    from ray_tpu.util.metrics import metrics_summary

    summary = metrics_summary()
    return {
        name: [float(row.get("sum", 0.0)), float(row.get("count", 0.0))]
        for name, row in summary.items()
        if name.startswith("serve_engine_") and "count" in row
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
    load = ctx["generator"].generate(
        traffic, ctx["seed"], seconds, config["model"]["vocab_size"]
    )
    marks = {"start": time.time()}
    probe = run_probe(ctx)
    marks["probe_done"] = time.time()

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
        warm = []
        while True:
            warm = [
                stream_request(port, r, time.perf_counter, {})
                for r in load["warmup"]
            ]
            if all(r["ok"] for r in warm):
                break
            if time.monotonic() > deadline:
                raise BenchmarkError(f"warm-up never completed: {warm[-1]}")
        # Greedy replay through the same path: both sends find the
        # prompt in the prefix cache (a hit and a miss may round
        # differently and are never compared token for token).
        replays = [
            stream_request(port, load["warmup"][0], time.perf_counter, {})
            for _ in range(2)
        ]
        replay_equal = (
            all(r["ok"] for r in replays)
            and replays[0]["tokens"] == replays[1]["tokens"]
        )
        for i, rate in enumerate(ctx.get("sweep") or ()):
            # Finding the knee: one window per offered rate in this
            # one process (one set-up), each with another seed and cut
            # like the measured one; the engine drops what was cut, so
            # the next window starts on an idle engine.
            point = ctx["generator"].generate(
                dict(traffic, rate_per_s=rate), ctx["seed"] + 1000 + i,
                seconds, config["model"]["vocab_size"],
            )
            t_point = time.perf_counter()
            point_clock = lambda: time.perf_counter() - t_point  # noqa: E731
            pool, stop = ThreadPoolExecutor(max_workers=128), threading.Event()
            rows = offer_open(pool, port, point["requests"], point_clock, stop)
            finish(pool, rows, point_clock, seconds + DRAIN_S, stop)
            print("[benchmark] sweep " + json.dumps(
                sweep_point(rate, rows, seconds)), flush=True)

        replica.wait_idle()
        time.sleep(1.0)  # let the replica's metric buffer flush
        before = {
            "engine": replica.engine(), "probe": replica.probe(),
            "metrics": cluster_metrics(),
        }

        window_start_epoch = marks["window"] = time.time()
        t0 = time.perf_counter()

        def clock() -> float:
            return time.perf_counter() - t0

        tracer = None
        trace_dir = os.path.join(ctx["scratch"], "trace")
        if ctx["trace"]:
            tracer = threading.Thread(
                target=trace_window, daemon=True, args=(
                    replica, trace_dir, 0.4 * seconds,
                    min(float(traffic["trace_seconds"]), 0.5 * seconds),
                    clock,
                ),
            )
            tracer.start()
        is_open = load["loop"] == "open"
        pool, stop = ThreadPoolExecutor(max_workers=128), threading.Event()
        if is_open:
            records = offer_open(pool, port, load["requests"], clock, stop)
        else:
            records = offer_closed(
                pool, port, load["requests"], load["clients"], seconds,
                clock, stop,
            )
        time.sleep(max(0.0, seconds - clock()))
        # The window's edge. The timers reach the head's table up to
        # half a second late: a mean over 48 s does not see it.
        edge = {"engine": replica.engine(), "metrics": cluster_metrics()}
        finish(
            pool, records, clock, seconds + (DRAIN_S if is_open else 0.0),
            stop,
        )
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

    vocab = config["model"]["vocab_size"]
    in_range = all(
        0 <= t < vocab for r in records + warm for t in r["tokens"]
    )
    compiled = sum(after["probe"]["compiles"].values()) - sum(
        before["probe"]["compiles"].values()
    )
    failed = sum(1 for r in records if not (r["ok"] or r["cut"]))
    device = {
        "platform": after["probe"]["platform"],
        "kind": after["probe"]["kind"],
        "count": after["probe"]["count"],
        "memory_peak_bytes": after["probe"]["memory_peak_bytes"],
    }
    return {
        "kind": "serve",
        "device": device,
        "correct": bool(
            probe["correct"] and in_range and compiled == 0
            and replay_equal
            and not after["engine"].get("dead")
            and probe["device"]["platform"] == device["platform"]
        ),
        "attempted": len(records),
        "failed": failed,
        "setup_s": window_start_epoch - ctx["started_epoch"],
        "loop": load["loop"],
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
                "before_probe": marks["start"] - ctx["started_epoch"],
                "probe": marks["probe_done"] - marks["start"],
                "cluster_and_deploy": marks["deployed"] - marks["probe_done"],
                "load_and_warm": marks["window"] - marks["deployed"],
            },
            "window": (
                sweep_point(traffic["rate_per_s"], records, seconds)
                if load["loop"] == "open" else None
            ),
            "cut": sum(1 for r in records if r["cut"]),
        },
    }
