"""The load generator of the serve cells, in a process of its own.

    python3 benchmark/drivers/serve_client.py <spec.json>

The serve driver hosts the head daemon, so a sender thread there would
share an interpreter with it and stamp its stalls into every gap. This
process imports neither JAX nor the program: it makes the load from the
seed (the cell's generator, found by the traffic file's `kind`), says
`ready`, reads the clock's origin from standard input (a
`CLOCK_MONOTONIC` reading, which every process of a machine shares:
the time the window opens), offers, and writes the records as JSON to
`spec["out"]`. Times are seconds after the origin: a request of the
lead-in is due, sent and partly streamed before 0.

Open loop: each request is sent when due and timed from when it was
due. Closed loop: `clients` callers take the next request of one shared
list as soon as their last reply ended, from 0 to the window's edge.
What is in flight at the edge is cut (the connection is closed under
its reader) `DRAIN_S` later in an open loop, which only lets a request
due late deliver its first token, and at once in a closed one. A cut
request is neither failed nor complete.

spec: {"port", "traffic", "seed", "seconds", "vocab_size", "out"}.
"""

from __future__ import annotations

import http.client
import json
import os
import socket
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

ROUTE = "/llm"
REQUEST_TIMEOUT_S = 120.0
DRAIN_S = 5.0
MAX_SENDERS = 128
NEVER = threading.Event()


def monotonic() -> float:
    """The clock this process shares with the driver's (seconds of
    `CLOCK_MONOTONIC`, one origin for every process of a machine)."""
    return time.clock_gettime(time.CLOCK_MONOTONIC)


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
    # `prompt` is the request's own list, kept for the comparison with
    # the reference (`serve_probe.sample`)
    record.update(
        prompt=request["prompt"], n_prompt=len(request["prompt"]),
        want=request["max_new_tokens"], token_s=[], status=0,
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


def offer_open(pool, port: int, requests: list, clock, stop) -> list:
    """Send each request when it is due; returns once the last is on
    its way, with the records the sender threads fill. One due before
    the window opens (the lead-in) carries `lead_in`: it loads the
    engine and is no sample of the window's requests."""
    records = [
        {"due_s": r["due_s"], "lead_in": r["due_s"] < 0.0} for r in requests
    ]
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


def offer(load: dict, port: int, seconds: float, clock) -> list:
    """One window of `load` (what a generator's `generate` returns)
    against `port`, from the lead-in to the cut; the finished records."""
    pool, stop = ThreadPoolExecutor(max_workers=MAX_SENDERS), threading.Event()
    if load["loop"] == "open":
        records = offer_open(pool, port, load["requests"], clock, stop)
        drain = DRAIN_S
    else:
        time.sleep(max(0.0, -clock()))
        records = offer_closed(
            pool, port, load["requests"], load["clients"], seconds, clock,
            stop,
        )
        drain = 0.0
    time.sleep(max(0.0, seconds - clock()))
    finish(pool, records, clock, seconds + drain, stop)
    return records


def main(argv: list) -> int:
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import harness

    with open(argv[1]) as f:
        spec = json.load(f)
    traffic = spec["traffic"]
    load = harness.load_module("traffic", traffic["kind"]).generate(
        traffic, spec["seed"], spec["seconds"], spec["vocab_size"]
    )
    print("ready", flush=True)
    line = sys.stdin.readline()
    if not line.strip():
        return 1  # the driver went away before the window
    origin = float(line)

    def clock() -> float:
        return monotonic() - origin

    records = offer(load, spec["port"], spec["seconds"], clock)
    with open(spec["out"], "w") as f:
        json.dump(records, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
