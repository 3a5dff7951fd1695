"""What the engine loop says about itself (ISSUE 23): `engine.*` phases
that partition the loop's wall time and show up in a `jax.profiler`
trace, exact admission counters, compile counts under the names the
compile watch credits, and one `engine.request` span per request that
travels through the metrics flusher, never from the loop's thread."""

import glob
import http.client
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu._private import compile_watch, profiling, step_telemetry
from ray_tpu.util import tracing

LOOP_PHASES = {
    "engine.idle", "engine.reap", "engine.admit",
    "engine.prefill.prepare", "engine.prefill.dispatch",
    "engine.prefill.wait", "engine.decode.prepare",
    "engine.decode.dispatch", "engine.decode.sync", "engine.emit",
}
TINY = {
    "vocab_size": 128, "dim": 64, "n_layers": 2, "n_heads": 4,
    "n_kv_heads": 2, "intermediate": 128, "max_seq_len": 128,
}


@pytest.fixture(scope="module")
def tiny_model():
    from ray_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig(**TINY, dtype=jnp.float32, attention="reference")
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture
def engine(tiny_model):
    from ray_tpu.llm import EngineConfig, InferenceEngine

    cfg, params = tiny_model
    eng = InferenceEngine(
        params, cfg,
        # A short idle park: a phase still open when stats() is read
        # is not in the totals yet, so it bounds the partition's error.
        EngineConfig(
            slots=2, max_len=64, prefill_chunk=8, max_new_tokens=8,
            idle_wait_s=0.002,
        ),
        family="tiny",
    )
    yield eng
    eng.close()


def run_requests(eng, n=6, max_new_tokens=8):
    rng = np.random.default_rng(3)
    streams = [
        eng.submit(
            rng.integers(1, 128, size=5 + 3 * i).tolist(),
            max_new_tokens=max_new_tokens,
        )
        for i in range(n)
    ]
    return [list(s) for s in streams]


def host_events(trace_dir):
    """Names of the host-plane events in the newest profile under
    `trace_dir`."""
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"
    )))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    return {
        event.name
        for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines for event in line.events
    }


# -- phases -----------------------------------------------------------

def test_phases_partition_the_loops_wall_time(engine):
    run_requests(engine, n=2)  # compiles both programs
    time.sleep(0.05)
    before, t0 = engine.stats(), time.monotonic()
    deadline = t0 + 0.8
    while time.monotonic() < deadline:
        run_requests(engine, n=4)
    time.sleep(0.05)  # an idle engine: only short parks are open
    after, wall_ms = engine.stats(), (time.monotonic() - t0) * 1e3
    assert set(after["loop_ms"]) == LOOP_PHASES
    grown = {
        phase: ms - before["loop_ms"].get(phase, 0.0)
        for phase, ms in after["loop_ms"].items()
    }
    assert all(ms >= 0.0 for ms in grown.values())  # it only grows
    assert after["loop_iterations"] > before["loop_iterations"]
    assert abs(sum(grown.values()) - wall_ms) <= 0.02 * wall_ms, (
        sum(grown.values()), wall_ms, grown,
    )
    # Work was done in every phase the LLM path has.
    assert all(grown[p] > 0.0 for p in LOOP_PHASES)
    # The two phases in which the loop waits for the device keep their
    # names with the new order (the blocking fetch of a retired step's
    # tokens, the wait for a retired chunk): they are what the
    # benchmark's `engine_host_share` leaves out of the host's side.
    from benchmark.layer_metrics.engine_host_share import on_device

    assert {p for p in LOOP_PHASES if on_device(p)} == {
        "engine.prefill.wait", "engine.decode.sync",
    }
    # And the loop ran ahead: nearly every program was dispatched with
    # an earlier one in flight.
    programs = after["programs"] - before["programs"]
    ahead = after["programs_ahead"] - before["programs_ahead"]
    assert programs > 0 and ahead > 0.5 * programs


def test_admission_counters_are_exact(engine):
    before = engine.stats()
    run_requests(engine, n=5)
    after = engine.stats()
    assert after["admitted"] - before["admitted"] == 5
    waited = after["admit_wait_ms_total"] - before["admit_wait_ms_total"]
    # Five requests for two slots: the later ones waited for a slot.
    assert waited > 0.0
    assert waited < 5 * 60e3


def test_policy_engine_bills_its_batches_to_a_phase():
    from ray_tpu.llm import BatchProgram, EngineConfig, InferenceEngine

    class Doubler(BatchProgram):
        buckets = (4,)

        def run(self, params, inputs, key):
            return {"out": np.asarray(inputs) * 2}

    eng = InferenceEngine(
        {}, None, EngineConfig(idle_wait_s=0.002), program=Doubler()
    )
    try:
        out = eng.submit_policy(np.ones((2, 3))).result(timeout=30)
        assert out["out"].tolist() == [[2.0] * 3] * 2
        time.sleep(0.02)
        stats = eng.stats()
        assert stats["loop_ms"]["engine.policy"] > 0.0
        assert "compiles" not in stats  # no LLM programs to count
    finally:
        eng.close()


#: What `stats()["compiles"]` calls the loop's four programs, and the
#: names the compile watch knows them by.
ENGINE_PROGRAMS = {
    "prefill": "generate.paged_prefill",
    "decode": "generate.paged_engine_step",
    "patch": "generate.patch_step_slot",
    "finish_chunk": "generate.finish_chunk",
}


def test_compile_counts_are_what_the_watch_credits(engine):
    run_requests(engine, n=2)  # warm-up: every program has compiled
    compiles = engine.stats()["compiles"]
    snapshot = compile_watch.snapshot()
    assert set(compiles) == set(ENGINE_PROGRAMS)
    for kind, name in ENGINE_PROGRAMS.items():
        assert compiles[kind]["compiles"] >= 1, kind
        assert compiles[kind]["compiles"] == snapshot[name]["compiles"]
    # One wrapper a program: nothing is registered under a second name.
    assert not [name for name in snapshot if name.startswith("engine.")]
    before = sum(row["compiles"] for row in snapshot.values())
    run_requests(engine, n=3)
    # A cancellation's patch is the admission's program: any request
    # at all has warmed it up.
    stream = engine.submit([5, 4, 3, 2, 1], max_new_tokens=40)
    next(stream)
    stream.cancel()
    list(stream)
    # Two patches a request, and the cancellation's, which the loop
    # dispatches right after it ends the stream.
    deadline = time.monotonic() + 10
    while (
        engine.stats()["state_patches"] < 2 * 6 + 1
        and time.monotonic() < deadline
    ):
        time.sleep(0.01)
    assert engine.stats()["state_patches"] == 2 * 6 + 1
    assert engine.stats()["compiles"] == compiles  # steady state
    assert sum(
        row["compiles"] for row in compile_watch.snapshot().values()
    ) == before  # and no eager operation compiled beside them


# -- the profiler's trace ---------------------------------------------

def test_profiler_trace_holds_engine_and_input_phases(engine, tmp_path):
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train.train_step import prefetch_to_device

    run_requests(engine, n=2)
    mesh = MeshSpec(fsdp=1).build(jax.devices()[:1])
    jax.profiler.start_trace(str(tmp_path))
    try:
        run_requests(engine, n=3)
        batches = prefetch_to_device(
            iter([{"x": np.zeros((2, 4), np.float32)}] * 3), mesh,
            logical_axes=("batch", None),
        )
        assert len(list(batches)) == 3
    finally:
        jax.profiler.stop_trace()
    step_telemetry.take_phases()
    names = host_events(str(tmp_path))
    assert {"engine.decode.sync", "engine.admit"} <= names
    assert {"data_wait_ms", "h2d_ms"} <= names


def test_capture_gang_traces_on_the_cpu_backend(engine):
    run_requests(engine, n=2)
    done = threading.Event()

    def traffic():
        while not done.is_set():
            run_requests(engine, n=2)

    thread = threading.Thread(target=traffic, daemon=True)
    thread.start()
    try:
        result = profiling.capture_gang(duration_s=0.3, hz=50.0)
    finally:
        done.set()
        thread.join(timeout=60)
    assert not thread.is_alive()
    assert result["samples"] > 0  # the sampler still runs alongside
    names = host_events(result["jax_trace_dir"])
    assert {"engine.decode.dispatch", "engine.emit"} <= names
    assert 0.0 <= result["jax_trace_stop_s"] < 30.0


def test_phase_timer_needs_no_jax_and_annotates_only_the_outermost():
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from ray_tpu._private import step_telemetry as st\n"
        "with st.phase_timer('a_ms'):\n"
        "    with st.phase_timer('a_ms') as inner:\n"
        "        pass\n"
        "assert inner._annotation is None\n"
        "assert set(st.take_phases()) == {'a_ms'}\n"
        "assert 'jax' not in sys.modules\n"
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)
    with step_telemetry.phase_timer("b_ms") as outer:
        with step_telemetry.phase_timer("b_ms") as inner:
            pass
    assert outer._annotation is not None and inner._annotation is None
    assert set(step_telemetry.take_phases()) >= {"b_ms"}


# -- spans ------------------------------------------------------------

def head_spans(rt, name, want, timeout_s):
    deadline = time.monotonic() + timeout_s
    while True:
        spans = [
            s for s in rt.api._session.worker.call(
                "list_spans", limit=10000
            )["spans"] if s["name"] == name
        ]
        if len(spans) >= want or time.monotonic() > deadline:
            return spans
        time.sleep(0.05)


def test_request_spans_ride_the_flusher_not_the_loop(
    rt_session, tiny_model, monkeypatch
):
    from ray_tpu._private.worker import global_worker
    from ray_tpu.llm import EngineConfig, InferenceEngine
    from ray_tpu.util.metrics import _FLUSH_INTERVAL_S

    senders = []
    client = global_worker()._client
    notify = client.notify

    def spy(method, **kwargs):
        if method == "span_event":
            senders.append(
                (threading.current_thread().name, len(kwargs["spans"]))
            )
        return notify(method, **kwargs)

    monkeypatch.setattr(client, "notify", spy)
    cfg, params = tiny_model
    eng = InferenceEngine(
        params, cfg,
        EngineConfig(slots=2, max_len=64, prefill_chunk=8),
        family="tiny",
    )
    try:
        with tracing.span("caller") as caller:
            outs = run_requests(eng, n=4, max_new_tokens=4)
        finished = time.monotonic()
        spans = head_spans(
            rt_session, "engine.request", 4, 2 * _FLUSH_INTERVAL_S + 0.5
        )
        arrived = time.monotonic() - finished
    finally:
        eng.close()
    assert [len(o) for o in outs] == [4] * 4
    assert len(spans) == 4
    assert arrived <= 2 * _FLUSH_INTERVAL_S + 0.5
    assert senders and not [
        name for name, _ in senders if name.startswith("llm-engine")
    ]
    # Batched: fewer sends than spans (four requests and the caller's).
    assert len(senders) < sum(n for _, n in senders)
    for span in spans:
        attrs = span["attributes"]
        assert span["trace_id"] == caller.trace_id
        assert span["parent_span_id"] == caller.span_id
        assert attrs["tokens"] == "4" and attrs["finish_reason"] == "length"
        assert float(attrs["queue_ms"]) >= 0.0
        assert float(attrs["prefill_ms"]) > 0.0
        assert float(attrs["decode_ms"]) > 0.0
        assert span["end_ns"] > span["start_ns"]


@pytest.mark.timeout(240)
def test_engine_request_shares_its_trace_with_the_http_request(rt_session):
    import ray_tpu.serve as serve
    from ray_tpu.llm import build_llm_app

    family = {"kind": "init", "seed": 0, "config": dict(TINY, dtype="float32")}
    try:
        serve.run(
            build_llm_app(
                {"tiny": family},
                engine={"slots": 2, "max_len": 64, "prefill_chunk": 8},
            ),
            name="llm-trace", route_prefix="/llm",
        )
        port = serve.start(http_port=0)
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        conn.request(
            "POST", "/llm",
            body=json.dumps({"prompt": [3, 1, 4, 1, 5], "max_new_tokens": 3}),
            headers={
                "Content-Type": "application/json",
                "x-request-id": "trace-me",
            },
        )
        resp = conn.getresponse()
        body = resp.read()
        conn.close()
        assert resp.status == 200 and len(body.split()) == 3
        by_name = {}
        for name in ("serve.http", "serve.handle", "engine.request"):
            spans = [
                s for s in head_spans(rt_session, name, 1, 10.0)
                if s["attributes"].get("request_id") == "trace-me"
            ]
            assert len(spans) == 1, (name, spans)
            by_name[name] = spans[0]
    finally:
        serve.shutdown()
    http_span, handle, request = (
        by_name["serve.http"], by_name["serve.handle"],
        by_name["engine.request"],
    )
    assert handle["trace_id"] == http_span["trace_id"]
    assert request["trace_id"] == http_span["trace_id"]
    assert request["parent_span_id"] == handle["span_id"]
    assert request["attributes"]["tokens"] == "3"
    # The engine's span lies inside the handler's.
    assert handle["start_ns"] <= request["start_ns"]
    assert request["end_ns"] <= handle["end_ns"]
