"""The four per-layer readers that read what the program says of
itself (PR 23): the engine loop's phases and admission counters, and
the flash kernels by name in the device trace. Each on a hand-made
`run`, and on the `run` a program without those counters or names
gives (the parent of PR 23): nothing, and no exception."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import flops, harness  # noqa: E402


def read(name, run):
    return harness.load_module("layer_metrics", name).reduce(run)


def serve_run(before, after):
    return {"engine": {"before": before, "after": after}}


LOOP_BEFORE = {
    "engine.idle": 5000.0, "engine.reap": 1.0, "engine.admit": 2.0,
    "engine.prefill.prepare": 1.0, "engine.prefill.dispatch": 3.0,
    "engine.prefill.wait": 70.0, "engine.decode.prepare": 4.0,
    "engine.decode.dispatch": 6.0, "engine.decode.sync": 120.0,
    "engine.emit": 3.0,
}
#: The window adds 1,000 ms outside idle: 100 on the host, 900 waiting
#: for the device (a prefill wait of 300 and a decode sync of 600).
LOOP_AFTER = dict(LOOP_BEFORE, **{
    "engine.idle": 9000.0, "engine.reap": 11.0, "engine.admit": 12.0,
    "engine.prefill.prepare": 11.0, "engine.prefill.dispatch": 23.0,
    "engine.prefill.wait": 370.0, "engine.decode.prepare": 24.0,
    "engine.decode.dispatch": 26.0, "engine.decode.sync": 720.0,
    "engine.emit": 13.0,
})


def test_engine_host_share_is_host_phases_over_busy_phases():
    run = serve_run({"loop_ms": LOOP_BEFORE}, {"loop_ms": LOOP_AFTER})
    assert read("engine_host_share", run) == pytest.approx(10.0)
    # A phase that first shows up inside the window counts from zero.
    after = dict(LOOP_AFTER, **{"engine.policy": 100.0})
    run = serve_run({"loop_ms": LOOP_BEFORE}, {"loop_ms": after})
    assert read("engine_host_share", run) == pytest.approx(100 * 200 / 1100)


def test_engine_admit_wait_is_the_mean_over_the_windows_admissions():
    run = serve_run(
        {"admitted": 10, "admit_wait_ms_total": 500.0},
        {"admitted": 30, "admit_wait_ms_total": 4500.0},
    )
    assert read("engine_admit_wait_mean_ms", run) == pytest.approx(200.0)


@pytest.mark.parametrize("reader", [
    "engine_host_share", "engine_admit_wait_mean_ms",
])
@pytest.mark.parametrize("run", [
    {},
    {"engine": None},
    # the parent's engine: no phases, no admission counters
    serve_run({"steps": 1}, {"steps": 9}),
    # nothing happened in the window
    serve_run(
        {"loop_ms": LOOP_BEFORE, "admitted": 4, "admit_wait_ms_total": 9.0},
        {"loop_ms": dict(LOOP_BEFORE, **{"engine.idle": 9000.0}),
         "admitted": 4, "admit_wait_ms_total": 9.0},
    ),
], ids=["train", "no-engine", "parent", "idle"])
def test_engine_readers_give_nothing_where_there_is_nothing(reader, run):
    assert read(reader, run) is None


def timers_run(before, after):
    return {"engine_timers": {"before": before, "after": after}}


def test_ingress_overhead_is_proxy_time_less_replica_time_over_the_window():
    # 20 requests ended in the window: 6.0 s each at the proxy, 4.5 s
    # each in the replica's handler (PR 25: 1.5 requests/s, at the knee).
    run = timers_run(
        {"serve_http_request_latency_ms": [9000.0, 3.0],
         "serve_request_latency_ms": [8700.0, 3.0]},
        {"serve_http_request_latency_ms": [9000.0 + 20 * 6000.0, 23.0],
         "serve_request_latency_ms": [8700.0 + 20 * 4500.0, 23.0],
         "serve_engine_decode_step_ms": [100.0, 5.0]},
    )
    assert read("ingress_overhead_mean_ms", run) == pytest.approx(1500.0)


@pytest.mark.parametrize("run", [
    {},
    {"engine_timers": None},
    # a program with the engine's timers only
    timers_run({}, {"serve_engine_decode_step_ms": [100.0, 5.0]}),
    # no request ended in the window
    timers_run(
        {"serve_http_request_latency_ms": [50.0, 2.0], "serve_request_latency_ms": [40.0, 2.0]},
        {"serve_http_request_latency_ms": [50.0, 2.0], "serve_request_latency_ms": [40.0, 2.0]},
    ),
], ids=["train", "no-timers", "engine-only", "idle"])
def test_ingress_overhead_gives_nothing_where_there_is_nothing(run):
    assert read("ingress_overhead_mean_ms", run) is None


MODEL = {"dim": 4096, "n_layers": 4, "n_heads": 32, "n_kv_heads": 8,
         "intermediate": 14336, "vocab_size": 32768}


def train_run(device_ops, chips=1, **traffic):
    return {
        "trace": {"busy_s": 2.0, "window_s": 2.0, "device_ops": device_ops},
        "device": {"kind": "TPU v5 lite"}, "cell": {"chips": chips},
        "config": {"model": MODEL}, "traffic": traffic,
        "seq_len": 8192, "tokens_per_step": 8192 * chips,
    }


def test_flash_kernel_share_sums_both_kernels_over_busy_time():
    run = train_run([["fusion", 1.0], ["flash_bwd", 0.3], ["flash_fwd", 0.1]])
    assert read("flash_kernel_share", run) == pytest.approx(20.0)
    only_fwd = train_run([["fusion", 1.0], ["flash_fwd", 0.1]])
    assert read("flash_kernel_share", only_fwd) == pytest.approx(5.0)


@pytest.mark.parametrize("chips", [1, 4])
def test_flash_roofline_share_is_required_time_over_kernel_time(chips):
    # ISSUE 23's own arithmetic: four traced steps of one 8,192-token
    # sequence a chip need 2.64e13 operations, 0.134 s at the peak.
    required = 3 * flops.attention_flops_per_token_fwd(MODEL, 8192) * 8192 * 4
    assert required == pytest.approx(2.64e13, rel=0.01)
    run = train_run(
        [["flash_bwd", 0.25], ["fusion", 1.0], ["flash_fwd", 0.085]], chips
    )
    assert read("flash_roofline_share", run) == pytest.approx(
        100 * (required / 197e12) / 0.335
    )
    assert 39.0 < read("flash_roofline_share", run) < 41.0
    # `trace_steps` comes from the traffic file when it names one.
    two = train_run([["flash_bwd", 0.25], ["flash_fwd", 0.085]], chips,
                    trace_steps=2)
    assert read("flash_roofline_share", two) == pytest.approx(
        read("flash_roofline_share", run) / 2
    )


@pytest.mark.parametrize("reader", ["flash_kernel_share", "flash_roofline_share"])
@pytest.mark.parametrize("run", [
    {"trace": None},  # a rehearsal, or an untraced run
    {"engine": {}},  # a serve run
    # the parent: the kernels hide behind their wrappers' names
    train_run([["fusion", 0.8], ["checkpoint", 0.24], ["closed_call", 0.09]]),
    dict(train_run([["flash_fwd", 0.1]]), trace={"planes": 0}),
], ids=["untraced", "serve", "parent", "empty-trace"])
def test_flash_readers_give_nothing_where_there_is_nothing(reader, run):
    assert read(reader, run) is None


def test_flash_roofline_refuses_a_chip_without_published_peaks():
    run = train_run([["flash_fwd", 0.1]])
    run["device"]["kind"] = "cpu"
    with pytest.raises(flops.UnknownDevice):
        read("flash_roofline_share", run)
