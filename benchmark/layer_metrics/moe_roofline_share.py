"""The experts' grouped matmuls' share of their roofline: the least
time the chip could take for what the expert layers of the window
required, over the time the kernels took.

Required, per second of the window: the picks the engine counted
(prefill and decode, `moe_picks_*` deltas) x `moe_flops.pick_flops`
over the published bf16 peak, or the experts it touched x one
expert's bytes over the published bandwidth, whichever is larger (per
forward and layer the experts that got a token, each once:
`moe_chunk_experts` of the chunks, `moe_experts_touched` of the decode
steps). Taken: the kernels' seconds per
second of the traced part (`moe_kernel_share`'s names).

The two are not the same seconds: the counters cover the whole window
and the trace a few seconds in its middle, during which the profiler
slows serving (PERF.md section 6: by up to 16 %). The share assumes
that the window's mean rate of expert work is the traced part's. While
tracing, the engine does less work a second than the window's mean and
the kernels' seconds a second do not rise, so the share reads high
rather than low by that much; it is a bound to watch, not a number to
claim by. A dense engine, or a run with no trace, gives nothing."""

from benchmark.flops import peaks_for
from benchmark.harness import load_module
from benchmark.moe_flops import required

LAYER, UNIT, SOURCE = "expert kernel", "%", "device_trace"


def required_seconds_per_s(run: dict):
    """The roofline's seconds of expert work per second of the
    window, or None where the engine counted none."""
    engine = run.get("engine")
    model = run["config"]["model"]
    if not engine or not model.get("moe_experts"):
        return None
    before, after = engine["before"], engine["after"]
    if "moe_picks_prefill" not in after:
        return None

    def delta(key):
        return after[key] - before.get(key, 0)

    picks = delta("moe_picks_prefill") + delta("moe_picks_decode")
    touched = delta("moe_chunk_experts") + delta("moe_experts_touched")
    need = required(model, picks, touched, run["config"]["dtype"])
    peaks = peaks_for(run["device"]["kind"])
    return max(
        need["flops"] / peaks["bf16_flops_per_s"],
        need["bytes"] / peaks["hbm_bytes_per_s"],
    ) / run["window_s"]


def reduce(run: dict):
    trace = run.get("trace")
    seconds = load_module(
        "layer_metrics", "moe_kernel_share"
    ).kernel_seconds(trace)
    if not seconds or not trace.get("window_s"):
        return None
    least = required_seconds_per_s(run)
    if not least:
        return None
    return 100.0 * least / (seconds / trace["window_s"])
