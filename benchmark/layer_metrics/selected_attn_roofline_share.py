"""The selected-attention kernel's share of its roofline: the least
time the chip could take for the (query, key) pairs the window's
prefill chunks ATTENDED, over the time the kernel took.

Required, per second of the window: the pairs the engine counted in
chunks after the selection (`dsa_chunk_keys_selected`, the program's
own count, a layer at a time) x `mla_flops.pair_flops` over the
published bf16 peak (the operations bound it: `mla_flops.py`). Taken:
the kernel's seconds per second of the traced part
(`selected_attn_kernel_share`'s names). The kernel runs dense over the
live tiles and masks what the selection left out, so this reads at
most the share of visible pairs that were selected, however well the
kernel runs: what is under that is the kernel's, what is over it is
the price of the mask. As `moe_roofline_share`, counters cover the
window and the trace a few seconds of it: a bound to watch. An engine
that counts no selection, or a run with no trace, gives nothing."""

from benchmark.flops import peaks_for
from benchmark.harness import load_module
from benchmark.mla_flops import required

LAYER, UNIT, SOURCE = "attention kernel", "%", "device_trace"


def required_seconds_per_s(run: dict):
    engine = run.get("engine")
    if not engine:
        return None
    before, after = engine["before"], engine["after"]
    if "dsa_chunk_keys_selected" not in after:
        return None
    pairs = after["dsa_chunk_keys_selected"] - before.get(
        "dsa_chunk_keys_selected", 0
    )
    if pairs <= 0:
        return None
    need = required(run["config"]["model"], pairs)
    peaks = peaks_for(run["device"]["kind"])
    return need["flops"] / peaks["bf16_flops_per_s"] / run["window_s"]


def reduce(run: dict):
    trace = run.get("trace")
    seconds = load_module(
        "layer_metrics", "selected_attn_kernel_share"
    ).kernel_seconds(trace)
    if not seconds or not trace.get("window_s"):
        return None
    least = required_seconds_per_s(run)
    if not least:
        return None
    return 100.0 * least / (seconds / trace["window_s"])
