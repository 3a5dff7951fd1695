"""The flash-attention kernels' share of their roofline: the least time
the chip could take for the operations causal attention requires in
the traced steps, over the time the kernels took. Attention at these
widths is bound by the matrix unit, so the roofline is operations over
the published bf16 peak: forward QK^T and AV over half the square
(`flops.attention_flops_per_token_fwd`), backward twice that, for
every sequence a chip holds in every traced step. What the kernel
recomputes is not required work and counts only in its time."""

from benchmark.flops import attention_flops_per_token_fwd, peaks_for
from benchmark.harness import load_module

LAYER, UNIT, SOURCE = "attention kernel", "%", "device_trace"

#: `drivers/train.py` traces this many steps when the traffic file
#: does not say.
DEFAULT_TRACE_STEPS = 4


def required_flops(run: dict) -> float:
    seq_len = run["seq_len"]
    sequences_per_chip = (
        run["tokens_per_step"] / seq_len / run["cell"]["chips"]
    )
    steps = int(run["traffic"].get("trace_steps", DEFAULT_TRACE_STEPS))
    per_token = 3.0 * attention_flops_per_token_fwd(
        run["config"]["model"], seq_len
    )
    return per_token * seq_len * sequences_per_chip * steps


def reduce(run: dict):
    seconds = load_module(
        "layer_metrics", "flash_kernel_share"
    ).kernel_seconds(run.get("trace"))
    if not seconds:
        return None
    peak = peaks_for(run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * (required_flops(run) / peak) / seconds
