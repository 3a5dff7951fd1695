"""The flash-attention kernels' share of their roofline in a model
whose layers are not all alike: the least time the chip could take for
the operations attention REQUIRES in the traced steps, a window layer
counted by its window (`window_flops.attention_flops_fwd`: forward
QK^T and AV over the keys each query sees, backward twice that), over
the time the kernels took (`flash_kernel_share`'s kernel seconds, by
the kernels' names). Attention at these widths is bound by the matrix
unit, so the roofline is operations over the published bf16 peak. It
cannot pass 100 % while the kernel does the work it must; what a
kernel masks inside a block it visits lowers it. A program whose trace
names no such kernel, or a run with no trace, gives nothing."""

from benchmark.flops import peaks_for
from benchmark.harness import load_module
from benchmark.window_flops import attention_flops_fwd

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
    per_sequence = 3.0 * attention_flops_fwd(run["config"]["model"], seq_len)
    return per_sequence * sequences_per_chip * steps


def reduce(run: dict):
    seconds = load_module(
        "layer_metrics", "flash_kernel_share"
    ).kernel_seconds(run.get("trace"))
    if not seconds:
        return None
    peak = peaks_for(run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * (required_flops(run) / peak) / seconds
