"""Model families. Flagship: Llama (BASELINE.md north star).

Serving-side decode lives in the `generate` submodule: the jitted
paged-KV programs the continuous-batching engine (ray_tpu/llm)
drives. Import them from the submodule."""

from .llama import (
    LlamaConfig,
    flops_per_token,
    forward,
    init_params,
    loss_fn,
    param_annotations,
)

__all__ = [
    "LlamaConfig",
    "forward",
    "loss_fn",
    "init_params",
    "param_annotations",
    "flops_per_token",
]
