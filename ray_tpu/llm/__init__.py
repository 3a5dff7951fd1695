"""LLM inference serving: continuous-batching engine + serve glue.

The engine (engine.py) owns a PAGED KV cache — a refcounted block
pool with prefix reuse (kv_slots.py) — fed by a FIFO slot scheduler
gated on block availability (scheduler.py); serving.py wires it
behind `ray_tpu.serve` as a multiplexed streaming deployment, which
`benchmark/run.py` drives with open- and closed-loop traffic on the
chip (BENCHMARK.json's serve cells; readings in PERF.md).
"""

from .engine import (
    BatchProgram,
    EngineConfig,
    EngineDead,
    EngineOverloaded,
    InferenceEngine,
    PolicyTicket,
    TokenStream,
)
from .scheduler import SlotScheduler
from .kv_slots import BlockAllocator, BlocksExhausted, PagedKVCache
from .serving import LLMServer, build_llm_app

__all__ = [
    "BatchProgram",
    "EngineConfig",
    "EngineDead",
    "EngineOverloaded",
    "InferenceEngine",
    "PolicyTicket",
    "TokenStream",
    "SlotScheduler",
    "BlockAllocator",
    "BlocksExhausted",
    "PagedKVCache",
    "LLMServer",
    "build_llm_app",
]
