"""The deployment the serve cells run: `LLMServer` unchanged, plus one
method through which the benchmark reads what only the process that
holds the chip can see (device memory, this process's compile table)
and starts and stops `jax.profiler` there."""

from __future__ import annotations

import os

from ray_tpu.llm.serving import LLMServer

from ..harness import describe, peak_bytes


class BenchLLMServer(LLMServer):
    def bench_probe(self, op: str, arg: str = "") -> dict:
        import jax

        from ray_tpu._private import compile_watch

        if op == "trace_start":
            jax.profiler.start_trace(arg)
        elif op == "trace_stop":
            jax.profiler.stop_trace()
        elif op != "snapshot":
            raise ValueError(f"unknown probe op {op!r}")
        devices = jax.local_devices()
        return {
            "pid": os.getpid(),
            **describe(devices),
            "memory_peak_bytes": peak_bytes(devices),
            "compiles": {
                name: row["compiles"]
                for name, row in compile_watch.snapshot().items()
            },
        }
