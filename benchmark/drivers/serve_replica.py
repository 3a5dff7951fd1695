"""The deployment the serve cells run: `LLMServer` unchanged, plus one
method through which the benchmark reads what only the process that
holds the chip can see (device memory, this process's compile table)
and starts and stops `jax.profiler` there.

The weights it serves are the benchmark's (`reference/weights.py`, one
jitted call from the seed), not the program's `init_params`: a family
of kind "benchmark" is built here, through the one seam the program
has for it, `serving.build_model` (spec -> params, config), which this
module wraps in the replica's process. Which leaves the tree has is
said by the configuration's reference module where it defines `shapes`
(the family carries its name; the module is the benchmark's file, not
the program's). The reference's pass makes the same tree from the same
seed by itself, so neither side of `correct` takes its weights from
the other.

`build_model` also times the two parts of the first request's load
that happen before the engine is built, for the run's notes: the TPU
backend's start (the one part of a serve cell's set-up that is not
steady; the configurations' `runtime_env` says what is done about it)
and the weights."""

from __future__ import annotations

import os
import time

from ray_tpu.llm import serving
from ray_tpu.llm.serving import LLMServer

from ..harness import describe, peak_bytes
from ..reference import compare, weights

_build_model = serving.build_model
#: Seconds of the first request's load and the leaves of the tree it
#: made, for the run's notes.
LOAD_S: dict = {}


def build_model(spec: dict):
    """`serving.build_model`, with one more kind: "benchmark"."""
    if spec.get("kind") != "benchmark":
        return _build_model(spec)
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    model = dict(spec["config"])
    dtype = model.pop("dtype")
    cfg = LlamaConfig(**model, dtype=jnp.dtype(dtype))
    reference = compare.load(spec.get("reference"))
    t0 = time.monotonic()
    jax.devices()
    t1 = time.monotonic()
    params = jax.block_until_ready(
        weights.make(model, dtype, spec["seed"], reference)
    )
    LOAD_S.update(
        backend=t1 - t0, weights=time.monotonic() - t1,
        leaves=len(jax.tree.leaves(params)),
    )
    return params, cfg


serving.build_model = build_model


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
            "compile_ms": {
                name: row["total_ms"]
                for name, row in compile_watch.snapshot().items()
            },
            "load_s": dict(LOAD_S),
        }
