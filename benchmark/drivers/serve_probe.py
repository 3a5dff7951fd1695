"""Correctness of the serve path at the cell's own geometry, in a
short process that holds the chip and exits before `serve.run`.

`paged_prefill` then `paged_decode_step`, through a block pool and
tables of the engine's shapes (so both programs are the ones the
replica will load from the compile cache), against the plain
reference's full forward pass. Logits are compared, never tokens: with
random weights the largest logit changes on rounding.

    python -m benchmark.drivers.serve_probe '<json spec>'

prints one JSON line: device, errors, `correct`.
"""

from __future__ import annotations

import json
import sys


def pool_geometry(engine: dict) -> tuple:
    """(block length, table width, blocks in the pool) as
    `InferenceEngine` derives them from its config."""
    from ray_tpu.llm.kv_slots import default_block_len

    block = engine["kv_block_len"] or default_block_len(engine["prefill_chunk"])
    width = engine["max_len"] // block
    return block, width, engine["kv_blocks"] or engine["slots"] * width + 1


def main() -> int:
    spec = json.loads(sys.argv[1])
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu._private.compile_cache import ensure_compile_cache
    from ray_tpu.models.generate import (
        init_block_pool, paged_decode_step, paged_prefill,
    )
    from ray_tpu.models.llama import LlamaConfig, init_params

    from benchmark.harness import describe
    from benchmark.reference import compare

    ensure_compile_cache()
    device = describe(jax.devices())
    if not spec["rehearse"] and device["platform"] != "tpu":
        print(f"no TPU: JAX reports {device}", file=sys.stderr)
        return 1
    if device["count"] < spec["chips"]:
        print(f"needs {spec['chips']} chip(s): {device}", file=sys.stderr)
        return 1

    model, engine = spec["model"], spec["engine"]
    cfg = LlamaConfig(**model, dtype=jnp.dtype(spec["dtype"]))
    params = jax.jit(lambda k: init_params(k, cfg))(
        jax.random.PRNGKey(spec["seed"])
    )
    block, width, n_blocks = pool_geometry(engine)
    pool = init_block_pool(cfg, n_blocks, block)
    chunk, slots = engine["prefill_chunk"], engine["slots"]

    rng = np.random.default_rng([spec["seed"], 0x9E0B])
    tables = np.zeros((slots, width), np.int32)
    positions = np.zeros(slots, np.int32)
    alive = np.zeros(slots, bool)
    last_logits = jnp.zeros((slots, cfg.vocab_size), jnp.float32)
    prompts, prefill_logits = [], []
    next_block = 1
    for row, n in enumerate(spec["probe_lengths"]):
        prompt = rng.integers(1, cfg.vocab_size, size=n)
        need = -(-(n + 1) // block)
        tables[row, :need] = np.arange(next_block, next_block + need)
        next_block += need
        padded = np.zeros((1, chunk), np.int32)
        padded[0, :n] = prompt
        logits, pool = paged_prefill(
            params, cfg, jnp.asarray(padded), pool,
            jnp.asarray(tables[row:row + 1]), jnp.int32(0), jnp.int32(chunk),
        )
        prompts.append(prompt)
        prefill_logits.append(logits[0, :n])
        last_logits = last_logits.at[row].set(logits[0, n - 1])
        positions[row], alive[row] = n, True
    token, pool, decode_logits = paged_decode_step(
        params, cfg, pool, jnp.asarray(tables), last_logits,
        jnp.asarray(positions), jnp.asarray(alive), jax.random.PRNGKey(0),
        temperature=0.0, top_k=0,
    )
    token = np.asarray(token)
    del pool, last_logits

    reference = compare.load(spec.get("reference"))
    pad_to = max(spec["probe_lengths"]) + 1
    errors = []
    for row, prompt in enumerate(prompts):
        n = len(prompt)
        seq = np.zeros(pad_to, np.int32)
        seq[:n], seq[n] = prompt, token[row]
        want = reference.forward(params, jnp.asarray(seq), model)
        errors.append({
            "tokens": n,
            "prefill": compare.relative_rms_error(
                prefill_logits[row], want[:n]
            ),
            "decode": compare.relative_rms_error(
                decode_logits[row], want[n]
            ),
        })
    worst = max(max(e["prefill"], e["decode"]) for e in errors)
    print(json.dumps({
        "device": device,
        "reference": reference.__name__,
        "errors": errors,
        "worst": worst,
        "correct": bool(worst <= spec["tolerance"]["logits_rel_rms"]),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
