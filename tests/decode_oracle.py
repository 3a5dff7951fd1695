"""Decoding oracles the serve-path tests compare against.

`greedy_uncached` is the plain reference: greedy continuation by the
training forward (`llama.forward`), a full forward per token, no
cache of any kind. `serial_streams` runs the paged programs
(`paged_prefill` + `paged_decode_step`) one at a time with the state
on the host, by the engine's policy; `paged_greedy` is its greedy
one-liner for a batch of prompts.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.llama import forward

_forward = jax.jit(forward, static_argnames=("cfg",))


def greedy_uncached(params, cfg, prompt, max_new_tokens, eos=-1, forward=None):
    """Greedy continuation of `prompt` (a list of ids) -> token list,
    ending after `eos` (inclusive) or `max_new_tokens`. Every token
    costs a whole forward over a buffer of the final length: causal
    attention and per-token routing keep what lies past a position out
    of its logits, so one shape (one compile) serves every step.
    `forward(params, tokens [1, t]) -> logits [1, t, vocab]` is another
    plain forward, for a model the training forward does not run."""
    forward = forward or functools.partial(_forward, cfg=cfg)
    n = len(prompt)
    tokens = np.zeros((1, n + max_new_tokens), np.int32)
    tokens[0, :n] = prompt
    out = []
    for pos in range(n, n + max_new_tokens):
        logits = forward(params, jnp.asarray(tokens))
        token = int(jnp.argmax(logits[0, pos - 1]))
        tokens[0, pos] = token
        out.append(token)
        if token == eos:
            break
    return out


def serial_streams(params, cfg, ec, jobs):
    """The plain reference: the engine's policy (FIFO, one prompt
    prefilling at a time, one chunk an iteration and then one decode
    step over the rows alive, `fold_in(base_key, step)` keys) run one
    program at a time with the state on the host. Every chunk here is
    a whole one, the last padded: the engine's own schedule where its
    geometry offers a last chunk no shorter shape (`kv_block_len`
    above half of `prefill_chunk`, as the callers' is), so that
    no chunk leaves room in an iteration's budget for another
    (tests/test_engine_prefill_budget.py holds that schedule). `jobs`
    are (prompt, max_new_tokens, eos), all queued at the start and no
    more of them than slots. -> one token list a job."""
    from ray_tpu.llm.kv_slots import default_block_len
    from ray_tpu.models.generate import (
        init_block_pool, paged_decode_step, paged_prefill,
    )

    assert len(jobs) <= ec.slots
    chunk = ec.prefill_chunk
    bl = ec.kv_block_len or default_block_len(chunk)
    width = ec.max_len // bl
    pool = init_block_pool(cfg, ec.slots * width + 1, bl)
    # slot s owns blocks 1 + s * width ...: which ones is not the
    # mathematics' business.
    tables = 1 + np.arange(ec.slots * width, dtype=np.int32).reshape(
        ec.slots, width
    )
    positions = np.zeros(ec.slots, np.int32)
    alive = np.zeros(ec.slots, bool)
    last_logits = jnp.zeros((ec.slots, cfg.vocab_size), jnp.float32)
    base_key = jax.random.PRNGKey(ec.seed)
    outs = [[] for _ in jobs]
    waiting = list(range(len(jobs)))
    prefilling = None  # (slot, padded prompt, offset)
    step = 0
    while waiting or prefilling or alive.any():
        if prefilling is None and waiting:
            slot = waiting.pop(0)
            prompt = jobs[slot][0]
            padded = np.zeros((1, -(-len(prompt) // chunk) * chunk), np.int32)
            padded[0, : len(prompt)] = prompt
            prefilling = (slot, padded, 0)
        if prefilling:
            slot, padded, offset = prefilling
            logits, pool = paged_prefill(
                params, cfg, jnp.asarray(padded[:, offset:offset + chunk]),
                pool, jnp.asarray(tables[slot:slot + 1]),
                jnp.int32(offset), jnp.int32(offset + chunk),
            )
            prefilling = (slot, padded, offset + chunk)
            if offset + chunk >= padded.shape[1]:
                n = len(jobs[slot][0])
                last_logits = last_logits.at[slot].set(
                    logits[0, n - 1 - offset]
                )
                positions[slot], alive[slot] = n, True
                prefilling = None
        if alive.any():
            token, pool, last_logits = paged_decode_step(
                params, cfg, pool, jnp.asarray(tables), last_logits,
                jnp.asarray(positions), jnp.asarray(alive),
                jax.random.fold_in(base_key, step),
                temperature=ec.temperature, top_k=ec.top_k,
            )
            step += 1
            token = np.asarray(token)
            for slot in np.flatnonzero(alive):
                _, max_new, eos = jobs[slot]
                outs[slot].append(int(token[slot]))
                positions[slot] += 1
                if token[slot] == eos or len(outs[slot]) >= max_new:
                    alive[slot] = False
    return outs


def paged_greedy(params, cfg, prompts, max_new_tokens, prefill_chunk=8):
    """Greedy continuations of `prompts` (rows of ids) through the
    paged programs, the rows decoding side by side -> token lists."""
    from ray_tpu.llm import EngineConfig

    longest = max(len(p) for p in prompts) + max_new_tokens
    ec = EngineConfig(
        slots=len(prompts),
        max_len=-(-longest // prefill_chunk) * prefill_chunk,
        prefill_chunk=prefill_chunk,
    )
    return serial_streams(
        params, cfg, ec,
        [([int(t) for t in p], max_new_tokens, -1) for p in prompts],
    )
