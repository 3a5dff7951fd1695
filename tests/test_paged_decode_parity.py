"""The serve programs against the uncached forward, position by
position: `paged_prefill` of a prompt and then three
`paged_decode_step`s give the logits `llama.forward`, which keeps no
cache, gives over the same tokens. One parametrised test over the
block variants a configuration can switch on."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.generate import (
    init_block_pool, paged_decode_step, paged_prefill,
)
from ray_tpu.models.llama import LlamaConfig, forward, init_params

BL, CHUNK, WIDTH, VOCAB, STEPS = 8, 16, 4, 128, 3

VARIANTS = {
    "dense_gqa_qkv_bias": dict(n_kv_heads=2, attn_bias=True),
    "qk_norm_head": dict(n_kv_heads=4, qk_norm="head"),
    "moe_top_k": dict(
        n_kv_heads=4, moe_experts=8, moe_top_k=2, moe_router="softmax"
    ),
}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_prefill_then_decode_steps_match_the_uncached_forward(variant):
    cfg = LlamaConfig(
        vocab_size=VOCAB, dim=64, n_layers=2, n_heads=4,
        intermediate=128, max_seq_len=64, dtype=jnp.float32,
        attention="reference", **VARIANTS[variant],
    )
    params = init_params(jax.random.PRNGKey(1), cfg)
    if cfg.attn_bias:
        # `init_params` leaves biases at zero; a zero bias proves
        # nothing.
        keys = jax.random.split(jax.random.PRNGKey(2), 3)
        for key, name in zip(keys, ("bq", "bk", "bv")):
            params["layers"][name] = 0.5 * jax.random.normal(
                key, params["layers"][name].shape, cfg.dtype
            )
    rng = np.random.default_rng(4)
    lengths = [10, 13]  # neither a chunk's nor a block's multiple
    rows = len(lengths)
    prompts = rng.integers(1, VOCAB, size=(rows, CHUNK)).astype(np.int32)
    tables = 1 + np.arange(rows * WIDTH, dtype=np.int32).reshape(rows, WIDTH)
    pool = init_block_pool(cfg, rows * WIDTH + 1, BL)

    # What the programs emit, by position, and the tokens they read.
    got = [[] for _ in lengths]
    seqs = [prompts[row, :n].tolist() for row, n in enumerate(lengths)]
    last_logits = jnp.zeros((rows, VOCAB), jnp.float32)
    for row, n in enumerate(lengths):
        logits, pool = paged_prefill(
            params, cfg, jnp.asarray(prompts[row:row + 1]), pool,
            jnp.asarray(tables[row:row + 1]), jnp.int32(0), jnp.int32(CHUNK),
        )
        got[row].extend(np.asarray(logits[0, :n]))
        last_logits = last_logits.at[row].set(logits[0, n - 1])
    positions = np.asarray(lengths, np.int32)
    for _ in range(STEPS):
        token, pool, last_logits = paged_decode_step(
            params, cfg, pool, jnp.asarray(tables), last_logits,
            jnp.asarray(positions), jnp.ones(rows, bool),
            jax.random.PRNGKey(0),
        )
        for row in range(rows):
            seqs[row].append(int(token[row]))
            got[row].append(np.asarray(last_logits[row]))
        positions = positions + 1

    for row, seq in enumerate(seqs):
        want = np.asarray(forward(params, jnp.asarray([seq]), cfg))[0]
        # Greedy: each step fed the argmax of the logits before it.
        assert seq[lengths[row]:] == want[
            lengths[row] - 1:-1
        ].argmax(-1).tolist()
        np.testing.assert_allclose(
            np.stack(got[row]), want, rtol=2e-4, atol=2e-4
        )
