"""A tiny OLMoE (projection-wide q/k norm, top-k of gated experts,
gates not renormalised) with seeded weights: the training forward, and
`paged_prefill` then `paged_decode_step` through a pool, against the
benchmark's plain reference `benchmark/reference/olmoe_ref.py`."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import control_experts  # noqa: E402
from benchmark.reference import compare, olmoe_ref  # noqa: E402
from ray_tpu.models import generate as g  # noqa: E402
from ray_tpu.models.llama import LlamaConfig, forward, init_params  # noqa: E402

MODEL = dict(
    vocab_size=211, dim=64, n_layers=3, n_heads=4, n_kv_heads=4,
    intermediate=32, rope_theta=10000.0, max_seq_len=128, norm_eps=1e-5,
    moe_experts=8, moe_top_k=2, moe_router="softmax", qk_norm="proj",
)
BL, CHUNK, SLOTS = 8, 32, 4
WIDTH = MODEL["max_seq_len"] // BL
#: float32: the program and the reference differ by summation order.
F32_LIMIT = 1e-4
#: bfloat16 runs at 32 experts top-8 (dim 128), the seeded router
#: scaled by 4. Routing is a discrete choice: a token whose 8th and 9th
#: router probabilities lie closer than bf16's rounding of `h` meets
#: another expert in the program than in the reference, and at 8
#: experts top-2 with a flat seeded router one such token moves the
#: logits by more than int8 weights do (program 0.009-0.32 over seeds
#: 0-5, control 0.018-0.13: no limit separates them). With 8 of 32 and
#: a router as decided as a trained one, the program reads
#: 0.0155-0.0211 over seeds 0-5 and the int8 control (the float32
#: reference with every matmul weight, experts and router included,
#: rounded to 8 bits) 0.0356-0.0534. The limit is between them; the
#: benchmark's own is set the same way, on the chip at the published
#: widths.
BF16_MODEL = dict(MODEL, dim=128, moe_experts=32, moe_top_k=8)
BF16_ROUTER_SCALE = 4.0
BF16_LIMIT = 0.028


def _build(dtype, seed, model=MODEL, router_scale=1.0):
    cfg = LlamaConfig(**model, dtype=dtype, attention="reference")
    params = init_params(jax.random.PRNGKey(seed), cfg)
    params["layers"]["router"] = params["layers"]["router"] * router_scale
    # the norms are ones at init: make their placement count
    for name in ("q_norm", "k_norm"):
        shape = params["layers"][name].shape
        params["layers"][name] = (
            1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(seed + 7), shape)
        ).astype(dtype)
    return cfg, params


def _serve_errors(cfg, params, lengths, seed, model=MODEL):
    """The probe's walk (`benchmark/drivers/serve_probe.py`) at tiny
    sizes: each prompt prefilled into its pages, one decode step over
    all slots (two of them dead), each against the reference's full
    forward. -> (errors, the decode step's `moe_counts`)."""
    rng = np.random.default_rng(seed)
    pool = g.init_block_pool(cfg, SLOTS * WIDTH + 1, BL)
    tables = np.zeros((SLOTS, WIDTH), np.int32)
    positions = np.zeros(SLOTS, np.int32)
    alive = np.zeros(SLOTS, bool)
    last = jnp.zeros((SLOTS, cfg.vocab_size), jnp.float32)
    prompts, prefill_logits, next_block = [], [], 1
    for row, n in enumerate(lengths):
        prompt = rng.integers(1, cfg.vocab_size, size=n)
        need = -(-(n + 1) // BL)
        tables[row, :need] = np.arange(next_block, next_block + need)
        next_block += need
        padded = np.zeros((1, CHUNK), np.int32)
        padded[0, :n] = prompt
        logits, pool = g.paged_prefill(
            params, cfg, jnp.asarray(padded), pool,
            jnp.asarray(tables[row:row + 1]), jnp.int32(0), jnp.int32(CHUNK),
        )
        assert int(pool["moe_counts"].sum()) == (
            CHUNK * cfg.moe_top_k * cfg.n_layers
        )
        prompts.append(prompt)
        prefill_logits.append(logits[0, :n])
        last = last.at[row].set(logits[0, n - 1])
        positions[row], alive[row] = n, True
    token, pool, decode_logits = g.paged_decode_step(
        params, cfg, pool, jnp.asarray(tables), last, jnp.asarray(positions),
        jnp.asarray(alive), jax.random.PRNGKey(0), temperature=0.0, top_k=0,
    )
    token = np.asarray(token)
    errors = []
    for row, prompt in enumerate(prompts):
        n = len(prompt)
        seq = np.concatenate([prompt, token[row:row + 1]]).astype(np.int32)
        want = olmoe_ref.forward(params, jnp.asarray(seq), model, q_block=16)
        errors.append(compare.relative_rms_error(prefill_logits[row], want[:n]))
        errors.append(compare.relative_rms_error(decode_logits[row], want[n]))
    return errors, np.asarray(pool["moe_counts"])


def test_float32_training_forward_and_serve_forwards_match_the_reference():
    cfg, params = _build(jnp.float32, 0)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (40,), 0, 211)
    want = olmoe_ref.forward(params, tokens, MODEL, q_block=16)
    got = forward(params, tokens[None], cfg)[0]
    assert compare.relative_rms_error(got, want) < F32_LIMIT
    errors, counts = _serve_errors(cfg, params, [24, 13], seed=2)
    assert max(errors) < F32_LIMIT, errors
    # the decode step's two dead slots pick no expert
    assert counts.shape == (3, 8)
    assert counts.sum(axis=1).tolist() == [2 * 2] * 3


def test_the_reference_reads_its_routing_from_the_models_keys():
    cfg, params = _build(jnp.float32, 3)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (24,), 0, 211)
    raw = olmoe_ref.forward(params, tokens, MODEL, q_block=16)
    renorm = dict(MODEL, moe_router="softmax_renorm")
    other = olmoe_ref.forward(params, tokens, renorm, q_block=16)
    assert compare.relative_rms_error(other, raw) > 1e-2
    got = forward(
        params, tokens[None],
        LlamaConfig(**renorm, dtype=jnp.float32, attention="reference"),
    )[0]
    assert compare.relative_rms_error(got, other) < F32_LIMIT


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bfloat16_passes_a_limit_the_int8_control_fails(seed):
    cfg, params = _build(jnp.bfloat16, seed, BF16_MODEL, BF16_ROUTER_SCALE)
    errors, _ = _serve_errors(cfg, params, [24, 13], seed, BF16_MODEL)
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (40,), 0, 211)
    want = olmoe_ref.forward(params, tokens, BF16_MODEL, q_block=16)
    errors.append(
        compare.relative_rms_error(forward(params, tokens[None], cfg)[0], want)
    )
    assert max(errors) < BF16_LIMIT, errors
    quantized = control_experts.int8_weights(jax.tree.map(jnp.copy, params))
    assert not bool(jnp.all(
        quantized["layers"]["w_gate"] == params["layers"]["w_gate"]
    ))
    control = compare.relative_rms_error(
        olmoe_ref.forward(quantized, tokens, BF16_MODEL, q_block=16), want
    )
    assert BF16_LIMIT < control < 0.2, control
