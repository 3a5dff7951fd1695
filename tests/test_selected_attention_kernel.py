"""The chunk's selected-attention kernel (ops/selected_attention.py)
in Pallas interpret mode against the same mathematics in plain
float32: an arbitrary mask of its own a query, a rotary key shared by
all heads, rows that start anywhere, tiles it may skip."""

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.selected_attention import (
    selected_attention, selected_attention_reference,
)


def _inputs(seed, b, h, t, keys, first, length, density, dtype=jnp.float32):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), dtype)

    first, length = np.asarray(first), np.asarray(length)
    pos = first[:, None] + np.arange(t)
    k_pos = np.arange(keys)[None, None, :]
    visible = (k_pos <= pos[:, :, None]) & (k_pos < length[:, None, None])
    chosen = rng.random((b, t, keys)) < density
    chosen |= k_pos == pos[:, :, None]  # a query keeps its own key
    return (
        normal(b, h, t, 128), normal(b, h, t, 128), normal(b, keys, h * 128),
        normal(b, keys, 128), normal(b, keys, h * 128),
        jnp.asarray((visible & chosen).astype(np.int8)),
        jnp.asarray(first, jnp.int32), jnp.asarray(length, jnp.int32),
    )


@pytest.mark.parametrize("first, length, density", [
    ([96, 0], [160, 64], 0.3),     # mid-row chunk; a first chunk
    ([192, 128], [256, 192], 0.05),  # sparse: whole tiles masked for a query
    ([0, 0], [64, 40], 1.0),        # no selection: plain causal attention
], ids=["mixed", "sparse", "dense"])
def test_kernel_is_masked_attention(first, length, density):
    args = _inputs(0, 2, 3, 64, 256, first, length, density)
    got = selected_attention(*args, scale=0.05, block_q=32, block_k=64)
    want = selected_attention_reference(*args[:6], scale=0.05)
    assert got.shape == (2, 3, 64, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_skipped_tiles_hold_nothing_the_mask_allows():
    """Keys past a row's length or after a block's last query are
    never read: garbage there (NaN) does not reach the output."""
    qn, qr, kn, kr, v, mask, first, length = _inputs(
        1, 1, 2, 64, 256, [32], [96], 0.5
    )
    dirty = np.array(kn)
    dirty[:, 128:] = np.nan  # tiles 2, 3 of 64 keys: past the length
    got = selected_attention(
        qn, qr, jnp.asarray(dirty), kr, v, mask, first, length,
        scale=0.05, block_q=32, block_k=64,
    )
    want = selected_attention_reference(qn, qr, kn, kr, v, mask, scale=0.05)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_bfloat16_inputs_accumulate_in_float32():
    args = _inputs(2, 1, 2, 32, 128, [64], [96], 0.4, jnp.bfloat16)
    got = selected_attention(*args, scale=0.08, block_q=32, block_k=64)
    want = selected_attention_reference(*args[:6], scale=0.08)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), atol=0.03
    )


def test_blocks_must_divide():
    args = _inputs(3, 1, 1, 48, 128, [0], [48], 1.0)
    with pytest.raises(ValueError, match="whole blocks"):
        selected_attention(*args, scale=1.0, block_q=32, block_k=64)
