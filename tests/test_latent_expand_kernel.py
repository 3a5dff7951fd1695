"""The chunk's expansion kernel (ops/latent_expand.py) in Pallas
interpret mode: a row's live key tiles are `entries[..., :latent] @ wk`
and `@ wv`, tiles past them are never written (the interpreter leaves
NaN there, the chip whatever its memory held), and the attention kernel
reads none of them."""

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import generate
from ray_tpu.ops.latent_expand import latent_expand
from ray_tpu.ops.selected_attention import (
    selected_attention, selected_attention_reference,
)

LATENT, ROPE, HEADS, TILE, KEYS = 128, 128, 3, 64, 256

#: two rows of different lengths: under one tile; a tile's edge; several
#: tiles with a ragged last one
CONTEXTS = {"under_a_tile": [40, 17], "edge": [128, 64], "ragged": [200, 70]}


def _normal(rng, *shape, dtype=jnp.float32):
    return jnp.asarray(rng.normal(size=shape), dtype)


def _expanded(seed, lengths, dtype=jnp.float32):
    """-> (entries, wk, wv, live, kn, v): the kernel's results over
    seeded cache entries of two rows."""
    rng = np.random.default_rng(seed)
    entries = _normal(rng, len(lengths), KEYS, LATENT + ROPE, dtype=dtype)
    wk = _normal(rng, LATENT, HEADS * 128, dtype=dtype) * LATENT ** -0.5
    wv = _normal(rng, LATENT, HEADS * 128, dtype=dtype) * LATENT ** -0.5
    live = -(-np.asarray(lengths) // TILE)
    kn, v = latent_expand(
        entries, wk, wv, jnp.asarray(live), block_k=TILE, block_n=128
    )
    return entries, wk, wv, live, kn, v


def _plain(entries, w):
    return np.asarray(entries, np.float32)[..., :LATENT] @ np.asarray(
        w, np.float32
    )


@pytest.mark.parametrize("lengths", CONTEXTS.values(), ids=CONTEXTS.keys())
def test_live_tiles_are_the_plain_matmul_and_no_other_is_written(lengths):
    entries, wk, wv, live, kn, v = _expanded(0, lengths)
    assert kn.shape == v.shape == (2, KEYS, HEADS * 128)
    for got, w in ((kn, wk), (v, wv)):
        got, want = np.asarray(got), _plain(entries, w)
        for row, tiles in enumerate(live):
            made = tiles * TILE
            np.testing.assert_allclose(
                got[row, :made], want[row, :made], atol=1e-5
            )
            # (the interpreter's uninitialised memory is NaN)
            assert np.isnan(got[row, made:]).all()


@pytest.mark.parametrize("lengths", CONTEXTS.values(), ids=CONTEXTS.keys())
def test_attention_reads_no_tile_the_expansion_left_unwritten(lengths):
    """`selected_attention` over the kernel's buffers, their dead tiles
    poisoned, is the reference's over the plain expansion of every
    key."""
    entries, wk, wv, live, kn, v = _expanded(1, lengths)
    kn, v = np.array(kn), np.array(v)
    for row, tiles in enumerate(live):
        kn[row, tiles * TILE:] = v[row, tiles * TILE:] = np.nan
    rng = np.random.default_rng(2)
    t = 32
    lengths = np.asarray(lengths)
    first = np.maximum(lengths - t, 0)  # the row's last chunk
    pos = first[:, None] + np.arange(t)
    k_pos = np.arange(KEYS)[None, None, :]
    mask = (k_pos <= pos[:, :, None]) & (k_pos < lengths[:, None, None])
    mask &= (rng.random(mask.shape) < 0.5) | (k_pos == pos[:, :, None])
    qn, qr = _normal(rng, 2, HEADS, t, 128), _normal(rng, 2, HEADS, t, ROPE)
    kr = entries[..., LATENT:]
    mask = jnp.asarray(mask.astype(np.int8))
    got = selected_attention(
        qn, qr, jnp.asarray(kn), kr, jnp.asarray(v), mask,
        jnp.asarray(first, jnp.int32), jnp.asarray(lengths, jnp.int32),
        scale=0.05, block_q=t, block_k=TILE,
    )
    want = selected_attention_reference(
        qn, qr, jnp.asarray(_plain(entries, wk)), kr,
        jnp.asarray(_plain(entries, wv)), mask, scale=0.05,
    )
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_a_row_with_no_key_still_gets_its_first_tile():
    """The attention kernel reads tile 0 of a dead row (and masks all
    of it): it has to be finite."""
    entries, wk, _, _, kn, v = _expanded(3, [0, 64])
    np.testing.assert_allclose(
        np.asarray(kn)[:, :TILE], _plain(entries, wk)[:, :TILE], atol=1e-5
    )
    assert np.isfinite(np.asarray(v)[:, :TILE]).all()
    assert np.isnan(np.asarray(kn)[:, TILE:]).all()


def test_bfloat16_entries_accumulate_in_float32():
    entries, wk, _, live, kn, _ = _expanded(4, [200, 70], jnp.bfloat16)
    assert kn.dtype == jnp.bfloat16
    made = live[0] * TILE
    want = _plain(entries, wk)[0, :made]
    # one rounding of a float32 sum to bfloat16 (8 bits), no more
    np.testing.assert_allclose(
        np.asarray(kn, np.float32)[0, :made], want,
        atol=2.0 ** -8 * np.abs(want).max(),
    )


def test_whole_tiles_and_whole_lanes_or_an_error():
    rng = np.random.default_rng(5)
    entries, w = _normal(rng, 1, 96, 128), _normal(rng, 128, 128)
    with pytest.raises(ValueError, match="whole tiles"):
        latent_expand(entries, w, w, jnp.asarray([1]), block_k=64)
    with pytest.raises(ValueError, match="whole lanes"):
        latent_expand(
            entries, w[:, :96], w, jnp.asarray([1]), block_k=32
        )


@pytest.mark.parametrize("length", [16, 64, 100])
def test_the_forwards_expansion_gathers_a_rows_table_once(length):
    """`generate._expand_latent` over a pool and a block table: the
    rotary key of EVERY table entry, expanded keys and values of the
    live tiles, a latent narrower than its lanes met by rows of
    zeros."""
    rng = np.random.default_rng(6)
    latent, rope, bl, tile = 32, 16, 8, 32
    pool = _normal(rng, 2, 17, bl, 128)  # [layers, blocks, bl, lanes]
    tables = jnp.asarray(rng.permutation(16)[None, :] + 1, jnp.int32)
    wk, wv = _normal(rng, latent, 256), _normal(rng, latent, 128)
    kn, kr, v = generate._expand_latent(
        pool, 1, tables, jnp.asarray([length]), wk, wv, tile
    )
    entries = np.asarray(pool)[1][np.asarray(tables)].reshape(1, 128, 128)
    made = -(-length // tile) * tile
    np.testing.assert_array_equal(np.asarray(kr), entries[..., latent:])
    assert kr.shape[-1] >= rope
    for got, w in ((kn, wk), (v, wv)):
        np.testing.assert_allclose(
            np.asarray(got)[:, :made],
            entries[:, :made, :latent] @ np.asarray(w), atol=1e-5,
        )
