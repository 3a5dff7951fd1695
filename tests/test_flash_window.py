"""A window in the flash kernels (`flash_attention(window=)`): the
kernels interpreted (`force_pallas=True`), forward and gradients
against `mha_reference(window=)`, the ground truth. Sizes are small:
the interpreter is slow. Blocks of 16 under sequences of 48-64, so a
window under, equal to and over a block, one that cuts a block in the
middle, blocks that are not square, and a sequence that is no block
multiple (padding and window together) all occur."""

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops.attention import (
    _block_needs_mask, _block_runs, _first_key_block, flash_attention,
    mha_reference,
)

HEADS, DIM = 2, 32


def _qkv(t, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return [
        jax.random.normal(k, (1, HEADS, t, DIM), jnp.float32) for k in keys
    ]


def _flash(q, k, v, window, block_q=16, block_k=16):
    return flash_attention(
        q, k, v, causal=True, window=window, block_q=block_q,
        block_k=block_k, force_pallas=True,
    )


#: (sequence, window, block_q, block_k)
CASES = [
    (64, 8, 16, 16),    # under a block
    (64, 16, 16, 16),   # equal to a block
    (64, 24, 16, 16),   # over a block, cutting the next in the middle
    (64, 33, 32, 16),   # blocks that are not square
    (64, 40, 16, 32),
    (60, 24, 16, 16),   # no block multiple: padding and window
    (50, 7, 16, 16),
    (64, 1, 16, 16),    # the query's own position alone
]


@pytest.mark.parametrize("t, window, block_q, block_k", CASES)
def test_forward_matches_the_reference(t, window, block_q, block_k):
    q, k, v, _ = _qkv(t)
    got = _flash(q, k, v, window, block_q, block_k)
    want = mha_reference(q, k, v, causal=True, window=window)
    assert float(jnp.abs(got - want).max()) < 2e-5


@pytest.mark.parametrize("t, window, block_q, block_k", CASES)
def test_gradients_match_the_reference(t, window, block_q, block_k):
    q, k, v, do = _qkv(t, seed=t + window)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * do)

    got = jax.grad(
        loss(lambda *a: _flash(*a, window, block_q, block_k)), (0, 1, 2)
    )(q, k, v)
    want = jax.grad(
        loss(lambda *a: mha_reference(*a, causal=True, window=window)),
        (0, 1, 2),
    )(q, k, v)
    for name, a, b in zip("qkv", got, want):
        assert float(jnp.abs(a - b).max()) < 5e-5, name


@pytest.mark.parametrize("window", [48, 64, 1000])
def test_a_window_the_sequence_fits_is_plain_causal_bit_for_bit(window):
    q, k, v, do = _qkv(48)
    assert jnp.array_equal(_flash(q, k, v, window), _flash(q, k, v, 0))
    grads = [
        jax.grad(lambda q, k, v: jnp.sum(_flash(q, k, v, w) * do), (0, 1, 2))(
            q, k, v
        )
        for w in (window, 0)
    ]
    assert all(jnp.array_equal(a, b) for a, b in zip(*grads))
    # and it is the SAME program: the window is a static argument
    texts = [
        str(jax.make_jaxpr(lambda q, k, v: _flash(q, k, v, w))(q, k, v))
        for w in (window, 0)
    ]
    assert texts[0] == texts[1]


def test_the_reference_with_a_window_is_the_masked_softmax():
    q, k, v, _ = _qkv(12)
    rows, cols = jnp.arange(12)[:, None], jnp.arange(12)[None, :]
    seen = (cols <= rows) & (cols > rows - 5)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (DIM ** 0.5)
    weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    want = jnp.einsum("bhqk,bhkd->bhqd", weights, v)
    got = mha_reference(q, k, v, causal=True, window=5)
    assert float(jnp.abs(got - want).max()) < 1e-5


def test_a_window_is_causal_self_attentions():
    q, k, v, _ = _qkv(32)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q[:, :, :16], k, v, causal=True, window=8)
    with pytest.raises(ValueError, match="window"):
        mha_reference(q, k, v, causal=False, window=8)


@pytest.mark.parametrize("window, block_q, block_k, seq", [
    (2048, 1024, 1024, 8192), (2048, 512, 512, 8192), (600, 1024, 1024, 2304),
    (24, 16, 16, 64), (33, 32, 16, 64),
])
def test_the_blocks_visited_are_the_blocks_that_hold_a_seen_pair(
    window, block_q, block_k, seq
):
    """`_block_runs`, `_first_key_block` and `_block_needs_mask` against
    a brute-force look at the mask, at the benchmark's own block sizes:
    a tile runs where it holds a pair the mask lets through, the first
    that runs is floor((qi * block_q - window + 1) / block_k), not under
    0, and a tile needs its mask where it also holds a pair it hides."""
    import numpy as np

    rows, cols = np.arange(seq)[:, None], np.arange(seq)[None, :]
    seen = (cols <= rows) & (rows - cols < window)
    for qi in range(-(-seq // block_q)):
        ran = []
        for ki in range(-(-seq // block_k)):
            tile = seen[qi * block_q:(qi + 1) * block_q,
                        ki * block_k:(ki + 1) * block_k]
            runs = bool(_block_runs(qi, ki, block_q, block_k, True, window))
            assert runs == bool(tile.any()), (qi, ki)
            if runs:
                ran.append(ki)
                masked = bool(_block_needs_mask(
                    qi, ki, block_q, block_k, True, seq, window
                ))
                whole = tile.shape == (block_q, block_k) and tile.all()
                assert masked == (not whole), (qi, ki)
        assert ran[0] == int(_first_key_block(qi, block_q, block_k, window))
        assert ran == list(range(ran[0], ran[-1] + 1))
