"""A chunk's cache entries as every head's keys and values: one bounded
Pallas TPU matmul whose results ARE the buffers the attention kernel
reads.

Latent attention keeps one compressed entry a token; a prefill chunk
attends in the expanded form (ops/selected_attention.py), so a layer
expands the row's live key tiles through Wkv_b's two parts: `entries[:,
:latent] @ wk` and `@ wv`. Only the tiles up to a row's last live one
are ever read, so only those are made: the grid walks (row, column
block, key tile) with the KEY TILE INNERMOST, a tile past the row's
live ones is the last live one again (nothing new is fetched for it)
and its step does nothing, so the output block it revisits is written
back once, with what the last live step left in it. Past the live
tiles the outputs hold whatever the memory held: a `pallas_call`'s
result is not initialised, which is the point (no fill of `max_len`
keys x heads x lanes a layer), and the attention kernel skips the
same tiles by the same count.

Layout: entries [rows, keys, >= latent lanes] (the latent leads; what
lies behind it is not read), wk [latent, heads x dn], wv [latent,
heads x dv], live [rows] int32 -> kn [rows, keys, heads x dn], v [rows,
keys, heads x dv] in the entries' dtype, accumulated in float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret() -> bool:
    # (as ops/attention.py: the interpreter on the CPU, Mosaic on a TPU)
    return jax.default_backend() == "cpu"


def _kernel(live_ref, entries_ref, wk_ref, wv_ref, kn_ref, v_ref):
    @pl.when(pl.program_id(2) < live_ref[pl.program_id(0)])
    def _tile():
        latents = entries_ref[0]
        for w_ref, out_ref in ((wk_ref, kn_ref), (wv_ref, v_ref)):
            out_ref[0] = jnp.dot(
                latents, w_ref[...], preferred_element_type=jnp.float32
            ).astype(out_ref.dtype)


def _column_blocks(nk: int, nv: int, block_n: int) -> int:
    """The fewest column blocks that cut both widths into whole lanes
    of at most `block_n` columns."""
    lanes = math.gcd(nk, nv) // 128
    for blocks in range(1, lanes):
        if lanes % blocks == 0 and max(nk, nv) <= blocks * block_n:
            return blocks
    return lanes


def latent_expand(
    entries, wk, wv, live, *, block_k: int = 512, block_n: int = 1024,
):
    """(entries[..., :latent] @ wk, entries[..., :latent] @ wv) over
    each row's first `live[row]` key tiles of `block_k` (its first
    tile always: the attention kernel reads that one of a row with no
    key too); every other tile of the results is uninitialised,
    finite or not."""
    rows, keys, _ = entries.shape
    latent, nk = wk.shape
    nv = wv.shape[1]
    block_k = min(block_k, keys)
    if keys % block_k or latent % 128 or nk % 128 or nv % 128:
        raise ValueError(
            f"{keys} keys are not whole tiles of {block_k}, or "
            f"{latent} / {nk} / {nv} columns are not whole lanes"
        )
    blocks = _column_blocks(nk, nv, block_n)
    live = jnp.maximum(jnp.asarray(live, jnp.int32), 1)

    def tile(ri, ki, live_ref):
        return jnp.minimum(ki, live_ref[ri] - 1)

    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows, blocks, keys // block_k),
            in_specs=[
                pl.BlockSpec(
                    (1, block_k, latent),
                    lambda ri, ci, ki, live: (ri, tile(ri, ki, live), 0),
                ),
                pl.BlockSpec(
                    (latent, nk // blocks), lambda ri, ci, ki, live: (0, ci)
                ),
                pl.BlockSpec(
                    (latent, nv // blocks), lambda ri, ci, ki, live: (0, ci)
                ),
            ],
            out_specs=[
                pl.BlockSpec(
                    (1, block_k, nk // blocks),
                    lambda ri, ci, ki, live: (ri, tile(ri, ki, live), ci),
                ),
                pl.BlockSpec(
                    (1, block_k, nv // blocks),
                    lambda ri, ci, ki, live: (ri, tile(ri, ki, live), ci),
                ),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((rows, keys, nk), entries.dtype),
            jax.ShapeDtypeStruct((rows, keys, nv), entries.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
        # Its name in the compiled program and in a device trace
        # (`%latent_expand.N`): not `selected_attn*`, which is what the
        # attention kernel's time is summed by.
        name="latent_expand",
    )(live, entries, wk, wv)
