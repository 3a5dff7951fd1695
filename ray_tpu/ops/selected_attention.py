"""Attention of a prefill chunk over the keys a learned selection left
it: a forward-only Pallas TPU flash kernel with a mask of its own.

DeepSeek-V3.2's sparse attention gives every query its own set of
keys (the `index_topk` best by its indexer). A decode step gathers a
row's selected cache entries (models/generate.py); a chunk's 2,048
queries each select another 2,048 of up to 16k keys, and a gather of
those is 4 M cache rows a layer. So a chunk runs DENSE over the live
key tiles and attends where the selection allows: the mask is an int8
[queries, keys] array (causal mask, row length and selection in one),
made outside and read a tile at a time. In plain XLA that attention
writes every tile's scores and weights to memory (12 bytes a (head,
query, key)); here they live in VMEM, as in `ops/attention.py`'s
kernel, whose pattern this follows: a sequential grid over key tiles,
online-softmax state in VMEM scratch, float32 accumulation, the
softmax scale and log2(e) folded into q.

The heads are latent attention's in their EXPANDED form (a chunk's
keys are expanded from the latent cache once a layer, outside): a
head's score is `qn . kn` over its own key dims plus `qr . kr` over
the rotary dims, whose key is ONE for all heads and is passed once.
Key tiles wholly after a query block's last position, or past the
row's length, are neither fetched nor computed.

Layout: qn, qr [b, heads, t, dims] (dims whole lanes of 128); kn, v
[b, keys, heads x dims], the heads side by side in a key's row, as the
expansion's matmul leaves them (a block is one head's columns of a
tile of rows: no transpose of the expanded keys, which cost more than
the expansion); kr [b, keys, dims]; mask [b, t, keys] int8; -> [b,
heads, t, v dims]. `t` and `keys` are whole blocks.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)
_LOG2E = math.log2(math.e)


def _interpret() -> bool:
    # (as ops/attention.py: the interpreter on the CPU, Mosaic on a TPU)
    return jax.default_backend() == "cpu"


def _last_tile(bounds_ref, b, qi, block_q: int, block_k: int):
    """The last key tile query block `qi` of row `b` needs: the one
    that holds its last query's position, or the row's last key."""
    first_pos, length = bounds_ref[b, 0], bounds_ref[b, 1]
    last_key = jnp.minimum(first_pos + (qi + 1) * block_q, length) - 1
    return jnp.maximum(last_key, 0) // block_k


def _kernel(
    bounds_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref, mask_ref,
    out_ref, acc_ref, m_ref, l_ref, *, block_q: int, block_k: int,
):
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _start():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _MASKED)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(ki <= _last_tile(bounds_ref, b, qi, block_q, block_k))
    def _tile():
        contract = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(
            qn_ref[0, 0], kn_ref[0], contract,
            preferred_element_type=jnp.float32,
        ) + jax.lax.dot_general(
            qr_ref[0, 0], kr_ref[0], contract,
            preferred_element_type=jnp.float32,
        )  # [bq, bk], log2-domain logits
        s = jnp.where(mask_ref[0] != 0, s, _MASKED)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        # (a row with no key yet has m_new == _MASKED and weighs its
        # masked keys 1: the first real key's alpha is 0 and wipes it)
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m_prev - m_new)
        l_ref[:] = jnp.broadcast_to(
            alpha * l_ref[:, :1] + p.sum(axis=-1, keepdims=True),
            l_ref.shape,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        v = v_ref[0]
        acc_ref[:] = alpha * acc_ref[:] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        out_ref[0, 0] = (
            acc_ref[:] / jnp.where(l == 0.0, 1.0, l)
        ).astype(out_ref.dtype)


def selected_attention(
    qn, qr, kn, kr, v, mask, first_pos, length, *, scale: float,
    block_q: int = 512, block_k: int = 512,
):
    """softmax over the keys `mask` allows of (qn . kn + qr . kr) *
    scale, times v. qn [b, h, t, dn], qr [b, h, t, dr]; kn [b, keys,
    h x dn], kr [b, keys, dr], v [b, keys, h x dv]; mask [b, t, keys] int8
    (non-zero: attend); `first_pos` [b] the position of a row's first
    query and `length` [b] its keys (tiles the mask rules out whole
    are skipped by them) -> [b, h, t, dv] in q's dtype. Every query is
    allowed a key or more (its own, under a causal mask); one that is
    allowed none gets a mean of values nobody reads."""
    b, h, t, dn = qn.shape
    keys, dv = kn.shape[1], v.shape[-1] // h
    block_q, block_k = min(block_q, t), min(block_k, keys)
    if t % block_q or keys % block_k:
        raise ValueError(
            f"{t} queries / {keys} keys are not whole blocks of "
            f"{block_q} / {block_k}"
        )
    grid = (b, h, t // block_q, keys // block_k)
    fold = scale * _LOG2E
    qn = (qn.astype(jnp.float32) * fold).astype(qn.dtype)
    qr = (qr.astype(jnp.float32) * fold).astype(qr.dtype)
    bounds = jnp.stack(
        [jnp.asarray(first_pos, jnp.int32), jnp.asarray(length, jnp.int32)],
        axis=1,
    )  # [b, 2]

    def tile(bi, qi, ki, bounds_ref):
        # a tile past the last needed one is the last one again: the
        # pipeline fetches nothing new for it
        return jnp.minimum(
            ki, _last_tile(bounds_ref, bi, qi, block_q, block_k)
        )

    kernel = functools.partial(_kernel, block_q=block_q, block_k=block_k)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (1, 1, block_q, qn.shape[-1]),
                    lambda bi, hi, qi, ki, bounds: (bi, hi, qi, 0),
                ),
                pl.BlockSpec(
                    (1, 1, block_q, qr.shape[-1]),
                    lambda bi, hi, qi, ki, bounds: (bi, hi, qi, 0),
                ),
                pl.BlockSpec(
                    (1, block_k, dn),
                    lambda bi, hi, qi, ki, bounds: (
                        bi, tile(bi, qi, ki, bounds), hi
                    ),
                ),
                pl.BlockSpec(
                    (1, block_k, kr.shape[-1]),
                    lambda bi, hi, qi, ki, bounds: (
                        bi, tile(bi, qi, ki, bounds), 0
                    ),
                ),
                pl.BlockSpec(
                    (1, block_k, dv),
                    lambda bi, hi, qi, ki, bounds: (
                        bi, tile(bi, qi, ki, bounds), hi
                    ),
                ),
                pl.BlockSpec(
                    (1, block_q, block_k),
                    lambda bi, hi, qi, ki, bounds: (
                        bi, qi, tile(bi, qi, ki, bounds)
                    ),
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, block_q, dv),
                lambda bi, hi, qi, ki, bounds: (bi, hi, qi, 0),
            ),
            scratch_shapes=[
                pltpu.VMEM((block_q, dv), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, t, dv), qn.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary"
            ),
        ),
        interpret=_interpret(),
        # The kernel's name in the compiled program and in a device
        # trace (`%selected_attn.N`): what its time is summed by.
        name="selected_attn",
    )(bounds, qn, qr, kn, kr, v, mask)


def selected_attention_reference(qn, qr, kn, kr, v, mask, *, scale: float):
    """The same mathematics in plain float32 jnp (tests)."""
    f32 = jnp.float32
    b, h, _, dn = qn.shape
    keys = kn.shape[1]
    kn = kn.reshape(b, keys, h, dn).transpose(0, 2, 1, 3)
    v = v.reshape(b, keys, h, -1).transpose(0, 2, 1, 3)
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", qn.astype(f32), kn.astype(f32),
        precision="highest",
    ) + jnp.einsum(
        "bhqd,bkd->bhqk", qr.astype(f32), kr.astype(f32),
        precision="highest",
    )
    s = jnp.where(mask[:, None] != 0, s * scale, -jnp.inf)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(mask[:, None] != 0, p, 0.0)
    out = jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(f32), precision="highest"
    )
    return out / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
