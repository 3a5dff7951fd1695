"""Ring attention: exact attention over sequence shards on a ring.

Net-new relative to the reference, which has no sequence/context
parallelism at all (SURVEY.md §5.7 — verified absent; its nearest
primitives are NCCL p2p send/recv in util.collective). Here the ring
rides the ICI mesh axis: each step computes blockwise attention of the
local Q shard against the currently-held KV shard while `ppermute`
rotates KV shards around the ring, merging partial results with the
online-softmax rule — memory stays O(T_local^2 / ring) per step and KV
transfer overlaps compute under XLA's scheduler.

Use inside `shard_map` with the sequence dimension sharded over
`axis_name` ("sp"), contiguous layout: rank r owns positions
[r*T_local, (r+1)*T_local).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.collective import axis_size as _axis_size, pcast_varying

from .attention import DEFAULT_MASK_VALUE


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
) -> jax.Array:
    """Exact attention over a sequence-sharded ring.

    q/k/v: local shards [batch, heads, t_local, head_dim].
    Returns the local output shard [batch, heads, t_local, head_dim].
    """
    b, h, t_local, d = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    n = _axis_size(axis_name)
    rank = lax.axis_index(axis_name)

    qf = q.astype(jnp.float32)
    q_pos = rank * t_local + jnp.arange(t_local)  # global positions

    # Receive-from-left permutation: after s steps we hold the KV shard
    # of rank (rank - s) mod n.
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(s, carry):
        acc, m, l, k_cur, v_cur = carry
        src = (rank - s) % n
        k_pos = src * t_local + jnp.arange(t_local)
        logits = (
            jnp.einsum(
                "bhqd,bhkd->bhqk",
                qf,
                k_cur.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )
            * scale
        )
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(mask, logits, DEFAULT_MASK_VALUE)
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bhqk,bhkd->bhqd",
            p,
            v_cur.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        # Rotate KV shards one step around the ring (ICI neighbor hop).
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return acc_new, m_new, l_new, k_next, v_next

    # Initializers are device-varying over the ring axis (each rank
    # accumulates different data) — mark them so the loop carry's
    # varying-manual-axes type matches its body. *_like inherits every
    # varying axis q already carries (the ring axis among them when q
    # is sharded over it; pp/ep when ring attention runs inside the
    # pipeline/MoE composition).
    acc = pcast_varying(jnp.zeros_like(qf), axis_name)
    m = pcast_varying(jnp.full_like(qf[..., :1], -jnp.inf), axis_name)
    l = pcast_varying(jnp.zeros_like(qf[..., :1]), axis_name)
    acc, m, l, _, _ = lax.fori_loop(0, n, step, (acc, m, l, k, v))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return (acc / l_safe).astype(q.dtype)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    attention_fn=None,
) -> jax.Array:
    """Ulysses-style sequence parallelism: all-to-all from seq-sharded
    to head-sharded, run full-sequence attention locally on the head
    subset, all-to-all back (SURVEY.md §2.4 SP row).

    Requires heads % axis_size == 0. q/k/v: [batch, heads, t_local, d].
    """
    from .attention import mha_reference

    attention_fn = attention_fn or (
        lambda q, k, v: mha_reference(q, k, v, causal=causal, scale=scale)
    )
    n = _axis_size(axis_name)

    def reshard_to_heads(x):
        # [b, H, t/n, d] -> [b, H/n, t, d]: split heads, concat seq.
        return lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    def reshard_to_seq(x):
        return lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    qh, kh, vh = map(reshard_to_heads, (q, k, v))
    out = attention_fn(qh, kh, vh)
    return reshard_to_seq(out)
