"""Plain reference: a Llama-family decoder's forward pass in float32.

Written from the published equations (pre-norm residual blocks,
RMSNorm, rotary embedding in the half-split convention, grouped-query
causal attention with optional QKV bias, SwiGLU), independent of
`ray_tpu/models`: no kernel, no cache, no scan, no remat, no batching.
It takes the program's parameter tree (layers stacked on axis 0, `w3`
the gate and `w1` the up projection) and upcasts one layer at a time,
so a model whose weights fill most of a chip can still be checked.
Matrix multiplications run at `highest` precision: on a TPU a float32
matmul is otherwise done in bfloat16 passes.

Departures from the papers: none in the mathematics. Attention is
computed for `q_block` query rows at a time against all keys, and the
final norm and the head only over the `rows` that are asked for, which
changes memory and not the result.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def _rotary(x, positions, theta):
    """x: [heads, t, head_dim]; rotate the two halves."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(
    jax.jit,
    static_argnames=(
        "n_heads", "n_kv_heads", "head_dim", "eps", "theta", "q_block"
    ),
)
def _layer(x, layer, *, n_heads, n_kv_heads, head_dim, eps, theta, q_block):
    """One block on x [t, dim] float32; `layer` holds bf16 weights of
    this layer only and is upcast here."""
    w = {k: v.astype(jnp.float32) for k, v in layer.items()}
    t = x.shape[0]
    positions = jnp.arange(t)
    h = _rms_norm(x, w["attn_norm"], eps)
    q, k, v = h @ w["wq"], h @ w["wk"], h @ w["wv"]
    if "bq" in w:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = q.reshape(t, n_heads, head_dim).transpose(1, 0, 2)
    k = k.reshape(t, n_kv_heads, head_dim).transpose(1, 0, 2)
    v = v.reshape(t, n_kv_heads, head_dim).transpose(1, 0, 2)
    q = _rotary(q, positions, theta)
    k = _rotary(k, positions, theta)
    group = n_heads // n_kv_heads
    k = jnp.repeat(k, group, axis=0)
    v = jnp.repeat(v, group, axis=0)
    scale = 1.0 / (head_dim ** 0.5)
    blocks = []
    for start in range(0, t, q_block):
        rows = positions[start:start + q_block]
        scores = jnp.einsum("hqd,hkd->hqk", q[:, start:start + q_block], k)
        visible = positions[None, :] <= rows[:, None]
        scores = jnp.where(visible[None], scores * scale, -jnp.inf)
        blocks.append(
            jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, axis=-1), v)
        )
    attn = jnp.concatenate(blocks, axis=1)
    x = x + attn.transpose(1, 0, 2).reshape(t, n_heads * head_dim) @ w["wo"]
    h = _rms_norm(x, w["mlp_norm"], eps)
    return x + (jax.nn.silu(h @ w["w3"]) * (h @ w["w1"])) @ w["w2"]


#: The head runs over a number of rows rounded up to a multiple of
#: this, so that a handful of programs serve every request: a program
#: for each number of served tokens cost a run 40-70 s of compiling
#: (PERF.md section 6, PR 39).
HEAD_BLOCK = 256


@partial(jax.jit, static_argnames=("size", "eps"))
def _head_block(x, final_norm, lm_head, first, *, size, eps):
    x = jax.lax.dynamic_slice_in_dim(x, first, size, axis=0)
    x = _rms_norm(x, final_norm.astype(jnp.float32), eps)
    return x @ lm_head.astype(jnp.float32)


def _head(x, params, eps, rows=None):
    """Final norm and head of x [t, dim] -> logits [t, vocab], or of
    `rows` = (start, stop) alone: the same product for those rows, and
    every position's logits never exist (at most `HEAD_BLOCK` - 1
    neighbouring rows are computed beside them and cut off)."""
    t = x.shape[0]
    start, stop = rows or (0, t)
    size = min(-(-(stop - start) // HEAD_BLOCK) * HEAD_BLOCK, t)
    first = min(start, t - size)
    logits = _head_block(
        x, params["final_norm"], params["lm_head"], first, size=size, eps=eps
    )
    return logits[start - first:stop - first]


def forward(params, tokens, model: dict, rows=None, q_block: int = 512):
    """tokens [t] int -> logits [t, vocab] float32, or with
    `rows=(start, stop)` [stop - start, vocab], those positions' (the
    layers still run over all t). `model` holds `LlamaConfig` keys
    (dim, n_layers, n_heads, n_kv_heads, norm_eps, rope_theta)."""
    head_dim = model.get("custom_head_dim") or model["dim"] // model["n_heads"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        for i in range(model["n_layers"]):
            layer = {k: v[i] for k, v in params["layers"].items()}
            x = _layer(
                x, layer,
                n_heads=model["n_heads"], n_kv_heads=model["n_kv_heads"],
                head_dim=head_dim, eps=float(model.get("norm_eps", 1e-6)),
                theta=float(model.get("rope_theta", 10000.0)),
                q_block=q_block,
            )
        return _head(x, params, float(model.get("norm_eps", 1e-6)), rows)
