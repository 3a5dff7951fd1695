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
computed for `q_block` query rows at a time against all keys, which
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


def forward(params, tokens, model: dict, q_block: int = 512):
    """tokens [t] int -> logits [t, vocab] float32. `model` holds
    `LlamaConfig` keys (dim, n_layers, n_heads, n_kv_heads, norm_eps,
    rope_theta)."""
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
        x = _rms_norm(
            x, params["final_norm"].astype(jnp.float32),
            float(model.get("norm_eps", 1e-6)),
        )
        return x @ params["lm_head"].astype(jnp.float32)
