"""Plain reference: OLMoE's forward pass in float32.

Written from the published equations (arXiv:2409.02060 and
`modeling_olmoe.py` of transformers), independent of `ray_tpu/models`
and `ray_tpu/ops`: no kernel, no cache, no scan, no sort, no grouped
matmul. A block is

    q = RMSNorm(h Wq), k = RMSNorm(h Wk)   over the WHOLE projection,
                                           before the split into heads
    rotary (half-split), causal attention, Wo, residual
    p = softmax(h Wr) over all experts, in float32
    the k largest p and their experts; the gates are NOT renormalised
    y = sum_i p_i * Wdown_i( silu(Wgate_i h) * (Wup_i h) ), residual

and the experts are a loop over all of them with a mask, as the
published `OlmoeSparseMoeBlock` is. It takes the program's parameter
tree (layers stacked on axis 0; `router` [d, E]; `w_gate`, `w_up`
[E, d, f]; `w_down` [E, f, d]; `q_norm`, `k_norm` over the projection)
and upcasts one layer at a time. Matrix multiplications run at
`highest` precision. It reads `moe_top_k` and `moe_router` from the
model's keys, so a configuration that renormalises is checked as
such; rotary, the norm and the head are `llama_ref`'s.

Departures from the published code: the router's matmul and softmax
run in float32 here (and in the program); transformers multiplies by
the gate matrix in the model's dtype and upcasts only the softmax. In
float32, which is what this file computes in, the two are the same.
Attention is computed for `q_block` query rows at a time, which
changes memory and not the result. `clip_qkv` is null in the
published configuration and not implemented.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmark.reference.llama_ref import _head, _rms_norm, _rotary


@partial(
    jax.jit,
    static_argnames=(
        "n_heads", "n_kv_heads", "head_dim", "eps", "theta", "top_k",
        "renormalise", "q_block",
    ),
)
def _layer(
    x, layer, *, n_heads, n_kv_heads, head_dim, eps, theta, top_k,
    renormalise, q_block,
):
    """One block on x [t, dim] float32; `layer` holds this layer's
    weights in the model's dtype and is upcast here."""
    w = {k: v.astype(jnp.float32) for k, v in layer.items()}
    t = x.shape[0]
    positions = jnp.arange(t)
    h = _rms_norm(x, w["attn_norm"], eps)
    q = _rms_norm(h @ w["wq"], w["q_norm"], eps)
    k = _rms_norm(h @ w["wk"], w["k_norm"], eps)
    v = h @ w["wv"]
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
    probs = jax.nn.softmax(h @ w["router"], axis=-1)  # [t, E]
    gates, chosen = jax.lax.top_k(probs, top_k)
    if renormalise:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(w["router"].shape[-1]):
        # This expert's gate for each token: its probability where it
        # is one of the token's top k, else nothing.
        gate = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1)
        hidden = jax.nn.silu(h @ w["w_gate"][e]) * (h @ w["w_up"][e])
        y = y + gate[:, None] * (hidden @ w["w_down"][e])
    return x + y


def forward(params, tokens, model: dict, rows=None, q_block: int = 512):
    """tokens [t] int -> logits [t, vocab] float32, or with
    `rows=(start, stop)` those positions' alone. `model` holds
    `LlamaConfig` keys (dim, n_layers, n_heads, n_kv_heads, norm_eps,
    rope_theta, moe_top_k, moe_router)."""
    head_dim = model.get("custom_head_dim") or model["dim"] // model["n_heads"]
    eps = float(model.get("norm_eps", 1e-6))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        for i in range(model["n_layers"]):
            layer = {k: v[i] for k, v in params["layers"].items()}
            x = _layer(
                x, layer,
                n_heads=model["n_heads"], n_kv_heads=model["n_kv_heads"],
                head_dim=head_dim, eps=eps,
                theta=float(model.get("rope_theta", 10000.0)),
                top_k=int(model.get("moe_top_k", 2)),
                renormalise=model.get(
                    "moe_router", "softmax_renorm"
                ) == "softmax_renorm",
                q_block=q_block,
            )
        return _head(x, params, eps, rows)
