"""The MoE tests' oracle: every expert on every token in float32, then
each token's k experts picked out and weighted. Written from the
equations, sharing nothing with `ray_tpu/ops/moe.py` but the parameter
tree: router [d, E]; w_gate, w_up [E, d, f]; w_down [E, f, d]."""

import jax
import jax.numpy as jnp


def all_experts_ffn(params, x, k, renormalise=True, act=jax.nn.silu):
    """x [t, d] -> [t, d] float32."""
    p = {name: w.astype(jnp.float32) for name, w in params.items()}
    x = x.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(x @ p["router"], axis=-1)
        gates, experts = jax.lax.top_k(probs, k)
        if renormalise:
            gates = gates / gates.sum(axis=-1, keepdims=True)
        hidden = act(jnp.einsum("td,edf->tef", x, p["w_gate"])) * jnp.einsum(
            "td,edf->tef", x, p["w_up"]
        )
        outs = jnp.einsum("tef,efd->ted", hidden, p["w_down"])
    picked = jnp.take_along_axis(outs, experts[:, :, None], axis=1)
    return jnp.sum(picked * gates[:, :, None], axis=1)
