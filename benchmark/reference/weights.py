"""The weights of a serve cell, made by the benchmark from `--seed`.

Both sides of `correct` take them from here and neither from the
other: the replica is handed this tree in the program's place of
`init_params` (`drivers/serve_replica.py`), and the reference's pass
(`drivers/serve_probe.py`) makes the same tree again from the same
seed, after the replica has gone. So a leaf the program builds wrongly,
leaves out or lays out differently is not shared by the two sides.

One jitted call on the device, every leaf drawn, scaled and cast in one
pass in the type it is served in. Every leaf is drawn: matrices normal
with deviation 1 / sqrt(fan in), norm weights around 1 (deviation 0.1)
and biases with deviation 0.05, so that a norm weight or a bias that is
dropped or misplaced moves the logits by several times their rounding
(all-ones norms and zero biases would hide it). The biases are no
larger because they are exact on both sides: at a deviation of 0.5 the
value bias alone put a common, exactly computed part into every
position's residual stream and the int8 control read 0.030-0.035 where
it reads 0.053-0.060 without (PERF.md section 6, PR 36). Each leaf has a
key of its own, folded from its name, so adding a leaf moves no other.

The tree is the one the references read, layers stacked on axis 0:
`embed` [vocab, d], `lm_head` [d, vocab], `final_norm` [d], and under
`layers`: `wq` `wk` `wv` `wo`, `attn_norm`, `mlp_norm`; with
`attn_bias` `bq` `bk` `bv`; with `qk_norm` `q_norm` `k_norm` (over the
whole projection for "proj", over one head otherwise); dense `w1` (up),
`w3` (gate), `w2` (down); with `moe_experts` `router` [d, E], `w_gate`
`w_up` [E, d, f], `w_down` [E, f, d].
"""

from __future__ import annotations

import zlib


def shapes(model: dict) -> dict:
    """{leaf path: (shape, kind, fan in)} for a configuration's `model`
    keys; kind is "matrix", "norm" or "bias"."""
    d, layers = model["dim"], model["n_layers"]
    heads, kv = model["n_heads"], model.get("n_kv_heads") or model["n_heads"]
    hd = model.get("custom_head_dim") or d // heads
    f, vocab = model["intermediate"], model["vocab_size"]
    out = {
        "embed": ((vocab, d), "matrix", d),
        "lm_head": ((d, vocab), "matrix", d),
        "final_norm": ((d,), "norm", 0),
        "layers/wq": ((layers, d, heads * hd), "matrix", d),
        "layers/wk": ((layers, d, kv * hd), "matrix", d),
        "layers/wv": ((layers, d, kv * hd), "matrix", d),
        "layers/wo": ((layers, heads * hd, d), "matrix", heads * hd),
        "layers/attn_norm": ((layers, d), "norm", 0),
        "layers/mlp_norm": ((layers, d), "norm", 0),
    }
    if model.get("attn_bias"):
        out["layers/bq"] = ((layers, heads * hd), "bias", 0)
        out["layers/bk"] = ((layers, kv * hd), "bias", 0)
        out["layers/bv"] = ((layers, kv * hd), "bias", 0)
    norm = model.get("qk_norm")
    if norm:
        whole = norm == "proj"
        out["layers/q_norm"] = ((layers, heads * hd if whole else hd), "norm", 0)
        out["layers/k_norm"] = ((layers, kv * hd if whole else hd), "norm", 0)
    experts = model.get("moe_experts")
    if experts:
        out["layers/router"] = ((layers, d, experts), "matrix", d)
        out["layers/w_gate"] = ((layers, experts, d, f), "matrix", d)
        out["layers/w_up"] = ((layers, experts, d, f), "matrix", d)
        out["layers/w_down"] = ((layers, experts, f, d), "matrix", f)
    else:
        out["layers/w1"] = ((layers, d, f), "matrix", d)
        out["layers/w3"] = ((layers, d, f), "matrix", d)
        out["layers/w2"] = ((layers, f, d), "matrix", f)
    return out


def make(model: dict, dtype: str, seed: int) -> dict:
    """The parameter tree of `model` in `dtype`, from `seed`."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    plan = shapes(model)

    def draw(key):
        tree = {"layers": {}}
        for path, (shape, kind, fan_in) in plan.items():
            noise = jax.random.normal(
                jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF),
                shape, jnp.float32,
            )
            if kind == "matrix":
                leaf = noise * (fan_in ** -0.5)
            elif kind == "norm":
                leaf = 1.0 + 0.1 * noise
            else:
                leaf = 0.05 * noise
            where = tree
            *parents, name = path.split("/")
            for parent in parents:
                where = where[parent]
            where[name] = leaf.astype(dt)
        return tree

    return jax.jit(draw)(jax.random.PRNGKey(int(seed)))
