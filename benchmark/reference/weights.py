"""The weights of a serve cell, made by the benchmark from `--seed`.

Both sides of `correct` take them from here and neither from the
other: the replica is handed this tree in the program's place of
`init_params` (`drivers/serve_replica.py`), and the reference's pass
(`drivers/serve_probe.py`) makes the same tree again from the same
seed, after the replica has gone. So a leaf the program builds wrongly,
leaves out or lays out differently is not shared by the two sides.

Which leaves exist is said by a PLAN, `{path: (shape, kind, fan in)}`:
that of the configuration's reference module where it defines
`shapes(model)` (`make(..., reference=<module>)`; it may start from
this file's `shapes(model)` and add leaves or leave some out), else
this file's `shapes`, the dense and the expert Llama-family trees that
`llama_ref` and `olmoe_ref` read. So an architecture with one more
matrix is a reference module of its own and no edit here. A plan holds:

- paths: names joined by `/`, under any parents, made as they are met
  (`layers/wq`, `layers/indexer/wq`, `dense_layers/w1`, `mtp/proj`): a
  model whose layers are not all alike has more than one stack;
- kinds: "matrix" (normal, deviation 1 / sqrt(fan in)), "norm" (around
  1, deviation 0.1), "bias" (deviation 0.05), or a pair
  `(mean, deviation)` for a leaf whose place in the equations wants
  none of these (a decay's logarithm, a router's correction bias);
  anything else is refused.

One jitted call on the device, every leaf drawn, scaled and cast in one
pass in the type it is served in. Every leaf is drawn, norm weights and
biases too, so that a norm weight or a bias that is dropped or
misplaced moves the logits by several times their rounding (all-ones
norms and zero biases would hide it). The biases are no larger because
they are exact on both sides: at a deviation of 0.5 the value bias
alone put a common, exactly computed part into every position's
residual stream and the int8 control read 0.030-0.035 where it reads
0.053-0.060 without (PERF.md section 6, PR 36). Each leaf has a key of
its own, folded from its path, so a plan with more leaves draws the old
ones unchanged (`tests/benchmark/test_benchmark_weights.py` pins them).
"""

from __future__ import annotations

import zlib


def shapes(model: dict) -> dict:
    """The plan of the Llama-family tree the program's `init_params`
    lays out and `llama_ref` / `olmoe_ref` read, for a configuration's
    `model` keys, every layer's leaf stacked on axis 0 under `layers`:
    `embed` [vocab, d], `lm_head` [d, vocab], `final_norm` [d]; `wq`
    `wk` `wv` `wo`, `attn_norm`, `mlp_norm`; with `attn_bias` `bq` `bk`
    `bv`; with `qk_norm` `q_norm` `k_norm` (over the whole projection
    for "proj", over one head otherwise); dense `w1` (up), `w3` (gate),
    `w2` (down); with `moe_experts` `router` [d, E], `w_gate` `w_up`
    [E, d, f], `w_down` [E, f, d]."""
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


def _scaled(noise, kind, fan_in: int, path: str):
    """A leaf of `kind` from standard normal `noise`."""
    if kind == "matrix":
        return noise * (fan_in ** -0.5)
    if kind == "norm":
        return 1.0 + 0.1 * noise
    if kind == "bias":
        return 0.05 * noise
    if isinstance(kind, (tuple, list)) and len(kind) == 2:
        mean, deviation = kind
        return float(mean) + float(deviation) * noise
    raise ValueError(
        f"leaf {path!r}: kind {kind!r} is not \"matrix\", \"norm\", \"bias\" "
        "or a pair (mean, deviation)"
    )


def make(model: dict, dtype: str, seed: int, reference=None) -> dict:
    """The parameter tree of `model` in `dtype`, from `seed`: the plan
    of `reference` (the module the configuration names) where it
    defines `shapes`, else this file's."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    plan = getattr(reference, "shapes", shapes)(model)

    def draw(key):
        tree: dict = {}
        for path, (shape, kind, fan_in) in plan.items():
            noise = jax.random.normal(
                jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF),
                tuple(shape), jnp.float32,
            )
            where = tree
            *parents, name = path.split("/")
            for parent in parents:
                if isinstance(where, dict):
                    where = where.setdefault(parent, {})
            if not isinstance(where, dict) or name in where:
                raise ValueError(f"leaf {path!r} is both a leaf and a parent")
            where[name] = _scaled(noise, kind, fan_in, path).astype(dt)
        return tree

    return jax.jit(draw)(jax.random.PRNGKey(int(seed)))
