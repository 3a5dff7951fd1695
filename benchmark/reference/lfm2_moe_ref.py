"""Plain reference: LFM2-24B-A2B's forward pass in float32, for one
rank's share of its routed experts.

Written from the configuration's published keys (`config.json` of
LiquidAI/LFM2-24B-A2B, `model_type` `lfm2_moe`), independent of
`ray_tpu/`: no cache, no state slots, no chunks, no pages, no work
list, no scan over periods, no grouped matmul, no kernels. `n` is a
block's input after its RMSNorm (weight, no bias, `norm_eps`); blocks
are pre-norm residual, `h = x + Mixer(norm_op(x))`, `y = h +
FFN(norm_ffn(h))`; a final norm; a head.

    layer l is `layer_kinds[l]` = (window, kv heads, theta, sink, taps):
        taps > 0 a CONV layer (`layer_types[l] == "conv"`), else full attention

    conv layer, the gated short convolution:
        [B | C | u] = n W_in      (W_in as its three dim x dim thirds: leaves
                                   `wq`, `wc`, `wu`, in that order)
        z = B * u                                            (elementwise)
        c_t = sum_j w_j z_(t - (taps - 1) + j),  j = 0 .. taps - 1
              (depthwise, causal, z zero before the first token, no bias:
               tap `taps - 1` meets the current position; three shifted
               products over the whole sequence)
        Mixer = (C * c) W_out                                (leaf `wo`)
        no activation, no softmax, no rotary

    attention layer: q = n Wq -> heads x hd;  k = n Wk, v = n Wv -> kv x hd
        q, k <- RMSNorm over each head's hd dims (one weight of hd for q,
        one for k), THEN rotary on all hd dims at theta, the two halves
        turned against each other; causal softmax at hd^-1/2; o = concat_h(.) Wo

    FFN of the leading `dense_layers`: Wdown(silu(Wgate n) * (Wup n)), width
        `dense_intermediate` (`w3` the gate, `w1` up, `w2` down)
    of the others: s = sigmoid(n Wr) over ALL the router's outputs (float32);
        chosen: the `moe_top_k` largest of s + router_bias (the bias in the
        choice alone); g_e = route_scale * s_e / (sum over ALL the chosen
        of s + 1e-6);  y = sum over the chosen e HELD HERE of g_e E_e(n),
        E a SwiGLU of width `intermediate`; no shared expert

The experts are a scan over the held ones (`moe_first_expert` and the
`moe_experts` - 1 after it) with a mask; what the experts held on the
other ranks would add is left out, as the program leaves it out
(`deployment` in the configuration's file): no code stands in for the
absent ranks.

Departures from the published description, each listed in the
configuration's file under `assumed`: the row has no key for the
norm's kind, the order of W_in's thirds, the taps' order or the q/k
norm's place, and the choices above are the family's published code's;
the published model ties head and embedding, and here the head is a
leaf of its own (`lm_head`, drawn by its own path like every leaf of
the benchmark's weights: the same operations and bytes a token, 134 M
parameters more than the published count's share); the program's
router (shared with the two EP16 configurations) has no 1e-6 in its
normaliser, this file has: a part in two million of a gate.

Memory, not mathematics: attention runs `q_block` query rows at a time
under `lax.map`, a dense FFN as a sum over blocks of its width, each
weight upcast alone, and the final norm and head over `rows`.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmark.reference import weights
from benchmark.reference.llama_ref import _head, _rms_norm, _rotary
from benchmark.reference.mimo_v2_ref import FFN_BLOCK, _glu_sum, _Numbers

#: A layer's leaves that are its mixer's, of either kind; the others
#: its FFN's.
MIXER_LEAVES = (
    "attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "wc", "wu",
    "taps",
)
#: The deviation the seeded `expert_bias` is drawn with, around 0: it
#: then moves which experts win, as a trained one does.
BIAS_DEVIATION = 0.1
#: The published normaliser's epsilon (`norm_topk_prob`).
GATE_EPS = 1e-6


def _by_kind(model: dict) -> dict:
    """{"conv" | "full": that kind's layers in order}."""
    out: dict = {}
    for layer, kind in enumerate(model["layer_kinds"]):
        conv = len(kind) > 4 and kind[4]
        out.setdefault("conv" if conv else "full", []).append(layer)
    return out


def shapes(model: dict) -> dict:
    """The plan `weights.make` draws: `embed`, `lm_head` and
    `final_norm` as `weights.shapes` has them; the stacks
    `dense_layers/*` (the leading dense layers) and `layers/*` (the
    others), each holding its layers' `attn_norm` (the operator norm),
    `wq` (an attention layer's Wq, a conv layer's `B` third of W_in),
    `wo` (Wo / W_out) and `mlp_norm` beside their FFN (`w1` `w3` `w2`,
    or `router` [d, outputs], `router_bias` and the held experts'
    `w_gate` `w_up` `w_down`); and what a kind has of its own stacked
    by kind over that kind's layers in order: `attn_full/*` `wk` `wv`
    `q_norm` `k_norm`, `attn_conv/*` `wc` `wu` (the `C` and `u` thirds)
    and `taps` [taps, d]."""
    plain = weights.shapes(dict(model, moe_experts=0))
    out = {k: plain[k] for k in ("embed", "lm_head", "final_norm")}
    d, heads = model["dim"], model["n_heads"]
    hd = d // heads
    dense = model.get("dense_layers", 0)
    n, held, f = model["n_layers"] - dense, model["moe_experts"], model["intermediate"]
    outputs = model.get("moe_router_experts") or held
    for stack, L in (("dense_layers", dense), ("layers", n)):
        if L:
            out.update({
                f"{stack}/attn_norm": ((L, d), "norm", 0),
                f"{stack}/wq": ((L, d, d), "matrix", d),
                f"{stack}/wo": ((L, d, d), "matrix", d),
                f"{stack}/mlp_norm": ((L, d), "norm", 0),
            })
    if dense:
        f_dense = model["dense_intermediate"]
        out.update({
            "dense_layers/w1": ((dense, d, f_dense), "matrix", d),
            "dense_layers/w3": ((dense, d, f_dense), "matrix", d),
            "dense_layers/w2": ((dense, f_dense, d), "matrix", f_dense),
        })
    out.update({
        "layers/router": ((n, d, outputs), "matrix", d),
        "layers/router_bias": ((n, outputs), (0.0, BIAS_DEVIATION), 0),
        "layers/w_gate": ((n, held, d, f), "matrix", d),
        "layers/w_up": ((n, held, d, f), "matrix", d),
        "layers/w_down": ((n, held, f, d), "matrix", f),
    })
    by_kind = _by_kind(model)
    if "full" in by_kind:
        L = len(by_kind["full"])
        kv = model["layer_kinds"][by_kind["full"][0]][1]
        out.update({
            "attn_full/wk": ((L, d, kv * hd), "matrix", d),
            "attn_full/wv": ((L, d, kv * hd), "matrix", d),
            "attn_full/q_norm": ((L, hd), "norm", 0),
            "attn_full/k_norm": ((L, hd), "norm", 0),
        })
    if "conv" in by_kind:
        L = len(by_kind["conv"])
        taps = model["layer_kinds"][by_kind["conv"][0]][4]
        out.update({
            "attn_conv/wc": ((L, d, d), "matrix", d),
            "attn_conv/wu": ((L, d, d), "matrix", d),
            "attn_conv/taps": ((L, taps, d), "matrix", taps),
        })
    return out


@partial(jax.jit, static_argnames=("m",))
def _conv(x, layer, *, m):
    """x + Mixer(norm(x)) of one conv layer on x [t, dim]."""
    f32 = jnp.float32
    w = {k: v.astype(f32) for k, v in layer.items()}
    t = x.shape[0]
    n = _rms_norm(x, w["attn_norm"], m["eps"])
    z = (n @ w["wq"]) * (n @ w["wu"])
    taps = w["taps"]  # [taps, dim]; the last meets the current position
    c = jnp.zeros_like(z)
    for j in range(taps.shape[0]):
        back = taps.shape[0] - 1 - j  # positions this tap looks back
        c = c + taps[j] * jnp.pad(z, ((back, 0), (0, 0)))[:t]
    return x + ((n @ w["wc"]) * c) @ w["wo"]


@partial(jax.jit, static_argnames=("m", "kv", "theta", "q_block"))
def _attention(x, layer, *, m, kv, theta, q_block):
    """x + Attn(norm(x)) of one attention layer on x [t, dim]."""
    f32 = jnp.float32
    t = x.shape[0]
    heads, hd = m["n_heads"], m["hd"]
    group = heads // kv
    w = {k: v.astype(f32) for k, v in layer.items()}
    positions = jnp.arange(t)
    n = _rms_norm(x, w["attn_norm"], m["eps"])

    def turned(a, count, norm):  # [t, count * hd] -> [count, t, hd]
        a = a.reshape(t, count, hd).transpose(1, 0, 2)
        return _rotary(_rms_norm(a, norm, m["eps"]), positions, theta)

    q = turned(n @ w["wq"], heads, w["q_norm"]).reshape(kv, group, t, hd)
    k = turned(n @ w["wk"], kv, w["k_norm"])
    v = (n @ w["wv"]).reshape(t, kv, hd).transpose(1, 0, 2)

    def q_rows(start):
        at = start + jnp.arange(q_block)
        scores = jnp.einsum(
            "vgqd,vkd->vgqk",
            jax.lax.dynamic_slice_in_dim(q, start, q_block, axis=2), k,
        ) * (hd ** -0.5)
        seen = positions[None, :] <= at[:, None]
        scores = jnp.where(seen, scores, -jnp.inf)
        out = jnp.einsum(
            "vgqk,vkd->qvgd", jax.nn.softmax(scores, axis=-1), v
        )
        return out.reshape(q_block, heads * hd)

    attn = jax.lax.map(q_rows, jnp.arange(0, t, q_block)).reshape(t, -1)
    return x + attn @ w["wo"]


@partial(jax.jit, static_argnames=("m",))
def _ffn(x, layer, *, m):
    """x + FFN(norm(x)): a dense SwiGLU where `layer` has no router,
    else this rank's share of the routed experts."""
    f32 = jnp.float32
    t = x.shape[0]
    n = _rms_norm(x, layer["mlp_norm"].astype(f32), m["eps"])
    if "router" not in layer:
        d, f = layer["w3"].shape
        blocks = f // FFN_BLOCK if f % FFN_BLOCK == 0 else 1
        return x + _glu_sum(
            n, layer["w3"].reshape(d, blocks, f // blocks).transpose(1, 0, 2),
            layer["w1"].reshape(d, blocks, f // blocks).transpose(1, 0, 2),
            layer["w2"].reshape(blocks, f // blocks, d),
            jnp.ones((t, blocks), f32),
        )
    scores = jax.nn.sigmoid(n @ layer["router"].astype(f32))  # [t, outputs]
    _, chosen = jax.lax.top_k(
        scores + layer["router_bias"].astype(f32), m["moe_top_k"]
    )
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = gates / (
        jnp.sum(gates, axis=-1, keepdims=True) + GATE_EPS
    ) * m["route_scale"]
    held = m["first_expert"] + jnp.arange(layer["w_gate"].shape[0])
    # a held expert's gate for each token: its own where it was chosen
    mine = jnp.sum(
        jnp.where(chosen[:, :, None] == held, gates[:, :, None], 0.0), axis=1
    )
    return x + _glu_sum(
        n, layer["w_gate"], layer["w_up"], layer["w_down"], mine
    )


def _numbers(model: dict) -> _Numbers:
    if model.get("moe_groups", 1) != 1 or model.get("moe_top_groups", 1) != 1:
        raise ValueError("lfm2_moe_ref: the router has one group")
    return _Numbers(
        n_heads=model["n_heads"], hd=model["dim"] // model["n_heads"],
        eps=float(model.get("norm_eps", 1e-6)),
        moe_top_k=model.get("moe_top_k", 2),
        route_scale=float(model.get("moe_route_scale", 1.0)),
        first_expert=model.get("moe_first_expert", 0),
    )


def forward(params, tokens, model: dict, rows=None, q_block: int = 128):
    """tokens [t] int -> logits [t, vocab] float32, or with
    `rows=(start, stop)` those positions' alone (the layers still run
    over all t). `model` holds `LlamaConfig` keys; `t` is a multiple of
    `q_block` or shorter than it."""
    numbers = _numbers(model)
    t = tokens.shape[0]
    q_block = q_block if t % q_block == 0 else t
    dense = model.get("dense_layers", 0)
    by_kind = _by_kind(model)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        for layer, kind in enumerate(model["layer_kinds"]):
            stack, at = ("dense_layers", layer) if layer < dense else (
                "layers", layer - dense
            )
            mine = {k: v[at] for k, v in params[stack].items()}
            cache = "conv" if len(kind) > 4 and kind[4] else "full"
            of_kind = by_kind[cache].index(layer)
            mine.update(
                (k, v[of_kind]) for k, v in params[f"attn_{cache}"].items()
            )
            mixer = {k: mine[k] for k in MIXER_LEAVES if k in mine}
            if cache == "conv":
                x = _conv(x, mixer, m=numbers)
            else:
                x = _attention(
                    x, mixer, m=numbers, kv=int(kind[1]),
                    theta=float(kind[2]), q_block=q_block,
                )
            x = _ffn(
                x, {k: v for k, v in mine.items() if k not in MIXER_LEAVES},
                m=numbers,
            )
        return _head(x, params, numbers["eps"], rows)
