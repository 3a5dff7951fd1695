"""Plain reference: MiMo-V2-Flash's forward pass in float32, for one
chip's share of its routed experts.

Written from the configuration's published keys (`config.json` of
XiaomiMiMo/MiMo-V2-Flash, `model_type` `mimo_v2_flash`), independent of
`ray_tpu/`: dense masks, no cache, no pages, no ring, no work list, no
batching, no grouped matmul. `h` is a block's input after its RMSNorm
(weight, no bias, `layernorm_epsilon`); blocks are pre-norm residual,
`x <- x + Attn(norm(x))`, `x <- x + FFN(norm(x))`; final norm, untied
head; no biases, no q/k norm.

    layer l is of kind `layer_kinds[l]` = (window, kv heads, theta, sink):
        window 0 a FULL layer, else a WINDOW layer (`hybrid_layer_pattern`)
    q = h Wq -> heads x hd;  k = h Wk -> kv x hd;  v = value_scale * (h Wv) -> kv x vd
    rotary on the FIRST rotary_dim dims of q and k, base theta of the kind,
        the two halves of those dims turned against each other; the rest untouched
    s_ij = q_i . k_j * hd^-1/2      (heads / kv query heads share a kv head)
    full layer:   j <= i
    window layer: i - window < j <= i      (the query's own position counts)
    p_ij = exp(s_ij - m) / (sum_j' exp(s_ij' - m)  [+ exp(b_h - m) with a sink])
        m the largest of the row's terms; the sink b_h, one learned logit a
        head, joins the denominator and carries no value
    o = concat_h(sum_j p_ij v_j) Wo

    FFN of the leading `dense_layers`: Wdown(silu(Wgate h) * (Wup h)), width
        `dense_intermediate` (`w3` the gate, `w1` up, `w2` down)
    of the others: s = sigmoid(h Wr) over ALL the router's outputs (float32);
        chosen: the `moe_top_k` largest of s + router_bias (one group: `n_group` 1)
        g_e = route_scale * s_e / sum over ALL the chosen of s
        y = sum over the chosen e HELD HERE of g_e E_e(h),  E a SwiGLU of
        width `intermediate`; no shared expert

The experts are a scan over the held ones (`moe_first_expert` and the
`moe_experts` - 1 after it) with a mask; what the experts held on other
chips would add is left out, as the program leaves it out (`deployment`
in the configuration's file).

What is ASSUMED where the configuration's keys leave a choice is listed
in the configuration's file under `assumed`, line for line: RMSNorm
under the key `layernorm_epsilon`; no q/k norm; `attention_value_scale`
on the values (the same as on the attention's output); rotary on the
leading dims in transformers' half-split convention; the window counts
the query's own position; the multi-token-prediction layers are not
here (the main model's logits do not depend on them).

Memory, not mathematics: attention runs `q_block` query rows at a time
under `lax.map` (a 15k-token request compiles once), a dense FFN as a
sum over blocks of its width, each weight upcast alone, and the final
norm and head over `rows`.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmark.reference import weights
from benchmark.reference.llama_ref import _head, _rms_norm, _rotary

#: The width of one block of a dense FFN: what bounds the float32
#: copies that live at once.
FFN_BLOCK = 2048
#: A layer's leaves that are its attention's; the others its FFN's.
ATTENTION_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "sink")
#: The deviation the seeded sink logits are drawn with, around 0: with
#: seeded weights a score is about standard normal, so over a window of
#: 128 keys a head's sink takes from nothing to most of the softmax's
#: sum, as trained sinks do.
SINK_DEVIATION = 3.0


def shapes(model: dict) -> dict:
    """The plan `weights.make` draws: `embed`, `lm_head`, `final_norm`
    as `weights.shapes` has them; the stacks `dense_layers/*` (the
    leading dense layers) and `layers/*` (the others), each holding
    its layers' `attn_norm`, `wq`, `wo` and `mlp_norm` beside their FFN
    (`w1` `w3` `w2`, or `router` [d, outputs], `router_bias` and the
    held experts' `w_gate` `w_up` `w_down`); and what a kind of
    attention changes stacked by kind, `attn_full/*` and
    `attn_window/*` over that kind's layers in order: `wk` and `wv` at
    the kind's kv heads, and `sink` [heads] where the kind has one."""
    plain = weights.shapes(dict(model, moe_experts=0))
    out = {k: plain[k] for k in ("embed", "lm_head", "final_norm")}
    d, heads = model["dim"], model["n_heads"]
    hd = model.get("custom_head_dim") or d // heads
    vd = model.get("v_head_dim") or hd
    dense = model.get("dense_layers", 0)
    n, held, f = model["n_layers"] - dense, model["moe_experts"], model["intermediate"]
    outputs = model.get("moe_router_experts") or held
    for stack, L in (("dense_layers", dense), ("layers", n)):
        if L:
            out.update({
                f"{stack}/attn_norm": ((L, d), "norm", 0),
                f"{stack}/wq": ((L, d, heads * hd), "matrix", d),
                f"{stack}/wo": ((L, heads * vd, d), "matrix", heads * vd),
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
        "layers/router_bias": ((n, outputs), (0.0, 0.1), 0),
        "layers/w_gate": ((n, held, d, f), "matrix", d),
        "layers/w_up": ((n, held, d, f), "matrix", d),
        "layers/w_down": ((n, held, f, d), "matrix", f),
    })
    for cache, layers in _by_kind(model).items():
        _, kv, _, sink = model["layer_kinds"][layers[0]]
        L = len(layers)
        out.update({
            f"attn_{cache}/wk": ((L, d, kv * hd), "matrix", d),
            f"attn_{cache}/wv": ((L, d, kv * vd), "matrix", d),
        })
        if sink:
            out[f"attn_{cache}/sink"] = ((L, heads), (0.0, SINK_DEVIATION), 0)
    return out


def _by_kind(model: dict) -> dict:
    """{"full" | "window": that kind's layers in order}."""
    out: dict = {}
    for layer, kind in enumerate(model["layer_kinds"]):
        out.setdefault("window" if kind[0] else "full", []).append(layer)
    return out


def _glu_sum(h, gate_w, up_w, down_w, gates):
    """sum_n gates[:, n] * Wdown_n(silu(Wgate_n h) * (Wup_n h)), one n
    at a time, each upcast alone: gate_w, up_w [n, d, f], down_w
    [n, f, d] in the model's dtype, gates [t, n] float32."""
    def one(y, block):
        gate_w, up_w, down_w, gate = block
        f32 = jnp.float32
        hidden = jax.nn.silu(h @ gate_w.astype(f32)) * (h @ up_w.astype(f32))
        return y + gate[:, None] * (hidden @ down_w.astype(f32)), None

    y, _ = jax.lax.scan(
        one, jnp.zeros_like(h), (gate_w, up_w, down_w, gates.T)
    )
    return y


@partial(jax.jit, static_argnames=("m", "kind", "q_block"))
def _attention(x, layer, *, m, kind, q_block):
    """x + Attn(norm(x)) of one layer of `kind` on x [t, dim]."""
    f32 = jnp.float32
    t = x.shape[0]
    heads, hd, vd, rot = m["n_heads"], m["hd"], m["vd"], m["rotary_dim"]
    window, kv, theta, _ = kind
    group = heads // kv
    w = {k: v.astype(f32) for k, v in layer.items()}
    positions = jnp.arange(t)
    h = _rms_norm(x, w["attn_norm"], m["eps"])

    def turned(a, n):  # [t, n * hd] -> [n, t, hd], rotary on the first dims
        a = a.reshape(t, n, hd).transpose(1, 0, 2)
        return jnp.concatenate(
            [_rotary(a[..., :rot], positions, theta), a[..., rot:]], -1
        )

    q = turned(h @ w["wq"], heads).reshape(kv, group, t, hd)
    k = turned(h @ w["wk"], kv)
    v = (m["value_scale"] * (h @ w["wv"])).reshape(t, kv, vd).transpose(1, 0, 2)

    def q_rows(start):
        at = start + jnp.arange(q_block)
        scores = jnp.einsum(
            "vgqd,vkd->vgqk",
            jax.lax.dynamic_slice_in_dim(q, start, q_block, axis=2), k,
        ) * (hd ** -0.5)
        seen = positions[None, :] <= at[:, None]
        if window:
            seen &= positions[None, :] > at[:, None] - window
        scores = jnp.where(seen, scores, -jnp.inf)
        top = scores.max(axis=-1, keepdims=True)
        if "sink" in w:
            sink = w["sink"].reshape(kv, group, 1, 1)
            top = jnp.maximum(top, sink)
        weights_ = jnp.exp(scores - top)
        total = weights_.sum(axis=-1, keepdims=True)
        if "sink" in w:
            total = total + jnp.exp(sink - top)
        out = jnp.einsum("vgqk,vkd->qvgd", weights_ / total, v)
        return out.reshape(q_block, heads * vd)

    attn = jax.lax.map(q_rows, jnp.arange(0, t, q_block)).reshape(t, -1)
    return x + attn @ w["wo"]


@partial(jax.jit, static_argnames=("m",))
def _ffn(x, layer, *, m):
    """x + FFN(norm(x)): a dense SwiGLU where `layer` has no router,
    else this chip's share of the routed experts."""
    f32 = jnp.float32
    t = x.shape[0]
    h = _rms_norm(x, layer["mlp_norm"].astype(f32), m["eps"])
    if "router" not in layer:
        d, f = layer["w3"].shape
        n = f // FFN_BLOCK if f % FFN_BLOCK == 0 else 1
        return x + _glu_sum(
            h, layer["w3"].reshape(d, n, f // n).transpose(1, 0, 2),
            layer["w1"].reshape(d, n, f // n).transpose(1, 0, 2),
            layer["w2"].reshape(n, f // n, d), jnp.ones((t, n), f32),
        )
    scores = jax.nn.sigmoid(h @ layer["router"].astype(f32))  # [t, outputs]
    _, chosen = jax.lax.top_k(
        scores + layer["router_bias"].astype(f32), m["moe_top_k"]
    )
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True) * m["route_scale"]
    held = m["first_expert"] + jnp.arange(layer["w_gate"].shape[0])
    # a held expert's gate for each token: its own where it was chosen
    mine = jnp.sum(
        jnp.where(chosen[:, :, None] == held, gates[:, :, None], 0.0), axis=1
    )
    return x + _glu_sum(
        h, layer["w_gate"], layer["w_up"], layer["w_down"], mine
    )


class _Numbers(dict):
    """The model's numbers as a static argument of the layers."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _numbers(model: dict) -> _Numbers:
    if model.get("moe_groups", 1) != 1 or model.get("moe_top_groups", 1) != 1:
        raise ValueError("mimo_v2_ref: the router has one group (n_group 1)")
    hd = model.get("custom_head_dim") or model["dim"] // model["n_heads"]
    return _Numbers(
        n_heads=model["n_heads"], hd=hd, vd=model.get("v_head_dim") or hd,
        rotary_dim=model.get("rotary_dim") or hd,
        value_scale=float(model.get("value_scale", 1.0)),
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
            kind = (int(kind[0]), int(kind[1]), float(kind[2]), bool(kind[3]))
            stack, at = ("dense_layers", layer) if layer < dense else (
                "layers", layer - dense
            )
            mine = {k: v[at] for k, v in params[stack].items()}
            cache = "window" if kind[0] else "full"
            of_kind = by_kind[cache].index(layer)
            mine.update(
                (k, v[of_kind]) for k, v in params[f"attn_{cache}"].items()
            )
            x = _attention(
                x, {k: mine[k] for k in ATTENTION_LEAVES if k in mine},
                m=numbers, kind=kind, q_block=q_block,
            )
            x = _ffn(
                x, {k: v for k, v in mine.items() if k not in ATTENTION_LEAVES},
                m=numbers,
            )
        return _head(x, params, numbers["eps"], rows)
