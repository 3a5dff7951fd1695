"""Plain reference: Trinity-Mini's forward pass and loss in float32, for
one chip's share of its routed experts.

Written from the configuration's published keys (`config.json` of
arcee-ai/Trinity-Mini, `model_type` `afmoe`) and, where the keys do not
carry a term, from the family's public modelling code as ISSUE 55 sets
it down; independent of `ray_tpu/`: dense masks, no kernel, no scan
over layers, no remat, no grouped matmul, no sort. `N_*` is RMSNorm with
a weight, `rms_norm_eps`; layer `l` is of kind `layer_kinds[l]` =
(window, kv heads, theta, sink): window 0 a FULL layer, else a SLIDING
layer (`layer_types`).

    x_0 = E[tokens] * sqrt(dim)                                (mup_enabled)
    a   = N_in(x)
    q = N_q(heads(a Wq))   k = N_k(kv_heads(a Wk))   v = kv_heads(a Wv)   g = a Wg
        (N_q, N_k over a head's dims, one weight for all heads)
    sliding layer: rotary(q, k; theta), the two halves of a head turned
        against each other; keys j with i - window < j <= i (the
        query's own position counts)
    full layer:    NO rotary; keys j <= i
    o = softmax(q k^T / sqrt(head_dim)) v   (heads / kv query heads share a kv head)
    o = o * sigmoid(g);   y = o Wo;   x = x + N_post_attn(y)
    m = N_pre_mlp(x)
    the leading `dense_layers`: f = W2(silu(W3 m) * (W1 m)), width `dense_intermediate`
    the others: s = sigmoid(m Wr) over ALL the router's outputs (float32)
        chosen: the `moe_top_k` largest of s + b (b the expert bias: it
        decides the choice and not the gates, and has no gradient)
        w_e = route_scale * s_e / (sum over the chosen of s + 1e-20)
        f = Shared(m) + sum over the chosen e HELD HERE of w_e Expert_e(m)
        Shared and Expert_e SwiGLUs: down(silu(gate m) * (up m))
    x = x + N_post_mlp(f)
    logits = N_final(x_L) W_head                               (untied)
    loss = mean over positions of (logsumexp(logits) - logits[target])

The program's `init_params` tree is read by its leaf names:
`dense_layers/*` and `layers/*` hold `attn_norm` (N_in), `wq`, `wg`,
`wo`, `attn_post_norm`, `mlp_norm` (N_pre_mlp), `mlp_post_norm` and the
FFN (`w1` up, `w3` gate, `w2` down; or `router`, `router_bias`, the
held experts' `w_gate` `w_up` `w_down` and `shared_gate` `shared_up`
`shared_down`); `attn_window/*` and `attn_full/*` hold what a kind
changes, over that kind's layers in order: `wk`, `wv`, `q_norm`,
`k_norm`.

Departures from the published description, each also in the
configuration's file:
  * ASSUMED (the config has no key for them; `transformers`
    `models/afmoe/modeling_afmoe.py` as ISSUE 55's author knows it, borne
    out by the catalog's `Trinity-Large-Preview` row, "SWA gated",
    "sandwich norm"): the gate `sigmoid(a Wg)` on the attention output;
    a norm on each half's OUTPUT before the residual; q/k norms over a
    head's dims; no rotary on a full layer.
  * The balance term of the published recipe (`load_balance_coeff`
    0.001) and the update rule of the expert bias are left out: the
    config gives a coefficient and no equation. The loss is the
    cross-entropy alone and `b` stays as drawn.
  * The share: the experts are a scan over the held ones
    (`moe_first_expert` and the `moe_experts` - 1 after it) with a mask;
    what the experts held on other chips would add is left out, as the
    program leaves it out (`deployment` in the configuration's file).
    The vocabulary is the slice the file states.

Memory, not mathematics: attention runs `q_block` query rows at a time
under `lax.map`, an FFN as a sum over blocks of its width or over its
experts, each weight upcast alone, and the final norm and head over
`rows`.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmark.reference.llama_ref import _head, _rms_norm, _rotary
from benchmark.reference.mimo_v2_ref import (
    FFN_BLOCK, _by_kind, _glu_sum, _Numbers,
)

#: A layer's leaves that are its attention half's; the others its FFN's.
ATTENTION_LEAVES = (
    "attn_norm", "wq", "wk", "wv", "wg", "wo", "q_norm", "k_norm",
    "attn_post_norm",
)


def shapes(model: dict) -> dict:
    """The plan `weights.make` draws (`weights.py`), every norm and the
    expert bias DRAWN: the tree above at `model`'s sizes."""
    d, heads = model["dim"], model["n_heads"]
    hd = model.get("custom_head_dim") or d // heads
    dense = model.get("dense_layers", 0)
    n, held = model["n_layers"] - dense, model["moe_experts"]
    f, fs = model["intermediate"], model.get("moe_shared_intermediate", 0)
    outputs = model.get("moe_router_experts") or held
    vocab = model["vocab_size"]
    out = {
        "embed": ((vocab, d), "matrix", d),
        "lm_head": ((d, vocab), "matrix", d),
        "final_norm": ((d,), "norm", 0),
    }
    for stack, L in (("dense_layers", dense), ("layers", n)):
        if L:
            out.update({
                f"{stack}/attn_norm": ((L, d), "norm", 0),
                f"{stack}/wq": ((L, d, heads * hd), "matrix", d),
                f"{stack}/wg": ((L, d, heads * hd), "matrix", d),
                f"{stack}/wo": ((L, heads * hd, d), "matrix", heads * hd),
                f"{stack}/attn_post_norm": ((L, d), "norm", 0),
                f"{stack}/mlp_norm": ((L, d), "norm", 0),
                f"{stack}/mlp_post_norm": ((L, d), "norm", 0),
            })
    if dense:
        fd = model["dense_intermediate"]
        out.update({
            "dense_layers/w1": ((dense, d, fd), "matrix", d),
            "dense_layers/w3": ((dense, d, fd), "matrix", d),
            "dense_layers/w2": ((dense, fd, d), "matrix", fd),
        })
    out.update({
        "layers/router": ((n, d, outputs), "matrix", d),
        "layers/router_bias": ((n, outputs), (0.0, 0.1), 0),
        "layers/w_gate": ((n, held, d, f), "matrix", d),
        "layers/w_up": ((n, held, d, f), "matrix", d),
        "layers/w_down": ((n, held, f, d), "matrix", f),
    })
    if fs:
        out.update({
            "layers/shared_gate": ((n, d, fs), "matrix", d),
            "layers/shared_up": ((n, d, fs), "matrix", d),
            "layers/shared_down": ((n, fs, d), "matrix", fs),
        })
    for cache, layers in _by_kind(model).items():
        kv, L = model["layer_kinds"][layers[0]][1], len(layers)
        out.update({
            f"attn_{cache}/wk": ((L, d, kv * hd), "matrix", d),
            f"attn_{cache}/wv": ((L, d, kv * hd), "matrix", d),
            f"attn_{cache}/q_norm": ((L, hd), "norm", 0),
            f"attn_{cache}/k_norm": ((L, hd), "norm", 0),
        })
    return out


@partial(jax.jit, static_argnames=("m", "kind", "q_block"))
def _attention(x, layer, *, m, kind, q_block):
    """x + N_post_attn(Attn(N_in(x))) of one layer of `kind` on x [t, dim]."""
    f32 = jnp.float32
    t = x.shape[0]
    heads, hd = m["n_heads"], m["hd"]
    window, kv, theta = kind
    group = heads // kv
    w = {k: v.astype(f32) for k, v in layer.items()}
    positions = jnp.arange(t)
    a = _rms_norm(x, w["attn_norm"], m["eps"])

    def split(y, n, norm):  # [t, n * hd] -> [n, t, hd], normed, turned
        y = _rms_norm(y.reshape(t, n, hd), norm, m["eps"]).transpose(1, 0, 2)
        return _rotary(y, positions, theta) if window else y

    q = split(a @ w["wq"], heads, w["q_norm"]).reshape(kv, group, t, hd)
    k = split(a @ w["wk"], kv, w["k_norm"])
    v = (a @ w["wv"]).reshape(t, kv, hd).transpose(1, 0, 2)
    # whole blocks of queries: the rows behind the last are cut off
    blocks = -(-t // q_block)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, blocks * q_block - t), (0, 0)))

    def q_rows(start):
        at = jnp.minimum(start + jnp.arange(q_block), t - 1)
        scores = jnp.einsum(
            "vgqd,vkd->vgqk",
            jax.lax.dynamic_slice_in_dim(q, start, q_block, axis=2), k,
        ) * (hd ** -0.5)
        seen = positions[None, :] <= at[:, None]
        if window:
            seen &= positions[None, :] > at[:, None] - window
        scores = jnp.where(seen, scores, -jnp.inf)
        out = jnp.einsum("vgqk,vkd->qvgd", jax.nn.softmax(scores, -1), v)
        return out.reshape(q_block, heads * hd)

    o = jax.lax.map(q_rows, jnp.arange(blocks) * q_block)
    o = o.reshape(blocks * q_block, -1)[:t]
    o = o * jax.nn.sigmoid(a @ w["wg"])
    return x + _rms_norm(o @ w["wo"], w["attn_post_norm"], m["eps"])


@partial(jax.jit, static_argnames=("m",))
def _ffn(x, layer, *, m):
    """x + N_post_mlp(FFN(N_pre_mlp(x))): a dense SwiGLU where `layer`
    has no router, else the shared expert and this chip's share of the
    routed ones."""
    f32 = jnp.float32
    t = x.shape[0]
    h = _rms_norm(x, layer["mlp_norm"].astype(f32), m["eps"])
    if "router" not in layer:
        d, f = layer["w3"].shape
        n = f // FFN_BLOCK if f % FFN_BLOCK == 0 else 1
        out = _glu_sum(
            h, layer["w3"].reshape(d, n, f // n).transpose(1, 0, 2),
            layer["w1"].reshape(d, n, f // n).transpose(1, 0, 2),
            layer["w2"].reshape(n, f // n, d), jnp.ones((t, n), f32),
        )
    else:
        scores = jax.nn.sigmoid(h @ layer["router"].astype(f32))
        _, chosen = jax.lax.top_k(
            scores + jax.lax.stop_gradient(layer["router_bias"].astype(f32)),
            m["moe_top_k"],
        )
        gates = jnp.take_along_axis(scores, chosen, axis=-1)
        gates = m["route_scale"] * gates / (
            jnp.sum(gates, axis=-1, keepdims=True) + 1e-20
        )
        held = m["first_expert"] + jnp.arange(layer["w_gate"].shape[0])
        # a held expert's gate for each token: its own where it was chosen
        mine = jnp.sum(
            jnp.where(chosen[:, :, None] == held, gates[:, :, None], 0.0),
            axis=1,
        )
        out = _glu_sum(
            h, layer["w_gate"], layer["w_up"], layer["w_down"], mine
        )
        if "shared_gate" in layer:
            out = out + _glu_sum(
                h, layer["shared_gate"][None], layer["shared_up"][None],
                layer["shared_down"][None], jnp.ones((t, 1), f32),
            )
    return x + _rms_norm(out, layer["mlp_post_norm"].astype(f32), m["eps"])


def _numbers(model: dict) -> _Numbers:
    if model.get("moe_groups", 1) != 1 or model.get("moe_top_groups", 1) != 1:
        raise ValueError("trinity_ref: the router has one group (n_group 1)")
    if not (model.get("attn_gate") and model.get("post_norms")):
        raise ValueError("trinity_ref: a gate and a norm on each half's output")
    if model.get("qk_norm") not in ("head", True):
        raise ValueError("trinity_ref: q/k norms over a head's dims")
    if any(kind[3] for kind in model["layer_kinds"]):
        raise ValueError("trinity_ref: no sink")
    return _Numbers(
        n_heads=model["n_heads"],
        hd=model.get("custom_head_dim") or model["dim"] // model["n_heads"],
        eps=float(model.get("norm_eps", 1e-6)),
        moe_top_k=model.get("moe_top_k", 2),
        route_scale=float(model.get("moe_route_scale", 1.0)),
        first_expert=model.get("moe_first_expert", 0),
    )


def _hidden(params, tokens, model: dict, q_block: int):
    """The residual stream behind the last layer, [t, dim] float32."""
    numbers = _numbers(model)
    dense = model.get("dense_layers", 0)
    by_kind = _by_kind(model)
    x = params["embed"][tokens].astype(jnp.float32)
    if model.get("embed_scale"):
        x = x * (model["dim"] ** 0.5)
    for layer, kind in enumerate(model["layer_kinds"]):
        kind = (int(kind[0]), int(kind[1]), float(kind[2]))
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
            x, {k: mine[k] for k in ATTENTION_LEAVES},
            m=numbers, kind=kind, q_block=q_block,
        )
        x = _ffn(
            x, {k: v for k, v in mine.items() if k not in ATTENTION_LEAVES},
            m=numbers,
        )
    return x, numbers


def forward(params, tokens, model: dict, rows=None, q_block: int = 128):
    """tokens [t] int -> logits [t, vocab] float32, or with
    `rows=(start, stop)` those positions' alone (the layers still run
    over all t). `model` holds `LlamaConfig` keys."""
    with jax.default_matmul_precision("highest"):
        x, numbers = _hidden(params, tokens, model, min(q_block, len(tokens)))
        return _head(x, params, numbers["eps"], rows)


def loss(params, tokens, targets, model: dict, q_block: int = 128):
    """Mean next-token cross-entropy of tokens [t] against targets [t]
    over the vocabulary held here: what `jax.grad` of is the
    reference's gradient (the balance term is left out, see above)."""
    logits = forward(params, tokens, model, q_block=q_block)
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)
