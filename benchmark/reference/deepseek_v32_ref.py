"""Plain reference: DeepSeek-V3.2's forward pass in float32, for one
chip's share of its routed experts.

Written from the published equations (DeepSeek-V2, arXiv:2405.04434,
for the latent attention; DeepSeek-V3, arXiv:2412.19437, for the
router; the DeepSeek-V3.2-Exp report and `inference/model.py` of its
release for the indexer), independent of `ray_tpu/`: no kernel, no
cache, no sort of tokens, no grouped matmul, and the attention NOT
absorbed. `h` is a block's input after its RMSNorm.

    cQ = RMSNorm(h Wq)                      [q_lora_rank]
    q_i = cQ Wq_b -> heads x (nope | rope)  rotary on the rope dims
    kv = h Wkv_a: cKV = RMSNorm(kv[:kv_lora_rank]), kR = rotary(kv[kv_lora_rank:])
    (kN_i | v_i) = cKV Wkv_b                per head: nope key dims, value dims
    score(t, s, i) = (qN_ti . kN_si + qR_ti . kR_s) * scale
    scale = (nope + rope)^-1/2 * m^2,  m = 0.1 ln(factor) + 1   (YaRN)
    softmax over the s in S_t;  out = concat_i(sum_s p v_si) Wo

    qI = cQ Wiq -> index heads x index dim;  kI = LayerNorm(h Wik)
    rotary on the FIRST rope dims of each;  w = h Wiw * heads^-1/2 * dim^-1/2
    I(t, s) = sum_j w_tj ReLU(qI_tj . kI_s)  for s <= t
    S_t = the index_topk largest I(t, .)     (every s <= t while there are fewer)

    s_e = sigmoid(h . Wr_e) over ALL the router's outputs
    chosen by s_e + b_e: a group's mark is the sum of its two largest,
    the best `moe_top_groups` groups stay, the `moe_top_k` largest in them
    g_e = scale_r * s_e / sum over ALL the chosen of s
    y = sum over the chosen e HELD HERE of g_e E_e(h) + E_shared(h)
    E(h) = Wdown(silu(Wgate h) * (Wup h))

The experts are a loop over the held ones (`moe_first_expert` and the
`moe_experts` - 1 after it) with a mask; what the experts held on
other chips would add is left out, as the program leaves it out
(`deployment` in the configuration's file). Leading `dense_layers` run
a dense GLU of width `dense_intermediate`. The selection is a mask
from a plain `top_k` over `I(t, .)`: a key is in `S_t` where its score
reaches the `index_topk`-th largest.

Departures, each also in the configuration's `assumed`:
- float32 at `highest` precision where the release computes in FP8
  and bfloat16; the indexer's Hadamard rotation of qI and kI (an
  orthogonal map applied to both, which leaves their product as it is
  in exact arithmetic and exists for FP8) and its FP8 quantisation are
  not applied;
- rotary turns the two HALVES of the rope dims (dim j with j + rope/2),
  transformers' convention; the release's own code turns neighbours,
  which is this under a fixed permutation of the seeded columns of
  Wq_b, Wkv_a, Wiq and Wik;
- the multi-token-prediction module is not here: the main model's
  logits do not depend on it;
- a group outside the best ones is marked -inf (the release's code;
  transformers marks it 0, the same unless a corrected score is
  negative); LayerNorm's epsilon is 1e-6, the release's.
Memory, not mathematics: attention runs `q_block` query rows and a
group of heads at a time under one scan each (a 16k-token request
compiles once), every FFN as a sum over blocks of its width, each
upcast alone, and the final norm and head over `rows`."""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from benchmark.reference import weights
from benchmark.reference.llama_ref import _head, _rms_norm

#: Heads of one pass over the keys, and the width of one block of an
#: FFN: what bounds the float32 copies that live at once.
HEAD_GROUP = 32
FFN_BLOCK = 2048


def _stack(model: dict, n: int, experts: bool) -> dict:
    d, heads = model["dim"], model["n_heads"]
    qr, kvr = model["q_lora_rank"], model["kv_lora_rank"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    vd = model["v_head_dim"]
    ih, idim = model["index_n_heads"], model["index_head_dim"]
    out = {
        "attn_norm": ((n, d), "norm", 0), "mlp_norm": ((n, d), "norm", 0),
        "wq": ((n, d, qr), "matrix", d), "q_norm": ((n, qr), "norm", 0),
        "wq_b": ((n, qr, heads * (nope + rope)), "matrix", qr),
        "wkv_a": ((n, d, kvr + rope), "matrix", d),
        "kv_norm": ((n, kvr), "norm", 0),
        "wkv_b": ((n, kvr, heads * (nope + vd)), "matrix", kvr),
        "wo": ((n, heads * vd, d), "matrix", heads * vd),
        "wiq": ((n, qr, ih * idim), "matrix", qr),
        "wik": ((n, d, idim), "matrix", d),
        "ik_norm": ((n, idim), "norm", 0), "ik_bias": ((n, idim), "bias", 0),
        "wiw": ((n, d, ih), "matrix", d),
    }
    if not experts:
        f = model["dense_intermediate"]
        out.update({
            "w1": ((n, d, f), "matrix", d), "w3": ((n, d, f), "matrix", d),
            "w2": ((n, f, d), "matrix", f),
        })
        return out
    held, f = model["moe_experts"], model["intermediate"]
    outputs, fs = model["moe_router_experts"], model["moe_shared_intermediate"]
    out.update({
        "router": ((n, d, outputs), "matrix", d),
        # sigmoid scores of seeded tokens spread by about 0.2: a
        # correction of this size moves which experts win
        "router_bias": ((n, outputs), (0.0, 0.1), 0),
        "w_gate": ((n, held, d, f), "matrix", d),
        "w_up": ((n, held, d, f), "matrix", d),
        "w_down": ((n, held, f, d), "matrix", f),
        "shared_gate": ((n, d, fs), "matrix", d),
        "shared_up": ((n, d, fs), "matrix", d),
        "shared_down": ((n, fs, d), "matrix", fs),
    })
    return out


def shapes(model: dict) -> dict:
    """The plan `weights.make` draws: `embed`, `lm_head`, `final_norm`
    as `weights.shapes` has them, and two stacks of latent-attention
    layers, `dense_layers/*` (the leading ones) and `layers/*` (the
    expert layers); every matrix stacked, so the int8 control rounds
    it."""
    plain = weights.shapes(dict(model, moe_experts=0))
    out = {k: plain[k] for k in ("embed", "lm_head", "final_norm")}
    dense = model.get("dense_layers", 0)
    for name, n, experts in (
        ("dense_layers", dense, False),
        ("layers", model["n_layers"] - dense, True),
    ):
        if n:
            out.update({
                f"{name}/{leaf}": plan
                for leaf, plan in _stack(model, n, experts).items()
            })
    return out


def yarn_inv_freq(dim, theta, factor, beta_fast, beta_slow, original):
    """YaRN's blended inverse frequencies of `dim` rotary dims."""
    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(theta)
        )

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    out = []
    for j in range(dim // 2):
        plain = theta ** (-2.0 * j / dim)
        keep = 1.0 - min(max((j - low) / max(high - low, 0.001), 0.0), 1.0)
        out.append(plain / factor * (1.0 - keep) + plain * keep)
    return out


def _rotary(x, positions, inv_freq):
    """x [t, ..., rope]: turn dim j with dim j + rope/2 by the angle of
    the row's position."""
    half = x.shape[-1] // 2
    angles = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)
    angles = angles.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer_norm(x, weight, bias, eps=1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * weight + bias


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


def _blocks(gate_w, up_w, down_w):
    """A dense GLU [d, f], [d, f], [f, d] as blocks of its width."""
    d, f = gate_w.shape
    n = f // FFN_BLOCK if f % FFN_BLOCK == 0 else 1
    return (
        gate_w.reshape(d, n, f // n).transpose(1, 0, 2),
        up_w.reshape(d, n, f // n).transpose(1, 0, 2),
        down_w.reshape(n, f // n, d),
    )


@partial(jax.jit, static_argnames=("m", "q_block"))
def _layer(x, layer, *, m, q_block):
    """One block on x [t, dim] float32. `m` holds the model's numbers
    (`_numbers`); `layer` this layer's weights in the model's dtype."""
    f32 = jnp.float32
    t = x.shape[0]
    heads, nope, rope, vd = m["n_heads"], m["nope"], m["rope"], m["vd"]
    kvr, ih, idim = m["kvr"], m["ih"], m["idim"]
    positions = jnp.arange(t)
    starts = jnp.arange(0, t, q_block)

    def rows(a, start):
        return jax.lax.dynamic_slice_in_dim(a, start, q_block, axis=0)

    small = {
        k: layer[k].astype(f32) for k in (
            "attn_norm", "mlp_norm", "wq", "q_norm", "wkv_a", "kv_norm",
            "wiq", "wik", "ik_norm", "ik_bias", "wiw",
        )
    }
    h = _rms_norm(x, small["attn_norm"], m["eps"])
    cq = _rms_norm(h @ small["wq"], small["q_norm"], m["eps"])
    kv = h @ small["wkv_a"]
    ckv = _rms_norm(kv[:, :kvr], small["kv_norm"], m["eps"])
    kr = _rotary(kv[:, kvr:], positions, m["inv_freq"])

    # -- the indexer: which keys each query attends --------------------
    ki = _layer_norm(h @ small["wik"], small["ik_norm"], small["ik_bias"])
    ki = jnp.concatenate(
        [_rotary(ki[:, :rope], positions, m["inv_freq"]), ki[:, rope:]], -1
    )
    wi = (h @ small["wiw"]) * (ih ** -0.5) * (idim ** -0.5)

    def select(start):
        at = start + jnp.arange(q_block)
        qi = (rows(cq, start) @ small["wiq"]).reshape(q_block, ih, idim)
        qi = jnp.concatenate(
            [_rotary(qi[..., :rope], at, m["inv_freq"]), qi[..., rope:]], -1
        )
        scores = jax.nn.relu(jnp.einsum("qhd,kd->qhk", qi, ki))
        index = jnp.sum(scores * rows(wi, start)[:, :, None], axis=1)
        visible = positions[None, :] <= at[:, None]
        index = jnp.where(visible, index, -jnp.inf)
        kth = jax.lax.top_k(index, min(m["topk"], t))[0][:, -1]
        return visible & (index >= kth[:, None])

    selected = jax.lax.map(select, starts).reshape(t, t)

    # -- attention, a group of heads at a time -------------------------
    hg = HEAD_GROUP if heads % HEAD_GROUP == 0 else heads
    groups = heads // hg

    def by_group(w, width):  # [in, heads * width] -> [groups, in, hg * width]
        return w.reshape(w.shape[0], groups, hg * width).transpose(1, 0, 2)

    def head_group(attn, group):
        wq_b, wkv_b, wo = (w.astype(f32) for w in group)
        q = (cq @ wq_b).reshape(t, hg, nope + rope)
        q_nope = q[..., :nope]
        q_rope = _rotary(q[..., nope:], positions, m["inv_freq"])
        kvb = (ckv @ wkv_b).reshape(t, hg, nope + vd)
        k_nope, v = kvb[..., :nope], kvb[..., nope:]

        def q_rows(start):
            scores = jnp.einsum(
                "qhd,khd->hqk", rows(q_nope, start), k_nope
            ) + jnp.einsum("qhd,kd->hqk", rows(q_rope, start), kr)
            scores = jnp.where(
                rows(selected, start)[None], scores * m["scale"], -jnp.inf
            )
            out = jnp.einsum(
                "hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v
            )
            return out.reshape(q_block, hg * vd) @ wo

        return attn + jax.lax.map(q_rows, starts).reshape(t, -1), None

    attn, _ = jax.lax.scan(
        head_group, jnp.zeros_like(x), (
            by_group(layer["wq_b"], nope + rope),
            by_group(layer["wkv_b"], nope + vd),
            layer["wo"].reshape(groups, hg * vd, -1),
        ),
    )
    x = x + attn

    # -- the FFN -------------------------------------------------------
    h = _rms_norm(x, small["mlp_norm"], m["eps"])
    if "router" not in layer:
        blocks = _blocks(layer["w3"], layer["w1"], layer["w2"])
        return x + _glu_sum(h, *blocks, jnp.ones((t, len(blocks[0])), f32))
    scores = jax.nn.sigmoid(h @ layer["router"].astype(f32))  # [t, outputs]
    corrected = scores + layer["router_bias"].astype(f32)
    n_groups = m["moe_groups"]
    grouped = corrected.reshape(t, n_groups, -1)
    marks = jnp.sort(grouped, axis=-1)[..., -2:].sum(axis=-1)
    worst_kept = jnp.sort(marks, axis=-1)[:, -m["moe_top_groups"]]
    grouped = jnp.where(
        (marks >= worst_kept[:, None])[:, :, None], grouped, -jnp.inf
    )
    _, chosen = jax.lax.top_k(grouped.reshape(t, -1), m["moe_top_k"])
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True) * m["route_scale"]
    held = m["first_expert"] + jnp.arange(layer["w_gate"].shape[0])
    # a held expert's gate for each token: its own where it was chosen
    mine = jnp.sum(
        jnp.where(chosen[:, :, None] == held, gates[:, :, None], 0.0), axis=1
    )
    y = _glu_sum(h, layer["w_gate"], layer["w_up"], layer["w_down"], mine)
    y = y + _glu_sum(
        h, *(w[None] for w in (
            layer["shared_gate"], layer["shared_up"], layer["shared_down"]
        )), jnp.ones((t, 1), f32),
    )
    return x + y


class _Numbers(dict):
    """The model's numbers as a static argument of `_layer`."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _numbers(model: dict) -> _Numbers:
    kind, factor, beta_slow, beta_fast, original = model["rope_scaling"]
    if kind != "yarn":
        raise ValueError(f"deepseek_v32_ref: rope scaling {kind!r} is not yarn")
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    mscale = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    return _Numbers(
        n_heads=model["n_heads"], nope=nope, rope=rope,
        vd=model["v_head_dim"], kvr=model["kv_lora_rank"],
        ih=model["index_n_heads"], idim=model["index_head_dim"],
        topk=model["index_topk"], eps=float(model.get("norm_eps", 1e-6)),
        inv_freq=tuple(yarn_inv_freq(
            rope, float(model.get("rope_theta", 10000.0)), factor,
            beta_fast, beta_slow, original,
        )),
        scale=(nope + rope) ** -0.5 * mscale * mscale,
        moe_groups=model.get("moe_groups", 1),
        moe_top_groups=model.get("moe_top_groups", 1),
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
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        for stack in ("dense_layers", "layers"):
            for i in range(len(params.get(stack, {}).get("attn_norm", ()))):
                layer = {k: v[i] for k, v in params[stack].items()}
                x = _layer(x, layer, m=numbers, q_block=q_block)
        return _head(x, params, numbers["eps"], rows)
